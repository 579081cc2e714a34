"""Weights of one served model, drawn from the run's seed.

The benchmark makes the weights itself, on the device, in one jitted call
per model and in the served dtype; the program under test receives them
as its parameter tree, and the reference draws them again from the same
seed after the program's state is freed.

Each matrix is normal with standard deviation 1/sqrt(fan-in), the fan-in
being the product of the axes it contracts over (d_model for the q/k/v
projections, heads x head_dim for the output projection); each norm gain
g (applied as 1 + g) is 0.1 x normal, so the norms are exercised. A
model with ``tie_embeddings`` has no LM head of its own: the head reads
the embedding table, whose fan-in is then d_model, as the head's. Leaf
``name`` is drawn from the seed's key folded with crc32(name). The
program's own initializer is not used: it takes the fan-in of the q/k/v
projections from the head axis, which makes every attention softmax
nearly one-hot, and bfloat16 rounding then flips which key wins, so no
float32 reference can follow it past a few layers (PERF.md, Findings).
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NORM_GAIN_STD = 0.1


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def weight_shapes(m: dict) -> dict:
    """Name -> (shape, fan-in or None for a norm gain), layers stacked on
    axis 0."""
    L, D, V = m["num_layers"], m["d_model"], m["vocab_size"]
    H, Kh, F = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    Dh = head_dim(m)
    tied = m.get("tie_embeddings", False)
    out = {
        "embed": ((V, D), D if tied else 1),
        "ln1": ((L, D), None),
        "wq": ((L, D, H, Dh), D),
        "wk": ((L, D, Kh, Dh), D),
        "wv": ((L, D, Kh, Dh), D),
        "wo": ((L, H, Dh, D), H * Dh),
        "ln2": ((L, D), None),
        "w_gate": ((L, D, F), D),
        "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F),
        "final_norm": ((D,), None),
    }
    if not tied:
        out["lm_head"] = ((D, V), D)
    if m["qk_norm"]:
        out["q_norm"] = ((L, Dh), None)
        out["k_norm"] = ((L, Dh), None)
    return out


def seed32(seed: int) -> int:
    """The run's seed (any whole number) folded to the 32 bits a JAX key
    holds, so that seeds above 2**32 stay distinct."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _spec(m: dict):
    return tuple(sorted((n, s, f) for n, (s, f) in weight_shapes(m).items()))


@partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, spec, dtype):
    out = {}
    for name, shape, fan_in in spec:
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        std = NORM_GAIN_STD if fan_in is None else 1.0 / np.sqrt(fan_in)
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * std).astype(dtype)
    return out


def make_weights(m: dict, seed: int) -> dict:
    """Every weight of model ``m`` from ``seed``, flat by name."""
    return _draw(jax.random.key(seed32(seed)), _spec(m), m["dtype"])


def program_tree(m: dict, w: dict) -> dict:
    """The flat weights arranged as the program's dense-decoder
    parameter tree."""
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if n in w}
    tree = {
        "embed": w["embed"],
        "layers": {"ln1": w["ln1"], "attn": attn, "ln2": w["ln2"],
                   "mlp": {n: w[n] for n in ("w_gate", "w_up", "w_down")}},
        "final_norm": w["final_norm"],
    }
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    return tree
