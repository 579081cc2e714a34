"""The dense decoder (``repro.config.DENSE``): its weights, its plain
reference and its FLOP and byte counts.

The reference's mathematics follows the program's dense decoder, which
departs from the published models in ways each configuration file lists
under ``departures``: RMSNorm with a zero-centred gain (1 + g) everywhere
(the published StableLM 2 uses LayerNorm with bias), no q/k/v bias,
rotary embedding on interleaved pairs (x[0::2], x[1::2]) of the first
``rotary_pct`` of each head, SwiGLU MLP, an LM head of its own or, with
``tie_embeddings``, the embedding table's transpose, and full causal
attention (a sliding window only where it is shorter than the sequence).

The segmentation runs one layer program, ``layer``, once per layer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.flops import _attended_keys, head_flops  # noqa: F401
from benchlib.reference import SERVED_VOCAB, _mm, _rms, _rope
from benchlib.weights import head_dim


# ---------------------------------------------------------------- weights
def weight_shapes(m: dict) -> dict:
    """Name -> (shape, fan-in or None for a norm gain), layers stacked on
    axis 0. The q/k/v projections contract over d_model, the output
    projection over heads x head_dim. A model with ``tie_embeddings`` has
    no LM head of its own: the head reads the embedding table, whose
    fan-in is then d_model, as the head's."""
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


# -------------------------------------------------------------- reference
def layer(m: dict, fp8: bool, lw: dict, x):
    """One decoder layer in float32. x: [B, S, D]; lw: this layer's
    weights, float32."""
    eps = m["norm_eps"]
    B, S, _ = x.shape
    H, Kh = m["num_heads"], m["num_kv_heads"]
    Dh = head_dim(m)
    h = _rms(x, lw["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, lw["wq"], fp8)
    k = _mm("bsd,dhk->bshk", h, lw["wk"], fp8)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], fp8)
    if m["qk_norm"]:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q = _rope(q, m["rotary_pct"], m["rope_theta"])
    k = _rope(k, m["rotary_pct"], m["rope_theta"])
    g = H // Kh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    logits = _mm("bqhd,bkhd->bhqk", q, k, fp8) * (Dh ** -0.5)
    qi = np.arange(S)[:, None]
    ki = np.arange(S)[None, :]
    mask = ki <= qi
    window = m.get("sliding_window")
    if window is not None and window < S:
        mask = mask & (qi - ki < window)
    logits = jnp.where(jnp.asarray(mask)[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    a = _mm("bhqk,bkhd->bqhd", p, v, fp8)
    x = x + _mm("bqhd,hdm->bqm", a, lw["wo"], fp8)
    h = _rms(x, lw["ln2"], eps)
    gate = _mm("bsd,df->bsf", h, lw["w_gate"], fp8)
    up = _mm("bsd,df->bsf", h, lw["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"], fp8)


_LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                 "w_down", "q_norm", "k_norm")


@partial(jax.jit, static_argnames=("mkey", "fp8"))
def _layer_step(mkey, fp8, stacked, i, x):
    m = dict(mkey)
    lw = {n: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
          .astype(jnp.float32) for n, a in stacked.items()}
    return layer(m, fp8, lw, x)


@partial(jax.jit, static_argnames=("mkey", "fp8"))
def _embed(mkey, fp8, table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("mkey", "fp8"))
def _head(mkey, fp8, final_norm, lm_head_cols, x):
    m = dict(mkey)
    h = _rms(x, final_norm.astype(jnp.float32), m["norm_eps"])
    return _mm("bsd,dv->bsv", h, lm_head_cols.astype(jnp.float32), fp8)


def _mkey(m: dict):
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "vocab_size", "qk_norm", "rope_theta", "rotary_pct",
            "norm_eps", "sliding_window")
    return tuple((k, m.get(k)) for k in keys)


def served_logits(m: dict, w: dict, tokens, precision: str = "float32"):
    """Logits over the first ``SERVED_VOCAB`` vocabulary entries at every
    position of ``tokens`` [B, S], as float32 numpy."""
    fp8 = {"float32": False, "fp8": True}[precision]
    mk = _mkey(m)
    stacked = {n: w[n] for n in _LAYER_LEAVES if n in w}
    x = _embed(mk, fp8, w["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(m["num_layers"]):
        x = _layer_step(mk, fp8, stacked, jnp.asarray(i, jnp.int32), x)
    cols = (w["lm_head"][:, :SERVED_VOCAB] if "lm_head" in w
            else w["embed"][:SERVED_VOCAB].T)
    return np.asarray(_head(mk, fp8, w["final_norm"], cols, x), np.float32)


# ------------------------------------------------------------------ counts
def layer_params(m: dict) -> int:
    """Matrix parameters of one layer (the norm gains are left out)."""
    D, H, Kh, F = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_ff"]
    Dh = head_dim(m)
    return D * H * Dh * 2 + D * Kh * Dh * 2 + 3 * D * F


def layer_flops(m: dict, batch: int, seq: int) -> int:
    H, Dh = m["num_heads"], head_dim(m)
    pairs = _attended_keys(seq, m.get("sliding_window"))
    return (2 * layer_params(m) * batch * seq
            + 2 * 2 * batch * H * Dh * pairs)


def layer_bytes(m: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    act = batch * seq * m["d_model"] * itemsize
    return layer_params(m) * itemsize + 2 * act


def layer_programs(m: dict, batch: int, seq: int) -> dict:
    """The one layer program, run once per layer."""
    return {"layer": (m["num_layers"], layer_flops(m, batch, seq),
                      layer_bytes(m, batch, seq))}
