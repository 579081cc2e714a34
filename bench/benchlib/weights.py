"""Weights of one served model, drawn from the run's seed.

The benchmark makes the weights itself, on the device, in one jitted call
per model and in the served dtype; the program under test receives them
as its parameter tree, and the reference draws them again from the same
seed after the program's state is freed.

Which leaves a model has, with their shapes and fan-ins, and how they
are arranged as the program's tree, is its family's
(``families/<family>.py``: ``weight_shapes``, ``program_tree``). The
drawing is shared: each matrix is normal with standard deviation
1/sqrt(fan-in), the fan-in being the product of the axes it contracts
over; each norm gain g (applied as 1 + g) is 0.1 x normal, so the norms
are exercised. Leaf ``name`` is drawn from the seed's key folded with
crc32(name). The program's own initializer is not used: it takes the
fan-in of the q/k/v projections from the head axis, which makes every
attention softmax nearly one-hot, and bfloat16 rounding then flips which
key wins, so no float32 reference can follow it past a few layers
(PERF.md, Findings).
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


def seed32(seed: int) -> int:
    """The run's seed (any whole number) folded to the 32 bits a JAX key
    holds, so that seeds above 2**32 stay distinct."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _spec(shapes: dict):
    return tuple(sorted((n, s, f) for n, (s, f) in shapes.items()))


@partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, spec, dtype):
    out = {}
    for name, shape, fan_in in spec:
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        std = NORM_GAIN_STD if fan_in is None else 1.0 / np.sqrt(fan_in)
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * std).astype(dtype)
    return out


def make_weights(family, m: dict, seed: int) -> dict:
    """Every weight of model ``m`` of program family ``family`` (its
    ``benchlib.families`` module) from ``seed``, flat by name."""
    return _draw(jax.random.key(seed32(seed)),
                 _spec(family.weight_shapes(m)), m["dtype"])
