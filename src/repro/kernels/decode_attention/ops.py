"""Public jit'd wrapper for the decode attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=(
    "window", "chunk", "scale", "block_k", "interpret"))
def decode_attention(q, k, v, kpos, pos, *, window=None, chunk=None,
                     scale=None, block_k=256, interpret=False):
    return decode_attention_kernel(
        q, k, v, kpos, pos, window=window, chunk=chunk, scale=scale,
        block_k=block_k, interpret=interpret)
