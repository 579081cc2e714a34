"""Public jit'd wrapper for the flash attention kernel.

The kernel compiles for the TPU. Off the TPU, pass ``interpret=True`` to
run its body in the Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "chunk", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, chunk=None,
                    scale=None, block_q=128, block_k=128, interpret=False):
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, chunk=chunk, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
