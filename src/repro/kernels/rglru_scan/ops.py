"""Public jit'd wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rglru_scan.kernel import rglru_scan_kernel
from repro.kernels.rglru_scan.ref import rglru_scan_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=(
    "block_b", "block_w", "block_s", "interpret"))
def rglru_scan(a, b, h0, *, block_b=8, block_w=128, block_s=128,
               interpret=False):
    return rglru_scan_kernel(a, b, h0, block_b=block_b, block_w=block_w,
                             block_s=block_s, interpret=interpret)
