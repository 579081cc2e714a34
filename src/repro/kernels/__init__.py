"""Pallas TPU kernels for the serving stack's compute hot spots.

FIKIT itself is pure scheduling infrastructure (no device-side compute
contribution); these kernels are the perf-critical layers of the models the
scheduler serves. Each kernel ships as a trio:

    <name>/kernel.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
    <name>/ops.py     — jit'd public wrapper (pass interpret=True off TPU)
    <name>/ref.py     — pure-jnp oracle used by the allclose tests
"""
from repro.kernels.decode_attention.ops import decode_attention  # noqa: F401
from repro.kernels.flash_attention.ops import flash_attention  # noqa: F401
from repro.kernels.rglru_scan.ops import rglru_scan  # noqa: F401
