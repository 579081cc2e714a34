"""Operations and bytes a model's algorithm needs, from shapes: what every
program family shares.

These count what the model requires, not what a compiled program happens
to do, so they stay fixed while the program changes. Operations are the
matrix products (2 per multiply-add); norms, rotary embedding and the
softmax are left out. Causal attention counts each query against the
keys at or before it (inside the window, where one is set). Bytes are
the least a call must move: its weights once, its input and its output.

Each family counts its own layer programs (``families/<family>.py``:
``layer_programs``) and names its head's count (``head_flops``); the
counts of a request and a call's least time are shared here.
"""
from __future__ import annotations


def _attended_keys(S: int, window) -> int:
    """Query-key pairs of causal attention over S positions."""
    if window is None or window >= S:
        return S * (S + 1) // 2
    w = window
    return w * (w + 1) // 2 + (S - w) * w


def head_flops(m: dict, batch: int, seq: int) -> int:
    """An LM head over the whole vocabulary at every position."""
    return 2 * batch * seq * m["d_model"] * m["vocab_size"]


def request_flops(family, m: dict, batch: int, seq: int) -> int:
    """One request of model ``m`` of program family ``family`` (its
    ``benchlib.families`` module): every call of every layer program and
    the head (the embedding is a gather)."""
    layers = sum(calls * f for calls, f, _ in
                 family.layer_programs(m, batch, seq).values())
    return layers + family.head_flops(m, batch, seq)


def least_time(flops: int, nbytes: int, peak: dict):
    """Roofline time of a call and the bound that sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
