"""Operations and bytes the dense decoder's algorithm needs, from shapes.

These count what the model requires, not what a compiled program happens
to do, so they stay fixed while the program changes. Operations are the
matrix products (2 per multiply-add); norms, rotary embedding and the
softmax are left out. Causal attention counts each query against the
keys at or before it (inside the window, where one is set). Bytes are
the least a call must move: its weights once, its input and its output.
"""
from __future__ import annotations

from benchlib.weights import head_dim


def _attended_keys(S: int, window) -> int:
    """Query-key pairs of causal attention over S positions."""
    if window is None or window >= S:
        return S * (S + 1) // 2
    w = window
    return w * (w + 1) // 2 + (S - w) * w


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


def head_flops(m: dict, batch: int, seq: int) -> int:
    return 2 * batch * seq * m["d_model"] * m["vocab_size"]


def request_flops(m: dict, batch: int, seq: int) -> int:
    """One request: every layer and the head (the embedding is a gather)."""
    return (m["num_layers"] * layer_flops(m, batch, seq)
            + head_flops(m, batch, seq))


def least_time(flops: int, nbytes: int, peak: dict):
    """Roofline time of a call and the bound that sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
