"""Decode (single-token) GQA attention Pallas TPU kernel.

One new query token per sequence attends over a long (possibly ring-buffer)
KV cache. Grid = (B, Kh, num_kv_blocks): each step loads one
(block_k, head_dim) cache tile into VMEM plus that tile's position row
(ring caches store positions per slot), masks invalid/out-of-window slots,
and maintains online-softmax statistics for the G query heads that share
the kv head. The memory term dominates decode (every cache byte is read
once) — exactly what the roofline for decode_32k/long_500k shows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e30
LANES = 128


def _decode_kernel(pos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   window: Optional[int], chunk: Optional[int],
                   block_k: int, num_k: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # [G, d]
    k = k_ref[0, 0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [G, bk]

    pos = pos_ref[0]                                       # scalar in SMEM
    kp = kpos_ref[...]                                     # [1, bk] slot pos
    valid = (kp >= 0) & (kp <= pos)
    if window is not None:
        valid &= pos - kp < window
    if chunk is not None:
        valid &= (pos // chunk) == (kp // chunk)
    logits = jnp.where(valid, logits, NEG)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_k - 1)
    def _out():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, kpos, pos, *, window=None, chunk=None,
                            scale=None, block_k=256, interpret=False):
    """q: [B, H, D] one token; k/v: [B, Kh, C, D]; kpos: [C] slot positions
    (-1 = empty); pos: scalar int32 current position. -> [B, H, D]."""
    B, H, D = q.shape
    Kh, C = k.shape[1], k.shape[2]
    G = H // Kh
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, C)
    assert C % block_k == 0
    nk = C // block_k

    qg = q.reshape(B, Kh, G, D)
    # kpos as one row: a 1-D block gets a different tiled layout from
    # Mosaic than from XLA, which the TPU compiler refuses.
    kpos_row = kpos.reshape(1, C)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               chunk=chunk, block_k=block_k, num_k=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Kh, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # pos scalar
            pl.BlockSpec((1, block_k), lambda b, h, j: (0, j)),  # kpos tile
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, kpos_row, qg, k, v)
    return out.reshape(B, H, D)
