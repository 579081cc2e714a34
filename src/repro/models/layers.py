"""Common layers: RMSNorm, rotary embeddings, gated MLP, initializers."""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
# Each init is one jitted program, so the float32 draw is fused into the
# cast and never held on the device beside the stored leaf.
@partial(jax.jit, static_argnames=("shape", "dtype", "scale"))
def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def rms_norm_params(dim: int, dtype) -> jax.Array:
    # stored as zero-centered scale (gemma convention: weight = 1 + gamma)
    return jnp.zeros((dim,), dtype=dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (partial rotation supported)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, rotary_pct: float, theta: float):
    rot_dim = int(head_dim * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot_dim, 2, dtype=np.float64) / rot_dim))
    return rot_dim, jnp.asarray(inv, jnp.float32)


def apply_rope(x: jax.Array, positions: jax.Array, rotary_pct: float,
               theta: float) -> jax.Array:
    """x: [..., S, H, Dh]; positions: broadcastable to [..., S]."""
    head_dim = x.shape[-1]
    rot_dim, inv = rope_freqs(head_dim, rotary_pct, theta)
    if rot_dim == 0:
        return x
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., S, rot/2]
    cos = jnp.cos(ang)[..., None, :]                      # [..., S, 1, rot/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------
def mlp_params(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp_shapes(d_model: int, d_ff: int, dtype):
    sds = jax.ShapeDtypeStruct
    return {
        "w_gate": sds((d_model, d_ff), dtype),
        "w_up": sds((d_model, d_ff), dtype),
        "w_down": sds((d_ff, d_model), dtype),
    }


# ---------------------------------------------------------------------------
# Param maker: one code path produces either real arrays (key given) or
# jax.ShapeDtypeStruct stand-ins (key=None) so dry-runs never allocate.
# ---------------------------------------------------------------------------
class Maker:
    def __init__(self, key, dtype):
        self.key = key
        self.dtype = jnp.dtype(dtype)

    def __call__(self, name: str, shape, kind: str = "dense",
                 scale: float = 1.0):
        if self.key is None:
            return jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        k = jax.random.fold_in(self.key, zlib.crc32(name.encode()) % (2 ** 31))
        if kind == "dense":
            return dense_init(k, tuple(shape), self.dtype, scale)
        if kind == "embed":
            return embed_init(k, tuple(shape), self.dtype)
        if kind == "zeros":
            return jnp.zeros(tuple(shape), self.dtype)
        if kind == "ones":
            return jnp.ones(tuple(shape), self.dtype)
        if kind == "f32":
            return jnp.zeros(tuple(shape), jnp.float32)
        raise ValueError(kind)

    def f32(self, name: str, shape):
        if self.key is None:
            return jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        return jnp.zeros(tuple(shape), jnp.float32)


def mlp_build(make: Maker, d_model: int, d_ff: int, prefix: str = "",
              stack: tuple = ()):
    s = tuple(stack)
    return {
        "w_gate": make(prefix + "w_gate", s + (d_model, d_ff)),
        "w_up": make(prefix + "w_up", s + (d_model, d_ff)),
        "w_down": make(prefix + "w_down", s + (d_ff, d_model)),
    }


def mlp_apply(p, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"])
