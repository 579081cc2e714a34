"""Plain float32 reference of the dense decoder the benchmark serves.

Independent of the program: it imports nothing from ``repro``, and the
weights it runs on are drawn again from the seed (``weights``) once the
program's state is freed, so it never reads a weight, a scale or a table
that the program holds.

The mathematics follows the program's dense decoder, which departs from
the published models in ways each configuration file lists under
``departures``: RMSNorm with a zero-centred gain (1 + g) everywhere (the
published StableLM 2 uses LayerNorm with bias), no q/k/v bias, rotary
embedding on interleaved pairs (x[0::2], x[1::2]) of the first
``rotary_pct`` of each head, SwiGLU MLP, an LM head of its own or, with
``tie_embeddings``, the embedding table's transpose, and full causal
attention (a sliding window only where it is shorter than
the sequence).

The forward pass runs layer by layer in float32 at ``highest`` matmul
precision, so it fits beside nothing else on one chip: the stacked
weights stay in the served type (bfloat16) and one layer at a time is
cast up. ``precision="fp8"`` is the control: every matmul operand is
rounded to float8 e4m3 with a per-tensor scale, the step below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.weights import head_dim

#: logits the served path reads: the head's host sampling takes the
#: argmax over the first ``SERVED_VOCAB`` entries of the vocabulary
SERVED_VOCAB = 64

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------- forward
def _q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, rotary_pct, theta):
    """Rotate interleaved pairs of the first rotary_pct of each head."""
    S, Dh = x.shape[1], x.shape[-1]
    rot = int(Dh * rotary_pct) // 2 * 2
    if rot == 0:
        return x
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


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


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each given token's
    reference logit lies, per position (0 where they agree)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None].astype(np.int64),
                             axis=-1)[..., 0]
    return best - got
