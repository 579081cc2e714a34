"""The plain float32 reference: what every program family shares.

Independent of the program: it imports nothing from ``repro``, and the
weights it runs on are drawn again from the seed (``weights``) once the
program's state is freed, so it never reads a weight, a scale or a table
that the program holds.

Shared here: the served slice of the vocabulary (``SERVED_VOCAB``), the
gap a served token is judged by (``token_gaps``), and the pieces every
family's forward pass is built of: the matrix product at ``highest``
precision with its float8 control (``_mm``, ``_q8``), RMSNorm with a
zero-centred gain (``_rms``) and rotary embedding on interleaved pairs
(``_rope``). Per family, in ``families/<family>.py``: the layer, the
embedding and the head, and ``served_logits``, the forward pass over a
prompt.

A family's forward pass runs layer by layer in float32 at ``highest``
matmul precision, so it fits beside nothing else on one chip: the
stacked weights stay in the served type (bfloat16) and one layer at a
time is cast up. ``precision="fp8"`` is the control: every matmul operand
is rounded to float8 e4m3 with a per-tensor scale, the step below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each given token's
    reference logit lies, per position (0 where they agree)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None].astype(np.int64),
                             axis=-1)[..., 0]
    return best - got
