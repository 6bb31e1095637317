"""Plain f32 pieces that the references of the model-files modules
(``models/<name>.py``) share, written from the published descriptions and
independent of the program's ``models/`` code: RMSNorm, causal attention in
blocks of query rows (so that it fits on the chip at the published widths),
and the padding of one sequence to whole blocks.  A module's reference runs
every matmul at ``highest`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
NEG = -1e30


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_attention(q, k, v, window: int = 0):
    """Softmax attention of one sequence: q and k (S, H, Dh), v (S, H, Dv),
    every head its own keys; a query sees the keys at or before it, within
    ``window`` of it where that is set.  Returns (S, H * Dv)."""
    s, h, dh = q.shape
    pos = jnp.arange(s)
    qb = min(Q_BLOCK, s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) / np.sqrt(dh)
        qpos = i * qb + jnp.arange(qb)
        keep = pos[None, :] <= qpos[:, None]
        if window:
            keep &= pos[None, :] > qpos[:, None] - window
        pr = jax.nn.softmax(jnp.where(keep[None], sc, NEG), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v)

    return jax.lax.map(jax.checkpoint(block), jnp.arange(s // qb)).reshape(s, h * v.shape[-1])


def padded(tokens: np.ndarray) -> np.ndarray:
    """The sequence padded at its end to a whole number of query blocks;
    causal attention keeps the padding out of every position before it."""
    out = np.zeros(-(-len(tokens) // Q_BLOCK) * Q_BLOCK, np.int32)
    out[:len(tokens)] = tokens
    return out


def static(m: dict) -> tuple:
    """The configuration's scalar sizes as a hashable key, for a jitted
    function that takes them as a static argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool)) or v is None))
