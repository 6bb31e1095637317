"""Plain f32 reference of the dense decoder trunk, written from the published
description (Llama-style pre-norm block: RMSNorm, rotary attention with
grouped key/value heads and an optional sliding window, SwiGLU MLP) and
independent of the program's ``models/`` code.

Every matmul runs at ``highest`` precision.  One sequence at a time, layer by
layer under ``lax.scan``, and attention in blocks of query rows, so that it
fits on the chip at the published widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
NEG = -1e30


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, Dh); rotate the two halves of each head (Llama's rotate_half)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m, x, p):
    s = x.shape[0]
    h, kvh, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, window = m["rms_norm_eps"], m.get("sliding_window") or 0
    pos = jnp.arange(s)
    qb = min(Q_BLOCK, s)
    a = p["attn"]
    y = _rms(x, p["ln1"], eps)
    q = _rope((y @ a["wq"]).reshape(s, h, dh), pos, m["rope_theta"])
    k = _rope((y @ a["wk"]).reshape(s, kvh, dh), pos, m["rope_theta"])
    v = (y @ a["wv"]).reshape(s, kvh, dh)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) / np.sqrt(dh)
        qpos = i * qb + jnp.arange(qb)
        keep = pos[None, :] <= qpos[:, None]
        if window:
            keep &= pos[None, :] > qpos[:, None] - window
        pr = jax.nn.softmax(jnp.where(keep[None], sc, NEG), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(s // qb)).reshape(s, h * dh)
    x = x + o @ a["wo"]
    y = _rms(x, p["ln2"], eps)
    f = p["ffn"]
    g = y @ f["w_gate"]
    return x + ((g * jax.nn.sigmoid(g)) * (y @ f["w_up"])) @ f["w_down"]


@functools.partial(jax.jit, static_argnums=0)
def _logits_at(mkey, master, tokens, read):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        x = master["embed"][tokens]
        x, _ = jax.lax.scan(lambda c, p: (_layer(m, c, p), None), x, master["seg0"])
        x = _rms(x[read], master["ln_f"], m["rms_norm_eps"])
        head = master["embed"].T if m["tie_word_embeddings"] else master["lm_head"]
        return x @ head


def logits_at(m: dict, master: dict, tokens: np.ndarray, read: np.ndarray) -> jax.Array:
    """f32 logits (len(read), V) of one sequence at positions ``read``.

    The sequence is padded at its end to a whole number of query blocks;
    causal attention keeps the padding out of every position read."""
    s = len(tokens)
    padded = np.zeros(-(-s // Q_BLOCK) * Q_BLOCK, np.int32)
    padded[:s] = tokens
    return _logits_at(_mkey(m), master, jnp.asarray(padded), jnp.asarray(read, jnp.int32))


def _mkey(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool)) or v is None))
