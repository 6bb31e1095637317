"""The self-attention KV cache, for every family: a buffer stacked per segment
or invocation, (layers, B, w, ...), holds position p at slot ``p % w``.  A
decode step reads it where it lies (``mask`` leaves out the token's slot, and
the token attends to its own K and V as one extra key); its trunk writes every
layer's token once, after its layer loop (``write``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.scopes import scope

SLOT_AXIS = 2  # of a stacked buffer
SELF_ATTN = ("k", "v", "ckv", "krope")  # buffers with a slot per position


def window(cfg: ModelConfig, seq_len: int) -> int:
    """Slots for ``seq_len`` positions: a ring of ``cfg.window`` under
    sliding-window attention, every position otherwise."""
    return min(cfg.window, seq_len) if cfg.attn_kind == "swa" else seq_len


def from_prefill(cfg: ModelConfig, kv):
    """A layer's cache from a prefill's (B, S, KVH, Dh) keys and values: the
    last ``w`` positions, position p at slot ``p % w``."""
    s = kv["k"].shape[1]
    w = window(cfg, s)
    if w == s:
        return kv
    with scope("attn_core"):
        sl = (jnp.arange(w) + (s - w)) % w
        return {n: jnp.zeros((a.shape[0], w, *a.shape[2:]), a.dtype).at[:, sl].set(a[:, s - w:])
                for n, a in kv.items()}


def mask(cfg: ModelConfig, pos: jax.Array, w: int) -> jax.Array:
    """(w,) bool: the resident slots a token at ``pos`` attends to, all but
    ``pos % w``, the one it will take."""
    slots = jnp.arange(w)
    if cfg.attn_kind == "swa":
        # ring buffer: slot i holds absolute position pos - ((pos - i) mod w);
        # everything resident is inside the window by construction.
        m = pos - jnp.mod(pos - slots, w) >= 0
    else:
        m = slots <= pos
    m &= slots != pos % w
    return m


def attend(cfg: ModelConfig, q, cache, k_new, v_new, pos: jax.Array):
    """A token's attention over a layer's cache where it lies and over its own
    K and V: (output, the token's K and V in the cache's dtype, for ``write``)."""
    new = {"k": k_new.astype(cache["k"].dtype), "v": v_new.astype(cache["v"].dtype)}
    m = mask(cfg, pos, cache["k"].shape[1])
    return nn.decode_attention(q, cache["k"], cache["v"], m, new["k"], new["v"]), new


def write(cache, new, pos: jax.Array):
    """Each buffer of ``new`` (layers, B, 1, ...) written at slot ``pos % w``
    of ``cache``, one update per buffer: in place where it is donated."""
    def put(c, n):
        start = [0] * c.ndim
        start[SLOT_AXIS] = pos % c.shape[SLOT_AXIS]
        return jax.lax.dynamic_update_slice(c, n, start)

    with scope("attn_core"):
        return jax.tree.map(put, cache, new)


def grow(cfg: ModelConfig, cache, total_len: int):
    """A prefill's cache with room for ``total_len`` positions: self-attention
    buffers padded to ``window(cfg, total_len)`` slots; SSM states and cross
    K/V, which hold no slot per position, as they are."""
    def pad(stack):
        slots = window(cfg, total_len)
        return {n: jnp.pad(a, [(0, slots - a.shape[i]) if i == SLOT_AXIS else (0, 0)
                               for i in range(a.ndim)]) if n in SELF_ATTN else a
                for n, a in stack.items()}

    if cfg.family in ("dense", "moe", "vlm"):  # {segment: stack}
        return {seg: pad(c) for seg, c in cache.items()}
    if cfg.family == "hybrid":  # {"attn": stack, "ssm<g>": SSM state}
        return {**cache, "attn": pad(cache["attn"])}
    if cfg.family == "audio":  # {k, v: self; ck, cv: cross}
        return pad(cache)
    return cache  # ssm
