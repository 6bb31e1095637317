"""Shared NN machinery: parameter specs (single source of truth for shapes,
logical sharding axes, and initializers), norms, rotary embeddings, and the
memory-bounded chunked attention used by every attention-bearing arch.

Parameters are plain nested dicts of arrays.  Every leaf has a companion
``ParamSpec`` carrying its *logical axis names* — ``runtime/sharding.py`` maps
logical names to mesh axes (``NamedSharding``), which is how the same model
definition runs on 1 CPU device, a 16x16 pod, or the 2x16x16 multi-pod mesh.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis names (None = replicated dim)
    init: str = "normal"  # normal | zeros | ones
    scale: float = 1.0  # stddev multiplier for "normal"

    def with_prefix(self, n: int, axis_name: str = "layers") -> "ParamSpec":
        return ParamSpec((n,) + self.shape, (axis_name,) + self.axes, self.init, self.scale)


def spec_tree_map(fn: Callable[[ParamSpec], Any], specs: PyTree) -> PyTree:
    return jax.tree.map(fn, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_specs(specs: PyTree, n: int) -> PyTree:
    """Prepend a scanned ``layers`` dimension to every spec in the tree."""
    return spec_tree_map(lambda s: s.with_prefix(n), specs)


def abstract_params(specs: PyTree, dtype=jnp.float32) -> PyTree:
    return spec_tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs)


def param_axes(specs: PyTree) -> PyTree:
    return spec_tree_map(lambda s: s.axes, specs)


def init_params(specs: PyTree, key: jax.Array, dtype=jnp.float32) -> PyTree:
    """Materialize real parameters (smoke tests / examples)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))

    def one(s: ParamSpec, k):
        if s.init == "zeros":
            return jnp.zeros(s.shape, dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, dtype)
        if s.init == "s4d":  # A_log init: log(1..N) along the last (state) dim
            row = jnp.log(jnp.arange(1, s.shape[-1] + 1, dtype=jnp.float32))
            return jnp.broadcast_to(row, s.shape).astype(dtype)
        if s.init == "dt_bias":  # softplus^-1 of dt ~ U[1e-3, 1e-1]
            u = jax.random.uniform(k, s.shape, jnp.float32, 1e-3, 1e-1)
            return jnp.log(jnp.expm1(u)).astype(dtype)
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        std = s.scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, s.shape, jnp.float32) * std).astype(dtype)

    return jax.tree.unflatten(treedef, [one(s, k) for s, k in zip(leaves, keys)])


def param_bytes(specs: PyTree, bytes_per_el: int = 4) -> int:
    total = 0
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec)):
        total += int(np.prod(s.shape)) * bytes_per_el
    return total


def param_count(specs: PyTree) -> int:
    return sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    )


# --------------------------------------------------------------------------
# basic ops
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(x.dtype))
    return jnp.einsum("...f,fd->...d", silu(g) * u, w_down.astype(x.dtype))


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (..., S, 1, Dh/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq_len: int, d_model: int, offset: jax.Array | int = 0) -> jax.Array:
    pos = (jnp.arange(seq_len) + offset)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)
    ang = pos / jnp.power(10_000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe


# --------------------------------------------------------------------------
# attention — memory-bounded chunked softmax attention (the XLA path).
# Every model calls this jnp version, on the TPU too; the Pallas flash
# kernel (kernels/flash_attention) is numerically equivalent but no model
# calls it yet.  The dry-run lowers this path (keeps cost_analysis()
# transparent — see DESIGN.md §3).
# --------------------------------------------------------------------------


def attention(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Skv, KVH, Dh)
    v: jax.Array,  # (B, Skv, KVH, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    q_offset: jax.Array | int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked attention. Peak memory O(B*H*chunk*Skv) instead of O(B*H*Sq*Skv).

    ``q_offset``: absolute position of q[:, 0] (decode: the write position).
    """
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kv_pos = jnp.arange(skv)

    if sq <= chunk:
        q_pos = jnp.arange(sq) + q_offset
        return _attn_chunk_masked(
            q, k, v, q_pos, kv_pos, causal=causal, window=window, scale=scale
        )

    n = sq // chunk
    assert sq % chunk == 0, f"seq {sq} % attn chunk {chunk}"
    qc = q.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)  # (n, B, C, H, Dh)

    def body(_, i):
        q_pos = i * chunk + jnp.arange(chunk) + q_offset
        o = _attn_chunk_masked(
            qc[i], k, v, q_pos, kv_pos, causal=causal, window=window, scale=scale
        )
        return None, o

    _, outs = jax.lax.scan(body, None, jnp.arange(n))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, v.shape[-1])


def decode_attention(
    q: jax.Array,  # (B, 1, H, Dh)
    k: jax.Array,  # (B, S, KVH, Dh), the cache as stored
    v: jax.Array,  # (B, S, KVH, Dv)
    mask: jax.Array,  # (S,) bool: which cache slots the token attends to
    k_new: jax.Array,  # (B, 1, KVH, Dh): the token's own key
    v_new: jax.Array,  # (B, 1, KVH, Dv)
) -> jax.Array:
    """One token's attention over a KV cache, as a grouped-query einsum.

    q's heads are viewed as (KVH, H // KVH) groups, so each cached K and V
    element is read once, in its own dtype, and never copied per query head
    (``G = 1`` is MHA).  Scores, mask and softmax in f32; the weighted sum
    takes bf16 probabilities with f32 accumulation.

    The token also attends to its own K and V as one extra key, in the same
    softmax, so that the cache is read where it lies and written by the
    caller afterwards (``mask`` leaves out the slot the token will take).
    """
    b, _, h, dh = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, kvh, h // kvh, dh)
    scale = jnp.sqrt(jnp.asarray(dh, jnp.float32))
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32) / scale
    scores = jnp.where(mask, scores, -1e30)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new[:, 0], preferred_element_type=jnp.float32
    ) / scale
    m = jnp.maximum(scores.max(axis=-1), s_new)
    e = jnp.exp(scores - m[..., None])
    e_new = jnp.exp(s_new - m)
    denom = e.sum(axis=-1) + e_new
    probs = (e / denom[..., None]).astype(v.dtype)
    p_new = (e_new / denom).astype(v.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", probs, v, preferred_element_type=jnp.float32)
    o += jnp.einsum("bkg,bkd->bkgd", p_new, v_new[:, 0], preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, v.shape[-1]).astype(q.dtype)


def repeat_kv(k: jax.Array, h: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, H, D), for prefill and training only (decode
    reads the cache as stored, ``decode_attention``).  Materializing the
    repeat (instead of a grouped einsum) lets the TP axis shard the full
    `heads` dim — sharding the raw kv_heads dim (often 8) on a 16-way model
    axis would pad 2x."""
    kvh = k.shape[2]
    if kvh == h:
        return k
    return jnp.repeat(k, h // kvh, axis=2)


def _attn_chunk_masked(q, k, v, q_pos, kv_pos, *, causal, window, scale):
    b, c, h, dh = q.shape
    k = repeat_kv(k, h)
    v = repeat_kv(v, h)
    # f32 ACCUMULATION via preferred_element_type — never materialize an f32
    # copy of K/V (2x HBM + 2x wire for the sharded decode cache; §Perf A1)
    scores = jnp.einsum(
        "bchd,bshd->bchs", q, k, preferred_element_type=jnp.float32
    )
    scores *= scale
    mask = jnp.ones((c, kv_pos.shape[0]), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= kv_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(mask[None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bchs,bshd->bchd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# sharding annotation helper — logical constraint applied inside jit bodies.
# Resolution to mesh axes happens through runtime.sharding rules; when no
# mesh/rules are active this is the identity (single-device smoke tests).
# --------------------------------------------------------------------------

_LOGICAL_RULES: Dict[str, Any] = {}
_MESH = None


def set_logical_rules(mesh, rules: Dict[str, Any]) -> None:
    global _MESH, _LOGICAL_RULES
    _MESH = mesh
    _LOGICAL_RULES = dict(rules)


def clear_logical_rules() -> None:
    global _MESH, _LOGICAL_RULES
    _MESH = None
    _LOGICAL_RULES = {}


def logical_constraint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint under the active logical rules (no-op if none)."""
    if _MESH is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = tuple(_LOGICAL_RULES.get(a) if a else None for a in axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, P(*spec)))
