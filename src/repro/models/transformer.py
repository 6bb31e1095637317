"""Decoder-only transformer trunk covering the dense archs (minicpm, danube,
stablelm, qwen3), the VLM backbone (internvl2 — stub ViT prefix), and the MoE
archs (deepseek-v2-lite with MLA, dbrx) via segment composition.

Layers are grouped into *segments* of uniform structure; each segment's
parameters are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` (keeps HLO size O(1) in depth — an 80L x d8192 model lowers
in seconds).  Caches are stacked the same way and co-scanned at decode; a GQA
cache is only read inside the scan and written once after it (``trunk_decode``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kv_cache
from repro.models import moe as moe_lib
from repro.models import nn
from repro.models.nn import ParamSpec, logical_constraint
from repro.models.scopes import scope

PyTree = Any


# --------------------------------------------------------------------------
# segments
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    name: str
    n_layers: int
    is_moe: bool


def segments(cfg: ModelConfig) -> List[Segment]:
    if cfg.family in ("dense", "vlm"):
        return [Segment("seg0", cfg.num_layers, False)]
    if cfg.family == "moe":
        segs = []
        if cfg.first_dense_layers:
            segs.append(Segment("seg0", cfg.first_dense_layers, False))
        segs.append(Segment(f"seg{len(segs)}", cfg.num_layers - cfg.first_dense_layers, True))
        return segs
    raise ValueError(f"transformer trunk does not build family {cfg.family!r}")


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    if cfg.attn_kind == "mla":
        h = cfg.num_heads
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        s = {
            "wq": ParamSpec((d, h * qk), ("embed", "heads")),
            "w_dkv": ParamSpec((d, cfg.kv_lora_rank + cfg.qk_rope_dim), ("embed", "lora")),
            "kv_norm": ParamSpec((cfg.kv_lora_rank,), (None,), "ones"),
            "w_uk": ParamSpec((cfg.kv_lora_rank, h * cfg.qk_nope_dim), ("lora", "heads")),
            "w_uv": ParamSpec((cfg.kv_lora_rank, h * cfg.v_head_dim), ("lora", "heads")),
            "wo": ParamSpec((h * cfg.v_head_dim, d), ("heads", "embed")),
        }
        return s
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h * dh), ("embed", "heads")),
        "wk": ParamSpec((d, kvh * dh), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kvh * dh), ("embed", "kv_heads")),
        "wo": ParamSpec((h * dh, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((dh,), (None,), "ones")
        s["k_norm"] = ParamSpec((dh,), (None,), "ones")
    return s


def mlp_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "mlp")),
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }


def block_specs(cfg: ModelConfig, is_moe: bool) -> Dict[str, Any]:
    s: Dict[str, Any] = {
        "ln1": ParamSpec((cfg.d_model,), (None,), "ones"),
        "attn": attn_specs(cfg),
        "ln2": ParamSpec((cfg.d_model,), (None,), "ones"),
    }
    s["ffn"] = moe_lib.moe_specs(cfg) if is_moe else mlp_specs(cfg)
    return s


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "ln_f": ParamSpec((cfg.d_model,), (None,), "ones"),
    }
    for seg in segments(cfg):
        s[seg.name] = nn.stack_specs(block_specs(cfg, seg.is_moe), seg.n_layers)
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return s


# --------------------------------------------------------------------------
# attention application
# --------------------------------------------------------------------------


def gqa_qkv(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
            *, decode: bool = False):
    b, s, _ = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with scope("attn_proj"):
        q = jnp.einsum("bsd,dk->bsk", x, p["wq"].astype(x.dtype)).reshape(b, s, h, dh)
        k = jnp.einsum("bsd,dk->bsk", x, p["wk"].astype(x.dtype)).reshape(b, s, kvh, dh)
        v = jnp.einsum("bsd,dk->bsk", x, p["wv"].astype(x.dtype)).reshape(b, s, kvh, dh)
        if cfg.qk_norm:
            q = nn.rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = nn.rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.pos_embed == "rope":
            q = nn.apply_rope(q, positions, cfg.rope_theta)
            k = nn.apply_rope(k, positions, cfg.rope_theta)
    if decode:
        # align with the cache sharding (kv_seq / kv_dh per the active rules)
        # so the einsums against the resident cache never re-shard it; the
        # single-token q/k/v are tiny in every layout (§Perf A1).
        q = logical_constraint(q, "act_batch", None, None, "kv_dh")
        k = logical_constraint(k, "act_batch", None, None, "kv_dh")
        v = logical_constraint(v, "act_batch", None, None, "kv_dh")
        return q, k, v
    # train/prefill: q shards over the full `heads` dim; raw k/v keep
    # kv_heads unsharded (often < TP degree) — the repeat inside attention
    # propagates q's head sharding onto the expanded copies.
    q = logical_constraint(q, "act_batch", None, "heads", None)
    k = logical_constraint(k, "act_batch", None, None, None)
    v = logical_constraint(v, "act_batch", None, None, None)
    return q, k, v


def gqa_attn_forward(
    cfg: ModelConfig,
    p,
    x: jax.Array,
    positions: jax.Array,
    *,
    make_cache: bool = False,
    causal: bool = True,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full-sequence attention (train / prefill)."""
    q, k, v = gqa_qkv(cfg, p, x, positions)
    window = cfg.window if cfg.attn_kind == "swa" else 0
    with scope("attn_core"):
        o = nn.attention(q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk)
    with scope("attn_proj"):
        out = jnp.einsum(
            "bsk,kd->bsd", o.reshape(o.shape[0], o.shape[1], -1), p["wo"].astype(x.dtype)
        )
    cache = kv_cache.from_prefill(cfg, {"k": k, "v": v}) if make_cache else None
    return out, cache


def gqa_decode_token(
    cfg: ModelConfig, p, x: jax.Array, cache: Dict[str, jax.Array], pos: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode that reads a layer's (ring) KV cache where it lies.
    x: (B, 1, d), pos scalar.  The token attends to every resident slot but
    ``pos % w``, the one it will take, and to its own K and V as one extra
    key.  Returns the output and the token's K and V, (B, 1, KVH, Dh) each,
    in the cache's dtype; the trunk writes them (``kv_cache.write``)."""
    positions = pos[None] if pos.ndim == 0 else pos
    q, k_new, v_new = gqa_qkv(cfg, p, x, positions, decode=True)
    with scope("attn_core"):
        o, new = kv_cache.attend(cfg, q, cache, k_new, v_new, pos)
    with scope("attn_proj"):
        out = jnp.einsum("bsk,kd->bsd", o.reshape(o.shape[0], 1, -1), p["wo"].astype(x.dtype))
    return out, new


# ---------------------------- MLA (deepseek) -------------------------------


def mla_project_q(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    h = cfg.num_heads
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"].astype(x.dtype))
    q = q.reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = nn.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_compress_kv(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array):
    ckv_rope = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    ckv, k_rope = jnp.split(ckv_rope, [cfg.kv_lora_rank], axis=-1)
    ckv = nn.rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = nn.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, k_rope


def mla_attn_forward(
    cfg: ModelConfig, p, x: jax.Array, positions: jax.Array, *, make_cache: bool = False
):
    """Prefill/train MLA: expand compressed kv to per-head K/V (paper-faithful)."""
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = mla_project_q(cfg, p, x, positions)
    ckv, k_rope = mla_compress_kv(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rk->bsk", ckv, p["w_uk"].astype(x.dtype)).reshape(
        b, s, h, cfg.qk_nope_dim
    )
    v = jnp.einsum("bsr,rk->bsk", ckv, p["w_uv"].astype(x.dtype)).reshape(
        b, s, h, cfg.v_head_dim
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, cfg.qk_rope_dim))], axis=-1)
    q = logical_constraint(q, "act_batch", None, "heads", None)
    k = logical_constraint(k, "act_batch", None, "heads", None)
    o = nn.attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    out = jnp.einsum("bsk,kd->bsd", o.reshape(b, s, -1), p["wo"].astype(x.dtype))
    cache = {"ckv": ckv, "krope": k_rope} if make_cache else None
    return out, cache


def mla_attn_decode(cfg: ModelConfig, p, x: jax.Array, cache, pos: jax.Array):
    """Absorbed MLA decode: attention runs in the compressed kv_lora space —
    the cache stays (B, S, R + rope) instead of (B, S, H, 2*dh)."""
    b = x.shape[0]
    h, r = cfg.num_heads, cfg.kv_lora_rank
    positions = pos[None]
    q_nope, q_rope = mla_project_q(cfg, p, x, positions)  # (B,1,H,*)
    ckv_new, krope_new = mla_compress_kv(cfg, p, x, positions)
    ckv = cache["ckv"].at[:, pos].set(ckv_new[:, 0])
    krope = cache["krope"].at[:, pos].set(krope_new[:, 0])

    w_uk = p["w_uk"].reshape(r, h, cfg.qk_nope_dim).astype(x.dtype)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)  # absorb k up-proj
    scores = jnp.einsum("bhr,bsr->bhs", q_abs.astype(jnp.float32), ckv.astype(jnp.float32))
    scores += jnp.einsum(
        "bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32), krope.astype(jnp.float32)
    )
    scores /= jnp.sqrt(jnp.asarray(cfg.qk_nope_dim + cfg.qk_rope_dim, jnp.float32))
    kv_pos = jnp.arange(ckv.shape[1])
    scores = jnp.where((kv_pos <= pos)[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, ckv.astype(jnp.float32)).astype(x.dtype)
    w_uv = p["w_uv"].reshape(r, h, cfg.v_head_dim).astype(x.dtype)
    o = jnp.einsum("bhr,rhd->bhd", ctx, w_uv)  # absorb v up-proj
    out = jnp.einsum("bk,kd->bd", o.reshape(b, -1), p["wo"].astype(x.dtype))[:, None, :]
    return out, {"ckv": ckv, "krope": krope}


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------


def apply_block(
    cfg: ModelConfig,
    p,
    x: jax.Array,
    positions: jax.Array,
    *,
    is_moe: bool,
    make_cache: bool = False,
    causal: bool = True,
):
    with scope("norm"):
        h = nn.rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        a, cache = mla_attn_forward(cfg, p["attn"], h, positions, make_cache=make_cache)
    else:
        a, cache = gqa_attn_forward(
            cfg, p["attn"], h, positions, make_cache=make_cache, causal=causal
        )
    x, aux = _ffn(cfg, p, x + a, is_moe)
    return logical_constraint(x, "act_batch", None, None), cache, aux


def apply_block_decode(cfg: ModelConfig, p, x, cache, pos, *, is_moe: bool):
    """One layer of one-token decode.  An MLA layer returns its latent cache
    with the token written; a GQA layer only the token's K and V."""
    with scope("norm"):
        h = nn.rms_norm(x, p["ln1"], cfg.norm_eps)
    decode_attn = mla_attn_decode if cfg.attn_kind == "mla" else gqa_decode_token
    a, new_cache = decode_attn(cfg, p["attn"], h, cache, pos)
    return _ffn(cfg, p, x + a, is_moe)[0], new_cache


def _ffn(cfg: ModelConfig, p, x, is_moe: bool):
    """A block's second half: x plus its feed-forward, and the MoE aux loss."""
    with scope("norm"):
        h = nn.rms_norm(x, p["ln2"], cfg.norm_eps)
    with scope("mlp"):
        if is_moe:
            f, aux = moe_lib.apply_moe(p["ffn"], h, cfg)
        else:
            f = nn.swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"])
            aux = jnp.zeros((), jnp.float32)
    return x + f, aux


# --------------------------------------------------------------------------
# trunk forward / prefill / decode over segments
# --------------------------------------------------------------------------


def _remat(fn, cfg: ModelConfig, training: bool):
    if not training or cfg.remat == "nothing":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def trunk_forward(
    cfg: ModelConfig,
    params,
    x: jax.Array,
    positions: jax.Array,
    *,
    training: bool,
    make_cache: bool = False,
    causal: bool = True,
):
    """x: (B, S, d) -> (hidden, cache_by_segment, aux_loss)."""
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}
    for seg in segments(cfg):
        def body(carry, p_l, _seg=seg):
            xx, aux = carry
            xx, cache, a = apply_block(
                cfg, p_l, xx, positions, is_moe=_seg.is_moe,
                make_cache=make_cache, causal=causal,
            )
            return (xx, aux + a), cache

        body = _remat(body, cfg, training)
        with scope("layers"):
            (x, aux_total), cache = jax.lax.scan(body, (x, aux_total), params[seg.name])
        if make_cache:
            caches[seg.name] = cache
    return x, caches, aux_total


def trunk_decode(cfg: ModelConfig, params, x, caches, pos):
    """One token through every segment.  A GQA cache goes into the layer
    scan read-only and comes out as each layer's new K and V alone, written
    into the stacked cache once after the scan: a cache scanned out (or
    carried) would be sliced, copied and re-stacked every step.  MLA's
    latent cache is scanned in and out with the token written per layer."""
    new_caches = {}
    for seg in segments(cfg):
        def body(xx, scanned, _seg=seg):
            p_l, cache_l = scanned
            return apply_block_decode(cfg, p_l, xx, cache_l, pos, is_moe=_seg.is_moe)

        with scope("layers"):
            x, out = jax.lax.scan(body, x, (params[seg.name], caches[seg.name]))
        if cfg.attn_kind != "mla":
            out = kv_cache.write(caches[seg.name], out, pos)
        new_caches[seg.name] = out
    return x, new_caches


# --------------------------------------------------------------------------
# cache specs (abstract shapes for dry-run input_specs)
# --------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Dict[str, Any]:
    out = {}
    w = kv_cache.window(cfg, seq_len)
    for seg in segments(cfg):
        if cfg.attn_kind == "mla":
            out[seg.name] = {
                "ckv": ParamSpec((seg.n_layers, batch, seq_len, cfg.kv_lora_rank), ("layers", "act_batch", "kv_seq", "kv_dh")),
                "krope": ParamSpec((seg.n_layers, batch, seq_len, cfg.qk_rope_dim), ("layers", "act_batch", "kv_seq", None)),
            }
        else:
            kvshape = (seg.n_layers, batch, w, cfg.num_kv_heads, cfg.head_dim)
            # which dim takes the TP axis is a RULES decision (runtime/
            # sharding.base_rules cache_shard=): "kv_seq" = split-KV over
            # sequence; "kv_dh" = split over head_dim (local cache writes,
            # tiny partial-sum AR on scores) — see EXPERIMENTS.md §Perf A1.
            axes = ("layers", "act_batch", "kv_seq", None, "kv_dh")
            out[seg.name] = {
                "k": ParamSpec(kvshape, axes),
                "v": ParamSpec(kvshape, axes),
            }
    return out
