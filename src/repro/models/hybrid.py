"""Zamba2-style hybrid: a Mamba2 backbone with ONE shared attention+MLP
block applied every ``attn_every`` SSM blocks.  The shared block reuses a
single parameter set across invocations, with small per-invocation LoRA
adapters on the q/k/v projections (zamba2's parameter-efficiency trick), and
consumes the concatenation [hidden, original-embedding] (2*d_model wide).

Simplifications vs. the HF checkpoint (noted in DESIGN.md): no per-invocation
output linear after the shared block, RMSNorm instead of LayerNorm.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba, nn
from repro.models.nn import ParamSpec


def n_invocations(cfg: ModelConfig) -> int:
    return -(-cfg.num_layers // cfg.attn_every)  # ceil


def _groups(cfg: ModelConfig) -> List[Tuple[int, int]]:
    """[(start_layer, n_layers)] per shared-block invocation."""
    out = []
    for g in range(n_invocations(cfg)):
        lo = g * cfg.attn_every
        hi = min(lo + cfg.attn_every, cfg.num_layers)
        out.append((lo, hi - lo))
    return out


def shared_block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d2 = 2 * cfg.d_model
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    r, ninv = cfg.shared_lora_rank, n_invocations(cfg)
    s: Dict[str, Any] = {
        "ln1": ParamSpec((d2,), (None,), "ones"),
        "wq": ParamSpec((d2, h * dh), ("embed", "heads")),
        "wk": ParamSpec((d2, kvh * dh), ("embed", "kv_heads")),
        "wv": ParamSpec((d2, kvh * dh), ("embed", "kv_heads")),
        "wo": ParamSpec((h * dh, cfg.d_model), ("heads", "embed")),
        "ln2": ParamSpec((d2,), (None,), "ones"),
        "w_gate": ParamSpec((d2, cfg.d_ff), ("embed", "mlp")),
        "w_up": ParamSpec((d2, cfg.d_ff), ("embed", "mlp")),
        "w_down": ParamSpec((cfg.d_ff, cfg.d_model), ("mlp", "embed")),
    }
    if r:
        for nme, width in (("q", h * dh), ("k", kvh * dh), ("v", kvh * dh)):
            s[f"lora_{nme}_a"] = ParamSpec((ninv, d2, r), (None, "embed", None), "normal", 0.1)
            s[f"lora_{nme}_b"] = ParamSpec((ninv, r, width), (None, None, "heads"), "zeros")
    return s


def _shared_qkv(cfg: ModelConfig, p, cat: jax.Array, inv: int, positions: jax.Array):
    b, s, _ = cat.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def proj(name, width, heads):
        w = p[f"w{name}"].astype(cat.dtype)
        y = jnp.einsum("bsd,dk->bsk", cat, w)
        if cfg.shared_lora_rank:
            la = p[f"lora_{name}_a"][inv].astype(cat.dtype)
            lb = p[f"lora_{name}_b"][inv].astype(cat.dtype)
            y = y + jnp.einsum("bsr,rk->bsk", jnp.einsum("bsd,dr->bsr", cat, la), lb)
        return y.reshape(b, s, heads, dh)

    q = proj("q", h * dh, h)
    k = proj("k", kvh * dh, kvh)
    v = proj("v", kvh * dh, kvh)
    if cfg.pos_embed == "rope":
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        k = nn.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_shared_block(
    cfg: ModelConfig, p, x: jax.Array, emb: jax.Array, inv: int, positions: jax.Array,
    *, make_cache: bool = False,
):
    cat = jnp.concatenate([x, emb], axis=-1)
    hh = nn.rms_norm(cat, p["ln1"], cfg.norm_eps)
    q, k, v = _shared_qkv(cfg, p, hh, inv, positions)
    o = nn.attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    x = x + jnp.einsum("bsk,kd->bsd", o.reshape(*o.shape[:2], -1), p["wo"].astype(x.dtype))
    cat2 = jnp.concatenate([x, emb], axis=-1)
    hh = nn.rms_norm(cat2, p["ln2"], cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", hh, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", hh, p["w_up"].astype(x.dtype))
    x = x + jnp.einsum("bsf,fd->bsd", nn.silu(g) * u, p["w_down"].astype(x.dtype))
    cache = {"k": k, "v": v} if make_cache else None
    return x, cache


def apply_shared_block_decode(cfg: ModelConfig, p, x, emb, inv: int, cache, pos):
    """One token. cache: {k, v: (B, S, KVH, dh)} for this invocation."""
    positions = pos[None]
    cat = jnp.concatenate([x, emb], axis=-1)
    hh = nn.rms_norm(cat, p["ln1"], cfg.norm_eps)
    q, k_new, v_new = _shared_qkv(cfg, p, hh, inv, positions)
    k = cache["k"].at[:, pos].set(k_new[:, 0])
    v = cache["v"].at[:, pos].set(v_new[:, 0])
    o = nn.decode_attention(q, k, v, jnp.arange(k.shape[1]) <= pos)
    x = x + jnp.einsum("bsk,kd->bsd", o.reshape(*o.shape[:2], -1), p["wo"].astype(x.dtype))
    cat2 = jnp.concatenate([x, emb], axis=-1)
    hh = nn.rms_norm(cat2, p["ln2"], cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", hh, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", hh, p["w_up"].astype(x.dtype))
    x = x + jnp.einsum("bsf,fd->bsd", nn.silu(g) * u, p["w_down"].astype(x.dtype))
    return x, {"k": k, "v": v}


# --------------------------------------------------------------------------
# full trunk
# --------------------------------------------------------------------------


def trunk_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "mamba": nn.stack_specs(mamba.mamba2_specs(cfg), cfg.num_layers),
        "shared": shared_block_specs(cfg),
    }


def _mamba_slice(params, lo: int, n: int):
    return jax.tree.map(lambda a: a[lo : lo + n], params)


def trunk_forward(cfg: ModelConfig, params, x, emb, positions, *, training: bool,
                  make_cache: bool = False):
    attn_caches, ssm_caches = [], []
    for inv, (lo, n) in enumerate(_groups(cfg)):
        x, ac = apply_shared_block(
            cfg, params["shared"], x, emb, inv, positions, make_cache=make_cache
        )
        attn_caches.append(ac)

        def body(xx, p_l):
            xx, c = mamba.mamba2_forward(cfg, p_l, xx, make_cache=make_cache)
            return xx, c

        if training and cfg.remat != "nothing":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            ) if cfg.remat == "dots" else jax.checkpoint(body)
        x, sc = jax.lax.scan(body, x, _mamba_slice(params["mamba"], lo, n))
        ssm_caches.append(sc)

    caches = None
    if make_cache:
        caches = {
            "attn": {
                "k": jnp.stack([c["k"] for c in attn_caches]),
                "v": jnp.stack([c["v"] for c in attn_caches]),
            },
            # ssm caches are grouped; keep per-group list keys for re-scan
            **{f"ssm{g}": c for g, c in enumerate(ssm_caches)},
        }
    return x, caches


def trunk_decode(cfg: ModelConfig, params, x, emb, caches, pos):
    new = dict(caches)
    ak = caches["attn"]["k"]
    av = caches["attn"]["v"]
    for inv, (lo, n) in enumerate(_groups(cfg)):
        x, ac = apply_shared_block_decode(
            cfg, params["shared"], x, emb, inv, {"k": ak[inv], "v": av[inv]}, pos
        )
        ak = ak.at[inv].set(ac["k"])
        av = av.at[inv].set(ac["v"])

        def body(xx, scanned):
            p_l, c_l = scanned
            xx, c = mamba.mamba2_decode(cfg, p_l, xx, c_l)
            return xx, c

        x, sc = jax.lax.scan(body, x, (_mamba_slice(params["mamba"], lo, n), caches[f"ssm{inv}"]))
        new[f"ssm{inv}"] = sc
    new["attn"] = {"k": ak, "v": av}
    return x, new


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Dict[str, Any]:
    ninv = n_invocations(cfg)
    kvshape = (ninv, batch, seq_len, cfg.num_kv_heads, cfg.head_dim)
    axes = (None, "act_batch", "kv_seq", None, "kv_dh")
    out: Dict[str, Any] = {
        "attn": {"k": ParamSpec(kvshape, axes), "v": ParamSpec(kvshape, axes)}
    }
    for g, (lo, n) in enumerate(_groups(cfg)):
        out[f"ssm{g}"] = nn.stack_specs(mamba.mamba2_cache_specs(cfg, batch), n)
    return out
