"""Unified model API over all assigned families.

    model_specs(cfg)            -> ParamSpec pytree (single source of truth)
    loss_fn(cfg, params, batch) -> (loss, metrics)      [train]
    prefill(cfg, params, batch) -> (last_logits, cache) [inference-prefill]
    decode_step(cfg, params, cache, token, pos)         [inference-decode]
    cache_specs(cfg, batch, seq_len)
    input_specs(cfg, cell)      -> ShapeDtypeStruct stand-ins for the dry-run

The cross-entropy is computed in sequence chunks against the (possibly
vocab-sharded) head so full (B, S, V) logits are never materialized.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import encdec, hybrid, mamba, nn, transformer
from repro.models.nn import ParamSpec, logical_constraint
from repro.models.scopes import scope

LOSS_CHUNK = 256
COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family in ("dense", "vlm", "moe"):
        return transformer.lm_specs(cfg)
    if cfg.family == "ssm":
        s: Dict[str, Any] = {
            "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "blocks": nn.stack_specs(mamba.mamba1_specs(cfg), cfg.num_layers),
            "ln_f": ParamSpec((cfg.d_model,), (None,), "ones"),
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        return s
    if cfg.family == "hybrid":
        s = {
            "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "trunk": hybrid.trunk_specs(cfg),
            "ln_f": ParamSpec((cfg.d_model,), (None,), "ones"),
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        return s
    if cfg.family == "audio":
        return encdec.model_specs(cfg)
    raise ValueError(cfg.family)


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    total = nn.param_count(model_specs(cfg))
    if active_only and cfg.family == "moe":
        moe_layers = cfg.num_layers - cfg.first_dense_layers
        routed = moe_layers * cfg.num_experts * 3 * cfg.d_model * cfg.moe_d_ff
        active = moe_layers * cfg.top_k * 3 * cfg.d_model * cfg.moe_d_ff
        total = total - routed + active
    return total


# --------------------------------------------------------------------------
# embedding / head
# --------------------------------------------------------------------------


def _embed(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    with scope("embed"):
        x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
        return logical_constraint(x, "act_batch", None, None)


def _head_weight(cfg: ModelConfig, params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T  # (d, V)
    return params["lm_head"]


def logits_at(cfg: ModelConfig, params, hidden: jax.Array) -> jax.Array:
    """hidden: (..., d) -> f32 logits (..., V)."""
    with scope("lm_head"):
        w = _head_weight(cfg, params).astype(COMPUTE_DTYPE)
        return jnp.einsum("...d,dv->...v", hidden, w).astype(jnp.float32)


# --------------------------------------------------------------------------
# trunk forward per family (training / teacher-forced)
# --------------------------------------------------------------------------


def forward_hidden(
    cfg: ModelConfig, params, batch: Dict[str, jax.Array], *, training: bool,
    make_cache: bool = False,
):
    """Returns (hidden_for_loss, cache, aux_loss)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        tokens = batch["tokens"]
        x = _embed(cfg, params, tokens)
        if fam == "vlm":
            patches = batch["patches"].astype(COMPUTE_DTYPE)
            x = jnp.concatenate([patches, x], axis=1)
        positions = jnp.arange(x.shape[1])
        x, cache, aux = transformer.trunk_forward(
            cfg, params, x, positions, training=training, make_cache=make_cache
        )
        with scope("norm"):
            x = nn.rms_norm(x, params["ln_f"], cfg.norm_eps)
        if fam == "vlm":
            x = x[:, batch["patches"].shape[1] :]  # loss over text positions only
        return x, cache, aux

    if fam == "ssm":
        x = _embed(cfg, params, batch["tokens"])

        def body(xx, p_l):
            xx, c = mamba.mamba1_forward(cfg, p_l, xx, make_cache=make_cache)
            return xx, c

        if training and cfg.remat != "nothing":
            body = (
                jax.checkpoint(body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
                if cfg.remat == "dots"
                else jax.checkpoint(body)
            )
        x, cache = jax.lax.scan(body, x, params["blocks"])
        x = nn.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x, cache, jnp.zeros((), jnp.float32)

    if fam == "hybrid":
        emb = _embed(cfg, params, batch["tokens"])
        positions = jnp.arange(emb.shape[1])
        x, cache = hybrid.trunk_forward(
            cfg, params["trunk"], emb, emb, positions, training=training, make_cache=make_cache
        )
        x = nn.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x, cache, jnp.zeros((), jnp.float32)

    if fam == "audio":
        frames = batch["frames"].astype(COMPUTE_DTYPE)
        enc_out = encdec.encode(cfg, params, frames, training=training)
        x, cache = encdec.decode_train(
            cfg, params, batch["tokens"], enc_out, training=training, make_cache=make_cache
        )
        return x, cache, jnp.zeros((), jnp.float32)

    raise ValueError(fam)


# --------------------------------------------------------------------------
# chunked cross-entropy loss
# --------------------------------------------------------------------------


def loss_fn(
    cfg: ModelConfig, params, batch: Dict[str, jax.Array], *, training: bool = True,
    aux_weight: float = 0.01, z_weight: float = 1e-4,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    hidden, _, aux = forward_hidden(cfg, params, batch, training=training)
    labels = batch["labels"]
    w = _head_weight(cfg, params).astype(COMPUTE_DTYPE)

    b, s, d = hidden.shape
    chunk = min(LOSS_CHUNK, s)
    if s % chunk:
        chunk = s
    n = s // chunk
    hc = hidden.reshape(b, n, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)

    def body(acc, inp):
        h_c, l_c = inp
        logits = jnp.einsum("bsd,dv->bsv", h_c, w).astype(jnp.float32)
        logits = logical_constraint(logits, "act_batch", None, "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(l_c, 0)[..., None], axis=-1)[..., 0]
        mask = (l_c >= 0).astype(jnp.float32)
        nll = (logz - ll) * mask
        zed = jnp.square(logz) * mask
        nll_sum, z_sum, cnt = acc
        return (nll_sum + nll.sum(), z_sum + zed.sum(), cnt + mask.sum()), None

    (nll_sum, z_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (hc, lc)
    )
    cnt = jnp.maximum(cnt, 1.0)
    ce = nll_sum / cnt
    loss = ce + z_weight * z_sum / cnt + aux_weight * aux
    metrics = {"loss": loss, "ce": ce, "aux": aux, "tokens": cnt}
    return loss, metrics


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    hidden, cache, _ = forward_hidden(cfg, params, batch, training=False, make_cache=True)
    with scope("lm_head"):
        last = hidden[:, -1, :]
    return logits_at(cfg, params, last), cache


def decode_step(cfg: ModelConfig, params, cache, token: jax.Array, pos: jax.Array):
    """token: (B,) int32, pos: scalar int32 (write position). -> (logits, cache)."""
    fam = cfg.family
    with scope("embed"):
        x = params["embed"].astype(COMPUTE_DTYPE)[token][:, None, :]
    if fam in ("dense", "moe", "vlm"):
        x, cache = transformer.trunk_decode(cfg, params, x, cache, pos)
    elif fam == "ssm":

        def body(xx, scanned):
            p_l, c_l = scanned
            xx, c = mamba.mamba1_decode(cfg, p_l, xx, c_l)
            return xx, c

        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif fam == "hybrid":
        emb = x
        x, cache = hybrid.trunk_decode(cfg, params["trunk"], x, emb, cache, pos)
    elif fam == "audio":
        x, cache = encdec.decode_step(cfg, params, cache, token, pos)
        return logits_at(cfg, params, x[:, 0]), cache
    else:
        raise ValueError(fam)
    with scope("norm"):
        x = nn.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return logits_at(cfg, params, x[:, 0]), cache


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Any:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return transformer.cache_specs(cfg, batch, seq_len)
    if fam == "ssm":
        return nn.stack_specs(mamba.mamba1_cache_specs(cfg, batch), cfg.num_layers)
    if fam == "hybrid":
        return hybrid.cache_specs(cfg, batch, seq_len)
    if fam == "audio":
        return encdec.cache_specs(cfg, batch, seq_len)
    raise ValueError(fam)


# --------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# --------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    """Abstract inputs for one (arch x shape) cell."""
    b, s = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        out = {}
        if cfg.family == "vlm":
            p = cfg.num_prefix_tokens
            out["tokens"] = _sds((b, s - p), jnp.int32)
            out["patches"] = _sds((b, p, cfg.d_model), COMPUTE_DTYPE)
            out["labels"] = _sds((b, s - p), jnp.int32)
        elif cfg.family == "audio":
            out["frames"] = _sds((b, s, cfg.d_model), COMPUTE_DTYPE)
            out["tokens"] = _sds((b, s), jnp.int32)
            out["labels"] = _sds((b, s), jnp.int32)
        else:
            out["tokens"] = _sds((b, s), jnp.int32)
            out["labels"] = _sds((b, s), jnp.int32)
        return out

    if cell.kind == "prefill":
        out = {}
        if cfg.family == "vlm":
            p = cfg.num_prefix_tokens
            out["tokens"] = _sds((b, s - p), jnp.int32)
            out["patches"] = _sds((b, p, cfg.d_model), COMPUTE_DTYPE)
        elif cfg.family == "audio":
            out["frames"] = _sds((b, s, cfg.d_model), COMPUTE_DTYPE)
            out["tokens"] = _sds((b, s), jnp.int32)
        else:
            out["tokens"] = _sds((b, s), jnp.int32)
        return out

    if cell.kind == "decode":
        cache = jax.tree.map(
            lambda sp: _sds(sp.shape, COMPUTE_DTYPE if sp.shape else COMPUTE_DTYPE),
            cache_specs(cfg, b, s),
            is_leaf=lambda x: isinstance(x, ParamSpec),
        )
        # SSM states stay f32 (accumulated recurrence)
        if cfg.family == "ssm":
            cache = {
                "state": _sds(cache["state"].shape, jnp.float32),
                "conv": cache["conv"],
            }
        elif cfg.family == "hybrid":
            cache = dict(cache)
            for k in list(cache):
                if k.startswith("ssm"):
                    cache[k] = {
                        "state": _sds(cache[k]["state"].shape, jnp.float32),
                        "conv": cache[k]["conv"],
                    }
        return {
            "token": _sds((b,), jnp.int32),
            "pos": _sds((), jnp.int32),
            "cache": cache,
        }

    raise ValueError(cell.kind)
