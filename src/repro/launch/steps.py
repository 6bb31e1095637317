"""jit-able step functions: train_step (fwd + bwd + AdamW) and the two
serving steps (prefill / decode).  The ``movement`` argument selects the
data-movement scheme for gradients & parameters:

  "baseline" — plain GSPMD: gradients all-reduced implicitly over DP axes,
               optimizer state mirrors params.
  "daemon"   — the paper's engine (core/movement): ZeRO-sharded optimizer,
               chunked + prioritized + link-compressed page collectives.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.scopes import scope
from repro.optim import adamw, schedule


def auto_microbatches(cfg: ModelConfig, seq_len: int, global_batch: int, n_dp: int,
                      budget_bytes: float = 6e9) -> int:
    """Pick the gradient-accumulation factor so the per-device activation
    stash (~2.5 bytes/elem x layers x local tokens x d_model: the residual
    saved per scanned layer plus policy-saved dot outputs) fits the budget.
    Power of two, at most one sequence per microbatch per DP shard."""
    local_batch = max(1, global_batch // max(n_dp, 1))
    layers = cfg.num_layers + cfg.enc_layers + cfg.dec_layers
    stash = 2.5 * layers * local_batch * seq_len * cfg.d_model
    k = 1
    while stash / k > budget_bytes and k < local_batch:
        k *= 2
    return k


def _microbatched_grads(cfg: ModelConfig, params, batch, k: int):
    """Mean loss/grads over k sequential microbatches (activation stash /k)."""
    if k <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch), has_aux=True
        )(params)
        return grads, metrics

    mb = jax.tree.map(lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(acc, mbatch):
        g_acc, loss_acc = acc
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, mbatch), has_aux=True
        )(params)
        g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
        return (g_acc, loss_acc + loss), metrics

    (g_sum, loss_sum), metrics = jax.lax.scan(body, (g0, jnp.zeros(())), mb)
    grads = jax.tree.map(lambda g: g / k, g_sum)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    metrics["loss"] = loss_sum / k
    return grads, metrics


def make_train_step(
    cfg: ModelConfig,
    *,
    peak_lr: float = 3e-4,
    total_steps: int = 10_000,
    movement: str = "baseline",
    movement_cfg: Optional[Any] = None,
    num_microbatches: int = 1,
) -> Callable:
    warmup = max(1, min(100, total_steps // 10))
    sched = schedule.make(
        cfg.schedule, peak_lr=peak_lr, total_steps=total_steps, warmup_steps=warmup
    )

    if movement == "daemon":
        from repro.core import movement as mv

        return mv.make_daemon_train_step(
            cfg, sched=sched, engine_cfg=movement_cfg, num_microbatches=num_microbatches
        )

    def train_step(params, opt_state, batch):
        grads, metrics = _microbatched_grads(cfg, params, batch, num_microbatches)
        lr = sched(opt_state.step)
        params, opt_state, om = adamw.update(grads, opt_state, params, lr)
        return params, opt_state, {**metrics, **om}

    return train_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, batch):
        return M.prefill(cfg, params, batch)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def decode_step(params, cache, token, pos):
        logits, cache = M.decode_step(cfg, params, cache, token, pos)
        with scope("sample"):
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, cache

    return decode_step
