"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
        --steps 50 --batch 8 --seq 128 --movement daemon --ckpt-dir /tmp/ck

Wires together: config -> mesh/shardings -> data pipeline -> (baseline |
daemon) train step -> async checkpointing -> supervisor (heartbeat +
straggler policy) -> elastic restart-from-checkpoint.  On this CPU container
it runs REDUCED configs for real (examples/train_lm.py trains a ~100M model);
full configs go through the dry-run instead.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import movement as mv
from repro.data import DataConfig, TokenPipeline
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import nn
from repro.optim import adamw
from repro.runtime import sharding as shd
from repro.runtime.fault import HeartbeatMonitor, RunSupervisor, StragglerPolicy


def train(
    arch: str,
    *,
    reduced: bool = True,
    steps: int = 50,
    global_batch: int = 8,
    seq_len: int = 128,
    movement: str = "baseline",
    peak_lr: float = 3e-4,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    resume: bool = False,
    mesh_shape=None,
    num_microbatches: int = 1,
    log_every: int = 10,
    seed: int = 0,
):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(mesh_shape or (1, 1))
    rules = shd.base_rules(mesh, fsdp=True)
    shd.activate(mesh, rules)
    specs = M.model_specs(cfg)
    psh = shd.sharding_for_specs(mesh, rules, specs)

    master = nn.init_params(specs, jax.random.key(seed))
    master = jax.tree.map(lambda p, s: jax.device_put(p, s), master, psh)

    step_fn = steps_lib.make_train_step(
        cfg, peak_lr=peak_lr, total_steps=steps, movement=movement,
        num_microbatches=num_microbatches,
    )
    if movement == "daemon":
        state = mv.init_state(master)
        params = mv.working_copy(master, mv.DAEMON_DEFAULT)
    else:
        state = adamw.init(master)
        params = master

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if mgr and resume and mgr.latest_step() is not None:
        (params, state), extra = mgr.restore(None, (params, state), shardings=None)
        start_step = int(extra.get("step", 0))
        print(f"resumed from step {start_step}")

    pipe = TokenPipeline(
        DataConfig(
            vocab_size=cfg.vocab_size, seq_len=seq_len, global_batch=global_batch,
            seed=seed,
        ),
        start_step=start_step,
    )
    supervisor = RunSupervisor(
        hosts=list(range(jax.process_count())),
        monitor=HeartbeatMonitor(interval_s=60),
        policy=StragglerPolicy(),
    )

    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    losses = []
    t_start = time.time()
    for i, host_batch in zip(range(start_step, steps), pipe):
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        if cfg.family == "vlm":
            p = cfg.num_prefix_tokens
            batch["patches"] = jnp.zeros(
                (batch["tokens"].shape[0], p, cfg.d_model), jnp.bfloat16
            )
        elif cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (batch["tokens"].shape[0], seq_len, cfg.d_model), jnp.bfloat16
            )
        t0 = time.time()
        params, state, metrics = jstep(params, state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        supervisor.monitor.beat(0)
        supervisor.tick({0: time.time() - t0})
        if mgr and (i + 1) % ckpt_every == 0:
            mgr.save_async(i + 1, (params, state), {"step": i + 1, "arch": arch})
        if (i + 1) % log_every == 0 or i == start_step:
            print(
                f"step {i+1:5d} loss {loss:.4f} lr {float(metrics['lr']):.2e} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"({(time.time()-t_start)/(i-start_step+1):.2f}s/step)"
            )
    if mgr:
        mgr.wait()
    pipe.close()
    shd.deactivate()
    return params, state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--movement", default="baseline", choices=["baseline", "daemon"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    a = ap.parse_args()
    enable_compile_cache()
    _, _, losses = train(
        a.arch, reduced=a.reduced, steps=a.steps, global_batch=a.batch,
        seq_len=a.seq, movement=a.movement, peak_lr=a.lr,
        ckpt_dir=a.ckpt_dir or None, resume=a.resume,
        num_microbatches=a.microbatches,
    )
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
