"""Movement-engine tests: compressed/chunked collectives under shard_map on
8 fake CPU devices (subprocess — device count locks at first jax init), the
selection unit's hysteresis, and the daemon train step's numerics."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str) -> dict:
    """Run `body` with 8 fake devices; it must print a final json line."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core import movement as mv
        mesh = jax.make_mesh((8,), ("data",))
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env,
        timeout=420,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_compressed_all_gather_roundtrip():
    out = run_in_subprocess(
        """
        x = jax.random.normal(jax.random.key(0), (16, 256), jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        def f(xl):
            return mv.compressed_all_gather(xl, "data", compress="int8")
        g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data")))(xs)
        # every shard gathered the same full tensor; check against x
        full = np.asarray(g).reshape(8, 16, 256)[0]
        err = np.abs(full - np.asarray(x)).max()
        bound = np.abs(np.asarray(x)).reshape(16, 2, 128).max(-1).max() / 127
        print(json.dumps({"err": float(err), "bound": float(bound)}))
        """
    )
    assert out["err"] <= out["bound"] * 1.01


@pytest.mark.slow
def test_chunked_all_gather_matches_plain():
    out = run_in_subprocess(
        """
        x = jax.random.normal(jax.random.key(1), (24, 128), jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        def f(xl):
            plain = jax.lax.all_gather(xl, "data", tiled=True)
            dual = mv.chunked_all_gather(xl, "data", page_chunks=3,
                                         critical_rows=1, compress_pages="bf16")
            return plain, dual
        p, d = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=(P("data"), P("data"))))(xs)
        err = float(np.abs(np.asarray(p, np.float32) - np.asarray(d, np.float32)).max())
        print(json.dumps({"err": err}))
        """
    )
    assert out["err"] < 0.02  # bf16 pages round at ~1e-2 relative


@pytest.mark.slow
def test_compressed_grad_sync_error_feedback_converges():
    """Error feedback: mean of int8-synced grads over steps tracks the true
    mean (residual prevents bias accumulation)."""
    out = run_in_subprocess(
        """
        key = jax.random.key(2)
        g_true = jax.random.normal(key, (8, 8, 128), jnp.float32)  # per-device grads
        gs = jax.device_put(g_true.reshape(64, 128),
                            NamedSharding(mesh, P("data")))
        def f(gl, res):
            gm, new_res = mv.compressed_grad_sync(gl, "data", res, compress="int8")
            return gm, new_res
        fm = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                               out_specs=(P("data"), P("data"))))
        res = jnp.zeros((64, 128), jnp.float32)
        acc = np.zeros((8, 128), np.float32)
        steps = 6
        for _ in range(steps):
            gm, res = fm(gs, res)
            acc += np.asarray(gm).reshape(8, 8, 128)[0]
        true_mean = np.asarray(g_true).mean(0)
        err = np.abs(acc / steps - true_mean).max()
        scale = np.abs(np.asarray(g_true)).max() / 127
        print(json.dumps({"err": float(err), "scale": float(scale)}))
        """
    )
    # with error feedback the time-averaged estimate is much tighter than one
    # quantization step
    assert out["err"] <= out["scale"] * 3


def test_selection_unit_hysteresis():
    from repro.core.movement import SelectionUnit

    su = SelectionUnit(hold_steps=5)
    assert su.config().param_gather == "bf16"
    # sustained collective pressure escalates once per hold window
    su.observe(0, collective_s=10.0, compute_s=1.0)
    assert su._level == 2  # noqa: SLF001 — starts at 1, escalates
    for s in range(1, 4):
        su.observe(s, 10.0, 1.0)
    assert su._level == 2  # capped
    # relaxation requires the hold window to elapse
    su.observe(5, 0.01, 1.0)
    assert su._level == 1
    su.observe(6, 0.01, 1.0)
    assert su._level == 1  # hysteresis holds
    su.observe(11, 0.01, 1.0)
    assert su._level == 0


def test_daemon_train_step_numerics():
    """The daemon step trains: loss decreases on a tiny model, and the bf16
    working copy equals master.astype(bf16)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import movement as mv
    from repro.launch import steps
    from repro.models import model as M
    from repro.models import nn

    cfg = get_config("minicpm-2b").reduced()
    specs = M.model_specs(cfg)
    master = nn.init_params(specs, jax.random.key(0))
    state = mv.init_state(master)
    params = mv.working_copy(master, mv.DAEMON_DEFAULT)
    step = steps.make_train_step(cfg, movement="daemon", num_microbatches=2)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32),
    }
    jstep = jax.jit(step)
    losses = []
    for _ in range(5):
        params, state, metrics = jstep(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses
    lw = jax.tree.leaves(params)[0]
    mw = jax.tree.leaves(state.master)[0]
    np.testing.assert_array_equal(
        np.asarray(lw), np.asarray(mw.astype(jnp.bfloat16))
    )


def test_daemon_int8_grad_sync_step():
    """grad_sync='int8' carries a residual and still trains."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import movement as mv
    from repro.launch import steps
    from repro.models import model as M
    from repro.models import nn
    from repro.optim import schedule

    cfg = get_config("h2o-danube-1.8b").reduced()
    specs = M.model_specs(cfg)
    master = nn.init_params(specs, jax.random.key(1))
    state = mv.init_state(master)
    params = mv.working_copy(master, mv.DAEMON_AGGRESSIVE)
    step = mv.make_daemon_train_step(
        cfg, sched=schedule.make("cosine", peak_lr=1e-3, total_steps=100),
        engine_cfg=mv.DAEMON_AGGRESSIVE, num_microbatches=1,
    )
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32),
    }
    jstep = jax.jit(step)
    l0 = None
    for i in range(4):
        params, state, metrics = jstep(params, state, batch)
        if i == 0:
            l0 = float(metrics["loss"])
    assert float(metrics["loss"]) < l0
    res_norm = sum(float(jnp.sum(jnp.abs(r))) for r in jax.tree.leaves(state.residual))
    assert res_norm > 0  # error feedback is live
