"""Smoke test of the DaeMon JAX path on a TPU.

Run from the repository root, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the DaeMon collectives on four chips

With no option it runs, in one process and in this order:

  device     requires that JAX's first device is a TPU; never falls back to
             the CPU.
  simulator  one captured-kernel cell and one 4-CC x 4-MC memory-pool cell,
             serially and before any array touches the device.
  kernels    the three Pallas kernels compiled natively, at their capture
             catalog shapes and at one deployment shape each, checked
             against their ref.py oracles with the tolerances of
             tests/test_kernels.py and timed after a warm-up.
  serve      the full-width, full-depth h2o-danube-1.8b server through
             ``repro.launch.serve.serve``, with the DaeMon bf16 working copy
             and with the f32 baseline.

With ``--chips 4`` it runs only the DaeMon collectives on a 4-device mesh
against their uncompressed counterparts.

Any failed check raises, so the process exits non-zero.  On success the
last line of standard output is one JSON object naming the device.  The
weights and inputs are random, made from ``--seed``; nothing else is read.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SERVE_ARCH = "h2o-danube-1.8b"
MAMBA_ARCH = "falcon-mamba-7b"
# the daemon run's first tokens must equal the f32 baseline's on at least
# this many of the batch's requests (its activations are bf16 either way,
# so the two differ only where XLA rounds differently)
FIRST_TOKEN_AGREEMENT = 3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: JAX's devices are {devs[0].platform} "
            f"({len(devs)}); this smoke test runs only on a TPU")
    check(len(devs) >= n_chips, f"{n_chips} TPU chips wanted, {len(devs)} found")
    log("device", f"platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs


# --------------------------------------------------------------------------
# simulator
# --------------------------------------------------------------------------


def check_simulator() -> None:
    """Capture + simulator, in this process: no pool may start once the
    process holds the chip (core/sim/sweep.py)."""
    from repro.core.sim import SimConfig, run_one

    t0 = time.perf_counter()
    m = run_one("fa_prefill", "daemon")
    check(m.cycles > 0, "fa_prefill cell ran")
    log("simulator", f"fa_prefill/daemon cycles={m.cycles} "
        f"({time.perf_counter() - t0:.2f} s host)")
    t0 = time.perf_counter()
    cfg = SimConfig(n_ccs=4, n_mcs=4, link_bw_frac=0.25,
                    mc_interleave="capacity_aware", mc_capacity_pages=128)
    m = run_one("pr+st", "daemon", cfg, seed=1, n_accesses=20_000)
    check(m.cycles > 0, "memside cell ran")
    log("simulator", f"pr+st/daemon 4CCx4MC cap=128 cycles={m.cycles} "
        f"spills={m.mc_spills} evictions={m.mc_evictions} "
        f"promotions={m.mc_promotions} ({time.perf_counter() - t0:.2f} s host)")


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _compile(fn, *args, interpret: bool):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if not interpret:
        check("tpu_custom_call" in compiled.as_text(),
              "the compiled HLO calls the Pallas kernel (tpu_custom_call)")
    return compiled, compile_s


def _median_time(compiled, *args, reps: int = 5, calls: int = 10) -> float:
    """Median over ``reps`` of the mean time per call of ``calls``
    back-to-back calls ended by one ``block_until_ready``: dispatch is
    asynchronous, so this amortizes the host's round trip to the device."""
    jax.block_until_ready(compiled(*args))  # warm-up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = compiled(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / calls)
    return statistics.median(times)


@jax.jit
def _allclose_excess(out, expect, atol, rtol):
    """(max |out - expect|, max of that minus np.allclose's allowance)."""
    out, expect = out.astype(jnp.float32), expect.astype(jnp.float32)
    err = jnp.abs(out - expect)
    return jnp.max(err), jnp.max(err - (atol + rtol * jnp.abs(expect)))


def _check_close(label, out, expect, atol, rtol):
    err, excess = (float(v) for v in _allclose_excess(out, expect, atol, rtol))
    check(excess <= 0, f"{label}: max|err|={err:.3g} within atol={atol} rtol={rtol}")
    return err


def flash_cases():
    from repro.capture.workloads import CAPTURED
    from repro.configs import get_config

    for name in ("fa_prefill", "fa_decode"):
        c = CAPTURED[name].config
        decode = c["variant"] == "decode"
        yield (f"{name} catalog", (c["b"], c["sq"], c["skv"], c["h"], c["kvh"], c["d"]),
               jnp.float32, not decode, 0, c.get("bq", 128))
    cfg = get_config(SERVE_ARCH)
    yield (f"{SERVE_ARCH} prefill", (1, 4096, 4096, cfg.num_heads, cfg.num_kv_heads,
                                     cfg.head_dim), jnp.bfloat16, True, cfg.window, 128)


def check_flash(cases, *, seed: int, interpret: bool = False) -> None:
    from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref

    for label, (b, sq, skv, h, kvh, d), dtype, causal, window, bq in cases:
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, skv, kvh, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, skv, kvh, d), jnp.float32).astype(dtype)

        def fa(q, k, v, causal=causal, window=window, bq=bq):
            return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                          bq=bq, bk=128, interpret=interpret)

        compiled, compile_s = _compile(fa, q, k, v, interpret=interpret)
        out = compiled(q, k, v)
        with jax.default_matmul_precision("highest"):
            expect = attention_ref(q, k, v, causal=causal, window=window)
        atol, rtol = (2e-5, 2e-5) if dtype == jnp.float32 else (2e-2, 0.0)
        err = _check_close(label, out, expect, atol, rtol)
        t = _median_time(compiled, q, k, v)
        log("kernels", f"flash_attention {label} B={b} Sq={sq} Skv={skv} H={h} "
            f"KVH={kvh} D={d} {jnp.dtype(dtype).name}: {t * 1e3:.3f} ms/call "
            f"(compile {compile_s:.2f} s) ref max|err|={err:.3g}")


def block_quant_cases():
    from repro.capture.workloads import CAPTURED
    from repro.configs import get_config

    c = CAPTURED["bq_quant"].config
    yield "bq_quant catalog", (c["r"], c["c"])
    cfg = get_config(SERVE_ARCH)
    yield f"{SERVE_ARCH} stacked MLP weight", (cfg.num_layers * cfg.d_model, cfg.d_ff)


@jax.jit
def _quant_diffs(q, s, q_ref, s_ref):
    dq = jnp.abs(q.astype(jnp.int32) - q_ref.astype(jnp.int32))
    ds = jnp.max(jnp.abs(s - s_ref) - 1e-6 * jnp.abs(s_ref))
    return jnp.max(dq), jnp.mean((dq != 0).astype(jnp.float32)), ds, jnp.max(s_ref)


def check_block_quant(cases, *, seed: int, interpret: bool = False) -> None:
    from repro.kernels.block_quant import ref
    from repro.kernels.block_quant.block_quant import dequantize_pallas, quantize_pallas

    for label, (r, c) in cases:
        x = jax.random.normal(jax.random.key(seed), (r, c), jnp.float32) * 3
        quant, qc_s = _compile(lambda x: quantize_pallas(x, interpret=interpret), x,
                               interpret=interpret)
        q, s = quant(x)
        q_ref, s_ref = ref.quantize_ref(x)
        max_code, frac, s_excess, s_max = (float(v) for v in _quant_diffs(q, s, q_ref, s_ref))
        check(max_code <= 1 and frac < 1e-3,
              f"{label}: int8 codes within 1 of ref ({max_code:.0f}), "
              f"<0.1% differ ({frac:.2e})")
        check(s_excess <= 0, f"{label}: scales within rtol 1e-6 of ref")
        deq, dq_s = _compile(lambda q, s: dequantize_pallas(q, s, jnp.float32,
                                                            interpret=interpret),
                             q, s, interpret=interpret)
        err = _check_close(f"{label} dequantize", deq(q, s),
                           ref.dequantize_ref(q_ref, s_ref), s_max * 1.01, 0.0)
        tq = _median_time(quant, x)
        tdq = _median_time(deq, q, s)
        log("kernels", f"block_quant {label} ({r}, {c}) f32: quantize {tq * 1e3:.3f} ms, "
            f"dequantize {tdq * 1e3:.3f} ms (compile {qc_s:.2f} + {dq_s:.2f} s) "
            f"codes max|diff|={max_code:.0f} differ={frac:.2e} dequant max|err|={err:.3g}")


def mamba_cases():
    from repro.capture.workloads import CAPTURED
    from repro.configs import get_config

    c = CAPTURED["mamba_fwd"].config
    yield "mamba_fwd catalog", (c["b"], c["s"], c["d"], c["n"])
    cfg = get_config(MAMBA_ARCH)
    yield f"{MAMBA_ARCH} d_inner", (1, 2048, cfg.d_model * cfg.ssm_expand, cfg.ssm_state)


def check_mamba(cases, *, seed: int, interpret: bool = False) -> None:
    from repro.kernels.mamba_scan.mamba_scan import selective_scan_pallas
    from repro.kernels.mamba_scan.ref import selective_scan_ref

    for label, (b, s, d, n) in cases:
        ks = jax.random.split(jax.random.key(seed), 5)
        dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)) - 1.0)
        a = -jnp.exp(jax.random.normal(ks[1], (d, n)) * 0.5)
        bm = jax.random.normal(ks[2], (b, s, n))
        cm = jax.random.normal(ks[3], (b, s, n))
        x = jax.random.normal(ks[4], (b, s, d))
        args = (dt, a, bm, cm, x)
        scan, compile_s = _compile(
            lambda *a: selective_scan_pallas(*a, interpret=interpret), *args,
            interpret=interpret)
        y, h = scan(*args)
        with jax.default_matmul_precision("highest"):
            y_ref, h_ref = jax.jit(selective_scan_ref)(*args)
        err_y = _check_close(f"{label} y", y, y_ref, 1e-4, 1e-4)
        err_h = _check_close(f"{label} h_last", h, h_ref, 1e-4, 1e-4)
        t = _median_time(scan, *args)
        log("kernels", f"mamba_scan {label} B={b} S={s} D={d} N={n} f32: "
            f"{t * 1e3:.3f} ms/call (compile {compile_s:.2f} s) "
            f"ref max|err| y={err_y:.3g} h={err_h:.3g}")


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def check_serve(arch: str, *, reduced: bool, batch: int, prompt_len: int,
                gen_tokens: int, seed: int) -> None:
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import model as M
    from repro.models import nn

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    n_params = nn.param_count(M.model_specs(cfg))
    runs = {}
    for movement in ("daemon", "baseline"):
        r = serve(arch, reduced=reduced, batch=batch, prompt_len=prompt_len,
                  gen_tokens=gen_tokens, movement=movement, seed=seed)
        toks = r["tokens"]
        check(toks.shape == (batch, gen_tokens),
              f"{movement}: tokens shape {toks.shape} == {(batch, gen_tokens)}")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
              f"{movement}: tokens within the vocabulary")
        check(r["logits_finite"], f"{movement}: every step's logits are finite")
        log("serve", f"{arch} params={n_params} movement={movement} batch={batch} "
            f"prompt={prompt_len} gen={gen_tokens}: compile {r['compile_s']:.2f} s, "
            f"prefill {r['prefill_s'] * 1e3:.3f} ms, decode "
            f"{r['decode_s_per_token'] * 1e3:.3f} ms/token, "
            f"{r['tokens_per_s']:.1f} tokens/s")
        runs[movement] = r
    d, b = runs["daemon"], runs["baseline"]
    agree = int((d["tokens"][:, 0] == b["tokens"][:, 0]).sum())
    diff = float(np.abs(d["first_logits"] - b["first_logits"]).max())
    check(agree >= min(FIRST_TOKEN_AGREEMENT, batch),
          f"daemon first tokens agree with the f32 baseline on {agree}/{batch} "
          f"requests (bar {FIRST_TOKEN_AGREEMENT})")
    log("serve", f"daemon vs baseline: first token agrees on {agree}/{batch} "
        f"requests, max |first-step logit diff| = {diff:.4g}")


# --------------------------------------------------------------------------
# collectives (four chips)
# --------------------------------------------------------------------------


def check_collectives(devices, shape, *, seed: int, steps: int = 6) -> None:
    """The DaeMon collectives against their uncompressed counterparts, with
    the bounds of tests/test_movement.py, on a tensor sharded over 'data'."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import movement as mv

    mesh = Mesh(np.array(devices), ("data",))
    n = len(devices)
    rows = NamedSharding(mesh, P("data"))
    x = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                out_shardings=rows)(jax.random.key(seed))

    def smap(f, out_specs):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=out_specs))

    def gather(xl):
        plain = jax.lax.all_gather(xl, "data", tiled=True)
        comp = mv.compressed_all_gather(xl, "data", compress="int8")
        bound = jax.lax.pmax(jnp.max(jnp.abs(xl)), "data") / 127
        return jnp.max(jnp.abs(comp - plain))[None], bound[None]

    err, bound = (float(v.max()) for v in smap(gather, (P("data"), P("data")))(x))
    check(err <= bound * 1.01, f"compressed_all_gather max|err|={err:.4g} <= "
          f"1.01 x absmax/127 = {bound * 1.01:.4g}")
    log("collectives", f"compressed_all_gather vs all_gather on {n} chips, "
        f"{shape} f32: max|err|={err:.4g} (bound {bound * 1.01:.4g})")

    def chunked(xl):
        plain = jax.lax.all_gather(xl, "data", tiled=True)
        dual = mv.chunked_all_gather(xl, "data", page_chunks=3, critical_rows=1,
                                     compress_pages="bf16")
        return jnp.max(jnp.abs(plain - dual))[None]

    err = float(smap(chunked, P("data"))(x).max())
    check(err < 0.02, f"chunked_all_gather max|err|={err:.4g} < 0.02")
    log("collectives", f"chunked_all_gather vs all_gather on {n} chips: "
        f"max|err|={err:.4g} (bound 0.02)")

    sync = jax.jit(jax.shard_map(
        lambda g, res: mv.compressed_grad_sync(g, "data", res, compress="int8"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data"))))
    mean = smap(lambda g: jax.lax.pmean(g, "data"), P("data"))(x)
    res = jnp.zeros_like(x)
    acc = jnp.zeros_like(x)
    for _ in range(steps):
        gm, res = sync(x, res)
        acc = acc + gm
    err = float(jnp.max(jnp.abs(acc / steps - mean)))
    scale = float(jnp.max(jnp.abs(x))) / 127
    check(err <= scale * 3, f"compressed_grad_sync time-averaged max|err|={err:.4g} "
          f"<= 3 x absmax/127 = {scale * 3:.4g}")
    log("collectives", f"compressed_grad_sync vs pmean on {n} chips, {steps} "
        f"error-feedback steps: max|err|={err:.4g} (bound {scale * 3:.4g})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    t_start = time.perf_counter()
    devs = require_tpu(a.chips)
    if a.chips == 4:
        from repro.configs import get_config

        cfg = get_config(SERVE_ARCH)
        check_collectives(devs[:4], (cfg.num_layers * cfg.d_model, cfg.d_ff), seed=a.seed)
    else:
        check_simulator()
        check_flash(list(flash_cases()), seed=a.seed)
        check_block_quant(list(block_quant_cases()), seed=a.seed)
        check_mamba(list(mamba_cases()), seed=a.seed)
        check_serve(SERVE_ARCH, reduced=False, batch=4, prompt_len=512,
                    gen_tokens=32, seed=a.seed)
    log("cache", f"dir={cache_dir} hits={cache_events['hits']} "
        f"misses={cache_events['misses']}")
    log("done", f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
