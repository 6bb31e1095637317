#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): weights from the seed on the device, the
cell's own programs compiled (or loaded from the compile cache kept at
``benchmarks/chip/.jax_cache``) and run once.  Then the measured window,
in which nothing may compile.  With ``--trace 1`` the window runs under the
profiler and the per-layer metrics are read from the trace, with the device
time of each named part of each program (``scope_split.py``); otherwise the
end-to-end metrics are printed.  Either way, once the window has closed and
the program's state is freed, what the window produced is compared with a
plain f32 reference, and ``correct`` says whether every number compared is
within its limit (``limits/<cell>.json``).

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, (breakdown), checks.  With no TPU, or fewer chips
than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CACHE_DIR = HERE / ".jax_cache"
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import cell as cell_lib  # noqa: E402


class CompileCounter:
    """Counts compilations (traces, lowerings, backend compiles) while armed."""

    def __init__(self):
        import jax

        self.armed, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.armed and "/jax/core/compile" in event:
            self.events.append(event)


def log(msg: str) -> None:
    print(f"[run] {msg}", file=sys.stderr, flush=True)


def setup_jax():
    """JAX with the persistent compile cache at the benchmark's fixed path,
    for the program too (it takes ``JAX_COMPILATION_CACHE_DIR``)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chips(jax, chips: int):
    """The devices to run on; exits 1 where there is no TPU or too few."""
    import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: no TPU found (JAX's devices are {devs[0].platform}); "
                 "the benchmark runs only on a TPU")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell asks for {chips} chips, JAX finds {len(devs)}")
    try:
        return devs[:chips], peaks.peaks_for(devs[0].device_kind)
    except KeyError as e:
        sys.exit(f"run.py: {e}")


def compare(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that the cell's limits name, beside its limit; correct
    only if every one was read and is within its limit."""
    checks = {name: {"value": readings.get(name), "limit": lim["limit"]}
              for name, lim in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return ok and len(checks) > 1, checks


def reduce_trace(path: str, span_names, texts, scopes) -> dict:
    """trace.py's reduction of the profiler trace at ``path``, with the
    device time of each named part of each program (``scopes``,
    ``conflict_s``), labelled from the compiled programs' ``texts`` by the
    scope names ``scopes``."""
    import scope_split
    import trace as trace_lib

    planes = trace_lib.load_planes(path, span_names)
    red = trace_lib.reduce_planes(planes, span_names)
    red.update(scope_split.reduce_scopes(planes, *scope_split.program_tables(texts, scopes)))
    return red


def run_cell(c: dict, seed: int, seconds: float, trace: bool, devices, peaks_row,
             counter=None, movement=None) -> dict:
    """Set-up, window, per-layer reduction and check of one cell, driven by
    ``<kind>_loop.py`` for the traffic's kind.  Returns the result object
    (without printing it)."""
    import jax

    loop = __import__(f"{c['traffic']['kind']}_loop")
    cfg = cell_lib.model_config(c["conf"])
    kw = {} if movement is None else {"movement": movement}
    system = loop.Cell(cfg, c, seed, **kw)
    system.warm_up()
    jax.block_until_ready(system.params)
    setup_s = time.perf_counter() - T_PROCESS

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if counter:
        counter.armed = True
    win = system.window(seed, seconds)
    if counter:
        counter.armed = False
    if trace:
        jax.profiler.stop_trace()

    weight_bytes = system.weight_bytes()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    chosen = system.check_inputs(win)
    texts = [p.as_text() for progs in system.programs.values() for p in progs] if trace else []
    system.free()
    del system
    t_check = time.perf_counter()
    readings = loop.readings(c, seed, chosen)
    log(f"window {win['window_s']:.3f} s; reference check {time.perf_counter() - t_check:.1f} s; "
        f"readings {readings}")
    for line in loop.describe(win):
        log(line)

    result = loop.e2e(win)
    result["setup_s"] = setup_s
    compiles = len(counter.events) if counter else 0
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    metrics, breakdown = {}, None
    if trace:
        import scope_split
        import trace as trace_lib

        t_trace = time.perf_counter()
        red = reduce_trace(trace_lib.find_xplane(trace_dir), loop.HOST_SPANS, texts,
                           c["model_files"].SCOPES)
        del texts
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduction {time.perf_counter() - t_trace:.1f} s")
        for line in scope_split.describe(red):
            log(f"scopes {line}")
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        rec = {"model": c["model"], "model_files": c["model_files"], "traffic": c["traffic"],
               "peaks": peaks_row, "trace": red, "work": loop.work(win, c["traffic"]["batch"]),
               "weight_bytes": weight_bytes}
        for m in c["per_layer"]:
            value = cell_lib.metric_reader(m["name"], c.get("dir", HERE))(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        for m in c["end_to_end"]:
            if m["name"] not in result:
                raise KeyError(f"{c['name']}: no end-to-end value for {m['name']}")
            metrics[m["name"]] = {"value": result[m["name"]], "unit": m["unit"]}

    readings["compiles_in_window"] = compiles
    correct, checks = compare(readings, dict(c["limits"] or {}, compiles_in_window={"limit": 0}))
    out = {"correct": correct, "attempted": loop.attempted(win), "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["readings"] = readings
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        sys.exit("run.py: --seed must be a whole number >= 0")
    c = cell_lib.load(a.workload)
    jax = setup_jax()
    devices, peaks_row = require_chips(jax, c["chips"])
    counter = CompileCounter()
    out = run_cell(c, a.seed, a.seconds, bool(a.trace), devices, peaks_row, counter)
    for name, chk in out["checks"].items():
        print(f"check {name} = {chk['value']!r} (limit {chk['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
