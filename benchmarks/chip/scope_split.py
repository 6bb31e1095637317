#!/usr/bin/env python3
"""Device time of each named part of a serving cell's programs, from a
profiler trace of one window on the chip.

    python3 benchmarks/chip/scope_split.py --workload <cell> --seed <n> --seconds <s> \
        [--out <file.json>]

The program names the parts of its serving step (``repro.models.scopes``:
embedding, attention projections and core, MLP, norms, LM head, sampling,
and the layer loop around them).  This run makes the cell's set-up and one
traced window as ``run.py --trace 1`` does, keeps each compiled program's
text, and then:

- labels the instructions of each program with ``op_scopes``.  The
  buckets' programs share one name (``jit_decode_step``); an instruction
  name that two buckets label differently counts as ``unscoped``, and its
  time is reported as ``conflict_s``;
- names each device operation inside the window by its program (the
  ``XLA Modules`` event around it) and its instruction, and adds its self
  time (trace.py's: a loop keeps only what its body leaves uncovered) to
  its label;
- prints one line per program on stderr, with each label's seconds and
  share of the program's device time, and last on stdout one JSON object:
  the split (``scopes``), the decode step's per-step numbers (``METRICS``),
  trace.py's own reduction, and what the scope reduction cost.

``run.py`` does not report these numbers: its reduction hands the metric
readers no per-operation times.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict

import run  # first: puts the program (src/) on sys.path
import cell as cell_lib
import count
import trace as trace_lib
from repro.models.scopes import UNSCOPED, op_scopes

DECODE = "jit_decode_step"
# the weight matmuls; on a TPU some of the weights' reads fall outside them,
# in the scan's slices (layer_loop) and async fetches (unscoped)
MATMUL = ("attn_proj", "mlp", "lm_head")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OP = re.compile(r"^%?([\w.\-]+) = ")


def program_tables(texts) -> tuple[dict, set]:
    """{program: {instruction: label}} from compiled programs' texts, and
    the (program, instruction) pairs that two texts of one program label
    differently, which are left ``unscoped``."""
    tables, conflicts = {}, set()
    for text in texts:
        prog = _MODULE.match(text).group(1)
        table = tables.setdefault(prog, {})
        for name, lab in op_scopes(text).items():
            if table.setdefault(name, lab) != lab:
                conflicts.add((prog, name))
    for prog, name in conflicts:
        tables[prog][name] = UNSCOPED
    return tables, conflicts


def load_planes(path: str, span_names) -> list:
    """The planes of an ``.xplane.pb`` as ``trace.reduce_planes`` takes them:
    device ops and modules, and the host spans named in ``span_names``."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {trace_lib.WINDOW}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if trace_lib._DEVICE_PLANE.match(plane.name):
            planes.append((plane.name, {ln.name: trace_lib._events(ln) for ln in plane.lines
                                        if ln.name in ("XLA Ops", "XLA Modules")}))
        elif plane.name.startswith("/host:"):
            planes.append((plane.name, {ln.name: trace_lib._events(ln, keep)
                                        for ln in plane.lines}))
    return planes


def reduce_scopes(planes, tables: dict, conflicts=frozenset()) -> dict:
    """``scopes``: {program: {label: device self seconds}} inside the traced
    window, averaged over the devices used, and ``conflict_s``: the part of
    it whose label two buckets disagreed on.  An operation that its
    program's table does not hold is ``unscoped``."""
    windows = [e for name, lines in planes if name.startswith("/host:")
               for evs in lines.values() for e in evs if e[0] == trace_lib.WINDOW]
    if not windows:
        raise ValueError("the trace holds no host span named 'window'")
    w0, w1 = windows[0][1], windows[0][2]
    split = defaultdict(lambda: defaultdict(int))
    conflict_ns, used = 0, 0
    for name, lines in planes:
        if not trace_lib._DEVICE_PLANE.match(name):
            continue
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in lines.get("XLA Ops", [])
               if s < w1 and e > w0]
        if not ops:
            continue
        used += 1
        modules = sorted((s, e, trace_lib._MODULE_NAME.match(n).group(1))
                         for n, s, e in lines.get("XLA Modules", []) if s < w1 and e > w0)
        starts = [m[0] for m in modules]
        named = []
        for n, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            prog = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
            m = _OP.match(n)
            named.append(((prog, m.group(1) if m else n), s, e))
        for (prog, instr), t in trace_lib._self_times(named):
            split[prog][tables.get(prog, {}).get(instr, UNSCOPED)] += t
            if (prog, instr) in conflicts:
                conflict_ns += t
    if not used:
        raise ValueError("no operation ran on a device inside the traced window")
    return {"scopes": {p: {lab: t * 1e-9 / used for lab, t in labs.items()}
                       for p, labs in split.items()},
            "conflict_s": conflict_ns * 1e-9 / used}


def _decode(rec):
    """The decode program's split and its step count; None where the trace
    has no split of it."""
    red = rec["trace"]
    split = red.get("scopes", {}).get(DECODE)
    p = red["programs"].get(DECODE)
    if not split or not p or not p["n"]:
        return None
    return split, p["n"]


def _per_step_ms(*labels):
    def read(rec):
        d = _decode(rec)
        return None if d is None else 1e3 * sum(d[0].get(lab, 0.0) for lab in labels) / d[1]
    return read


def attn_roofline(rec):
    """Share of the attention core's device time that the roofline says
    its steps need: per step, max(score and weighted-sum FLOPs / peak, the
    bf16 keys and values read and written / HBM bytes/s), from count.py."""
    d = _decode(rec)
    positions = rec["work"]["decode_positions"]
    if d is None or d[1] != len(positions) or not d[0].get("attn_core"):
        return None
    m, b, pk = rec["model"], rec["work"]["batch"], rec["peaks"]
    need = sum(max(count.attn_flops_per_pair(m) * b * count.attended(m, pos) / pk["bf16_flops"],
                   count.kv_bytes_per_token(m) * b * (count.attended(m, pos) + 1)
                   / pk["hbm_bytes_per_s"])
               for pos in positions)
    return 100.0 * need / d[0]["attn_core"]


METRICS = {
    "decode_attn_core_ms": _per_step_ms("attn_core"),
    "decode_attn_roofline": attn_roofline,
    "decode_matmul_ms": _per_step_ms(*MATMUL),
    "decode_layer_loop_ms": _per_step_ms("layer_loop"),
    "decode_unscoped_ms": _per_step_ms(UNSCOPED),
}


def describe(red: dict) -> list:
    """One line per program: each label's seconds and share of the
    program's device time, and the share the labels cover together."""
    out = []
    for prog, split in sorted(red["scopes"].items()):
        dev = red["programs"].get(prog, {}).get("device_s") or float("nan")
        parts = ", ".join(f"{lab} {t:.4f} s ({100 * t / dev:.2f}%)"
                          for lab, t in sorted(split.items(), key=lambda kv: -kv[1]))
        out.append(f"{prog}: {parts}; coverage {100 * sum(split.values()) / dev:.2f}% "
                   f"of {dev:.4f} s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    c = cell_lib.load(a.workload)
    jax = run.setup_jax()
    devices, peaks_row = run.require_chips(jax, c["chips"])
    loop = __import__(f"{c['traffic']['kind']}_loop")
    system = loop.Cell(cell_lib.model_config(c["conf"]), c["model"], c["traffic"], a.seed)
    system.warm_up()
    t0 = time.perf_counter()
    tables, conflicts = program_tables(p.as_text() for progs in system.programs.values()
                                       for p in progs)
    tables_s = time.perf_counter() - t0

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = system.window(a.seed, a.seconds)
    jax.profiler.stop_trace()
    system.free()

    t0 = time.perf_counter()
    planes = load_planes(trace_lib.find_xplane(trace_dir), loop.HOST_SPANS)
    red = trace_lib.reduce_planes(planes, loop.HOST_SPANS)
    t1 = time.perf_counter()
    red.update(reduce_scopes(planes, tables, conflicts))
    t2 = time.perf_counter()
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec = {"model": c["model"], "traffic": c["traffic"], "peaks": peaks_row, "trace": red,
           "work": loop.work(win, c["traffic"]["batch"])}
    for line in describe(red):
        print(f"[scopes] {line}", file=sys.stderr, flush=True)
    out = {"workload": a.workload, "seed": a.seed,
           "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                      "count": len(devices)},
           "metrics": {name: read(rec) for name, read in METRICS.items()},
           "trace": red,
           "cost_s": {"tables": tables_s, "load_and_reduce": t1 - t0, "scopes": t2 - t1}}
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
