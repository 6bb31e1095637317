"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- busy: the union of the intervals in which an operation ran on a device
  (the device planes' ``XLA Ops`` lines), inside the traced window, averaged
  over the devices used;
- the traced window: the host span named ``window`` that the benchmark
  writes with ``jax.profiler.TraceAnnotation``;
- device time per program: ``XLA Modules`` events, by program name
  (``jit_<function>``), with their count;
- breakdown: the device operations that took most time (self time: an
  operation that encloses others, as a loop does its body, counts only the
  time none of them covers), named ``program/%op (opcode)``, and the idle
  gaps summed by the benchmark's own host span that covered them most.

Device and host events share one clock to within about a millisecond, which
is what a window of seconds needs.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
_OP_NAME = re.compile(r"^(%?[\w.\-]+) = .*?\s([\w\-]+)\(")


def op_label(text: str) -> str:
    """``%fusion.58 (fusion)`` from an XLA op event's HLO text."""
    m = _OP_NAME.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text.split(" = ")[0][:80]


def _self_times(ops):
    """(name, self ns) of (name, start, end) events, nested ones subtracted
    from the event that encloses them."""
    out, stack = [], []  # stack of [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, t) for n, _, t in stack)
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line, keep=None):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
            if keep is None or e.name in keep]


def reduce_planes(planes, span_names=None) -> dict:
    """``planes``: iterable of (plane name, {line name: [(name, start, end)]})
    in nanoseconds on one clock.  Returns the reduction described above."""
    host_spans, devices = [], []
    for pname, lines in planes:
        if _DEVICE_PLANE.match(pname):
            devices.append(lines)
        elif pname.startswith("/host:"):
            for evs in lines.values():
                host_spans.extend(e for e in evs if span_names is None or e[0] in span_names
                                  or e[0] == WINDOW)
    windows = [e for e in host_spans if e[0] == WINDOW]
    if not windows:
        raise ValueError("the trace holds no host span named 'window'")
    w0, w1 = windows[0][1], windows[0][2]
    spans = sorted((e for e in host_spans if e[0] != WINDOW and e[2] > w0 and e[1] < w1),
                   key=lambda e: e[1])
    label = _Labeller(spans)
    used = [d for d in devices if any(s < w1 and e > w0 for _, s, e in d.get("XLA Ops", []))]
    if not used:
        raise ValueError("no operation ran on a device inside the traced window")

    busy_ns, op_ns = 0, defaultdict(int)
    programs = defaultdict(lambda: {"n": 0, "device_s": 0.0})
    gaps_by_span = defaultdict(int)
    for dev in used:
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in dev.get("XLA Ops", []) if s < w1 and e > w0]
        modules = sorted((s, e, _MODULE_NAME.match(n).group(1))
                         for n, s, e in dev.get("XLA Modules", []) if s < w1 and e > w0)
        starts = [m[0] for m in modules]
        for s, e, name in modules:
            programs[name]["n"] += 1
            programs[name]["device_s"] += (min(e, w1) - max(s, w0)) * 1e-9
        named = []
        for n, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            prog = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
            named.append((f"{prog}/{op_label(n)}", s, e))
        for n, t in _self_times(named):
            op_ns[n] += t
        merged = _union((s, e) for _, s, e in ops)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps_by_span[label(gs, ge)] += ge - gs
    n = len(used)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps_by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "devices": n,
        "programs": {k: {"n": v["n"] // n, "device_s": v["device_s"] / n}
                     for k, v in programs.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in top_ops],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in top_gaps],
    }


class _Labeller:
    """Names a gap [gs, ge) by the host span that overlaps it most; the
    shorter (inner) span wins a tie."""

    def __init__(self, spans):
        self.spans = spans
        self.starts = [s for _, s, _ in spans]
        self.longest = max((e - s for _, s, e in spans), default=0)

    def __call__(self, gs, ge) -> str:
        best, best_ov, best_len = "no host span", 0, None
        lo = bisect.bisect_left(self.starts, gs - self.longest)
        hi = bisect.bisect_left(self.starts, ge)
        for name, s, e in self.spans[lo:hi]:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov or (ov == best_ov and ov > 0 and e - s < best_len):
                best, best_ov, best_len = name, ov, e - s
        return best


def load_planes(path: str, span_names=None) -> list:
    """The planes of an ``.xplane.pb`` as ``reduce_planes`` takes them: the
    devices' ops and modules, and the host's spans (only those named in
    ``span_names`` and the window, where it is given)."""
    from jax.profiler import ProfileData

    keep = None if span_names is None else set(span_names) | {WINDOW}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            planes.append((plane.name, {ln.name: _events(ln) for ln in plane.lines
                                        if ln.name in ("XLA Ops", "XLA Modules")}))
        elif plane.name.startswith("/host:"):
            planes.append((plane.name, {ln.name: _events(ln, keep) for ln in plane.lines}))
    return planes


def reduce_file(path: str, span_names=None) -> dict:
    return reduce_planes(load_planes(path, span_names), span_names)
