"""Device time of each named part of a serving cell's programs, from a
profiler trace of one window on the chip.

The program names the parts of its serving step with ``jax.named_scope``
(embedding, attention projections and core, MLP, norms, LM head, sampling,
and the layer loop around them); the names are HLO metadata (``op_name``).
The cell's model-files module lists the names its path uses (``SCOPES``).
``run.py --trace 1`` keeps each compiled program's text before the
program's state is freed, and then:

- labels the instructions of each program (``op_scopes``,
  ``program_tables``): each instruction that can run as an operation of its
  own on the device takes the innermost name of ``SCOPES`` in its
  ``op_name``.  Two labels are derived rather than named:

      layer_loop  inside ``layers`` but in no block's scope: the scan's
                  slicing and re-stacking of weights and cache, the loop
      unscoped    no op_name, or one with no name of ``SCOPES``: layout
                  copies the compiler adds, async copy and slice starts
                  and dones

  The buckets' programs share one name
  (``jit_decode_step``); an instruction name that two buckets label
  differently counts as ``unscoped``, and its time is reported as
  ``conflict_s``;
- names each device operation inside the window by its program (the
  ``XLA Modules`` event around it) and its instruction, and adds its self
  time (trace.py's: a loop keeps only what its body leaves uncovered) to
  its label (``reduce_scopes``), into the record's ``trace`` as ``scopes``
  and ``conflict_s``.

``METRICS`` reads the decode step's per-step numbers from that record; each
is the reader of one ``metrics/<name>.py``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

import trace as trace_lib

LAYERS, LAYER_LOOP, UNSCOPED = "layers", "layer_loop", "unscoped"
DECODE = "jit_decode_step"
# the weight matmuls; on a TPU some of the weights' reads fall outside them,
# in the scan's slices (layer_loop) and async fetches (unscoped)
MATMUL = ("attn_proj", "mlp", "lm_head")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OP = re.compile(r"^%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# computations whose instructions run one by one: loop bodies and
# conditions, conditional branches, and the targets of a ``call``
_SUBCOMPUTATIONS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"\b(?:to_apply|calls)=%?([\w.\-]+)")


def label(op_name: str | None, scopes) -> str:
    """The label of an instruction with this ``op_name`` (None: none)."""
    inner = [p for p in (op_name or "").split("/") if p in scopes]
    if not inner:
        return UNSCOPED
    return LAYER_LOOP if inner[-1] == LAYERS else inner[-1]


def _computations(hlo_text: str):
    """{name: [instruction lines]} and the entry computation's name."""
    comps, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and not line.startswith(("HloModule", " ")):
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry = current
        elif line.strip() == "}":
            current = None
        else:
            comps[current].append(line)
    return comps, entry


def _op_name(line: str, opcode: str, comps: dict) -> str | None:
    """An instruction's op_name; a fusion that has none of its own (the
    CPU compiler leaves them bare) takes that of the last instruction
    inside it that has one."""
    op = _OP_NAME.search(line)
    if op or opcode != "fusion":
        return op.group(1) if op else None
    called = _CALLS.search(line)
    for inner in reversed(comps.get(called.group(1), []) if called else []):
        op = _OP_NAME.search(inner)
        if op:
            return op.group(1)
    return None


def op_scopes(hlo_text: str, scopes) -> dict:
    """{instruction name: label} of the instructions that a device trace
    shows as operations: those of the entry computation and of the
    computations it runs one instruction at a time (while bodies and
    conditions, conditional branches, calls), never those inside a fusion
    or a reduction's combiner."""
    comps, entry = _computations(hlo_text)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    out, todo, seen = {}, [entry], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            out[m.group(1)] = label(_op_name(line, m.group(2), comps), scopes)
            for sub in _SUBCOMPUTATIONS.finditer(line):
                if sub.group(1):
                    todo.append(sub.group(1))
                else:
                    todo += [b.strip().lstrip("%") for b in sub.group(2).split(",")]
            if m.group(2) == "call":
                todo += _CALLS.findall(line)
    return out


def program_tables(texts, scopes) -> tuple[dict, set]:
    """{program: {instruction: label}} from compiled programs' texts, by
    the names ``scopes``, and the (program, instruction) pairs that two
    texts of one program label differently, which are left ``unscoped``."""
    tables, conflicts = {}, set()
    for text in texts:
        prog = _MODULE.match(text).group(1)
        table = tables.setdefault(prog, {})
        for name, lab in op_scopes(text, scopes).items():
            if table.setdefault(name, lab) != lab:
                conflicts.add((prog, name))
    for prog, name in conflicts:
        tables[prog][name] = UNSCOPED
    return tables, conflicts


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
    """Device ms a step of the decode program under ``labels``; None where
    the program has none of them (a path that names no such part)."""
    def read(rec):
        d = _decode(rec)
        if d is None or not any(lab in d[0] for lab in labels):
            return None
        return 1e3 * sum(d[0].get(lab, 0.0) for lab in labels) / d[1]
    return read


def attn_roofline(rec):
    """Share of the attention core's device time that the roofline says
    its steps need: per step, max(the core's FLOPs / peak, the bytes it
    reads and writes / HBM bytes/s), by the counts of the cell's
    model-files module."""
    d = _decode(rec)
    positions = rec["work"]["decode_positions"]
    if d is None or d[1] != len(positions) or not d[0].get("attn_core"):
        return None
    mf, m, b, pk = rec["model_files"], rec["model"], rec["work"]["batch"], rec["peaks"]
    need = sum(max(mf.attn_core_flops(m, b, pos) / pk["bf16_flops"],
                   mf.attn_core_bytes(m, b, pos) / pk["hbm_bytes_per_s"])
               for pos in positions)
    return 100.0 * need / d[0]["attn_core"]


METRICS = {
    "decode_attn_core_ms": _per_step_ms("attn_core"),
    "decode_attn_roofline": attn_roofline,
    "decode_matmul_ms": _per_step_ms(*MATMUL),
    "decode_layer_loop_ms": _per_step_ms(LAYER_LOOP),
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
