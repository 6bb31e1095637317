"""The named parts of a serving step, and how to find them in a compiled
program.

The model wraps each part of its dense-family serving path in
``scope(<name>)`` (a ``jax.named_scope``), with names from ``SCOPES`` alone:

    embed      the token gather (``model._embed``, ``model.decode_step``)
    layers     the layer scan (``transformer.trunk_forward`` / ``trunk_decode``)
    attn_proj  q/k/v projections and rope, and the ``wo`` projection
    attn_core  KV write, scores, mask, softmax, weighted sum
    mlp        the feed-forward (``nn.swiglu`` / ``moe.apply_moe``)
    norm       the blocks' RMS norms and ``ln_f``
    lm_head    ``model.logits_at``
    sample     the argmax of ``launch/steps.make_decode_step``

A scope is HLO metadata only (``op_name``): it adds no operation.
``op_scopes`` reads a compiled program's text and labels each instruction
that can run as an operation of its own on the device with the innermost
scope in its ``op_name``.  Two labels are derived rather than scoped:

    layer_loop  inside ``layers`` but in no block scope: the scan's slicing
                of weights and caches, the stacking of its outputs, and the
                loop itself
    unscoped    no op_name, or one without any scope: layout copies the
                compiler adds, async copy and slice starts and dones
"""
from __future__ import annotations

import re

import jax

SCOPES = ("embed", "layers", "attn_proj", "attn_core", "mlp", "norm", "lm_head", "sample")
LAYER_LOOP = "layer_loop"
UNSCOPED = "unscoped"
LABELS = tuple(s for s in SCOPES if s != "layers") + (LAYER_LOOP, UNSCOPED)

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# computations whose instructions run one by one: loop bodies and
# conditions, conditional branches, and the targets of a ``call``
_SUBCOMPUTATIONS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"\b(?:to_apply|calls)=%?([\w.\-]+)")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of ``SCOPES``; any other name is
    an error, so that every label a reader looks for is defined here."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the scopes are {SCOPES}")
    return jax.named_scope(name)


def label(op_name: str | None) -> str:
    """The label of an instruction with this ``op_name`` (None: none)."""
    parts = (op_name or "").split("/")
    inner = [p for p in parts if p in SCOPES]
    if not inner:
        return UNSCOPED
    return LAYER_LOOP if inner[-1] == "layers" else inner[-1]


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


def op_scopes(hlo_text: str) -> dict:
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
            out[m.group(1)] = label(_op_name(line, m.group(2), comps))
            for sub in _SUBCOMPUTATIONS.finditer(line):
                if sub.group(1):
                    todo.append(sub.group(1))
                else:
                    todo += [b.strip().lstrip("%") for b in sub.group(2).split(",")]
            if m.group(2) == "call":
                todo += _CALLS.findall(line)
    return out
