"""Arithmetic that every model-files module's counts share: the keys a query
attends to, with or without a sliding window, and the bytes of a bf16
value.  What a layout's weights, caches and matmuls need is counted by the
layout's own module (``models/<name>.py``), from the configuration's shapes
alone: what any program serving that model must do, not what this program
happens to do.  A share computed from these can only rise toward 100% as
the program does less waste.
"""
from __future__ import annotations

BF16 = 2


def attended(m: dict, pos: int) -> int:
    """Keys the query at absolute position ``pos`` attends to (itself included)."""
    w = m.get("sliding_window") or 0
    return min(pos + 1, w) if w else pos + 1


def causal_pairs(m: dict, n: int) -> int:
    """(query, key) pairs of a causal pass over n tokens, window applied."""
    w = m.get("sliding_window") or 0
    if not w or n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w
