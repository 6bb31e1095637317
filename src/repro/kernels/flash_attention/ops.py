"""jit'd wrapper for flash attention: Pallas on TPU (or in interpret mode
when the caller asks, for validation); the memory-bounded chunked-jnp path
otherwise (``repro.kernels.dispatch``).

Also the kernel's trace-capture shim (:func:`trace_geometry`): the grid /
BlockSpec index-map math of ``flash_attention_pallas`` mirrored into a
jax-free :class:`~repro.capture.geometry.KernelGeometry` so the DS
simulator can observe the kernel's block-level HBM stream without a TPU
(DESIGN.md §2.8; drift against the kernel is locked by
tests/test_capture.py)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.dispatch import use_pallas
from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BK,
    DEFAULT_BQ,
    flash_attention_pallas,
)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "use_kernel", "interpret", "bq", "bk")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_kernel: bool = False, interpret: bool = False,
                    bq: int = 128, bk: int = 128):
    if use_pallas("flash_attention", use_kernel=use_kernel, interpret=interpret):
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, bq=bq, bk=bk,
            interpret=interpret,
        )
    from repro.models import nn

    return nn.attention(q, k, v, causal=causal, window=window)


def trace_geometry(*, b: int, sq: int, skv: int, h: int, kvh: int, d: int,
                   bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                   variant: str = "prefill"):
    """Capture shim: the exact grid + index maps of
    ``flash_attention_pallas`` for a (B, Sq, H, D) x (B, Skv, KVH, D)
    launch — grid (B*H, Sq/BQ, Skv/BK), KV axis innermost, Q/O parked
    across the KV loop, K/V shared across GQA head groups."""
    from repro.capture.geometry import KernelGeometry, Operand

    assert h % kvh == 0
    g = h // kvh
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    grid = (b * h, sq // bq, skv // bk)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // h) * kvh + (bh % h) // g, ki, 0)

    # per grid step: QK^T scores (2*bq*bk*d) + PV gather (2*bq*bk*d)
    flops = 4.0 * bq * bk * d
    return KernelGeometry(
        kernel="flash_attention", variant=variant, grid=grid,
        operands=(
            Operand("q", (b * h, sq, d), (1, bq, d), q_map),
            Operand("k", (b * kvh, skv, d), (1, bk, d), kv_map),
            Operand("v", (b * kvh, skv, d), (1, bk, d), kv_map),
            Operand("o", (b * h, sq, d), (1, bq, d), q_map, is_output=True),
        ),
        flops_per_step=flops,
    )
