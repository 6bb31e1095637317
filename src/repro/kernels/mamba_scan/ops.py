"""jit'd wrapper for the selective scan: Pallas kernel on TPU (or in
interpret mode when the caller asks), the sequential ref.py oracle elsewhere
(``repro.kernels.dispatch``; models/mamba.py provides the production XLA
path).

Also the kernel's trace-capture shim (:func:`trace_geometry`): the grid /
BlockSpec index-map math of ``selective_scan_pallas`` mirrored into a
jax-free :class:`~repro.capture.geometry.KernelGeometry` (DESIGN.md §2.8;
drift against the kernel is locked by tests/test_capture.py)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.dispatch import use_pallas
from repro.kernels.mamba_scan import ref
from repro.kernels.mamba_scan.mamba_scan import CHUNK, TILE_D, selective_scan_pallas


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def selective_scan(dt, a, bmat, cmat, x, *, use_kernel: bool = False,
                   interpret: bool = False):
    if use_pallas("mamba_scan", use_kernel=use_kernel, interpret=interpret):
        return selective_scan_pallas(dt, a, bmat, cmat, x, interpret=interpret)
    return ref.selective_scan_ref(dt, a, bmat, cmat, x)


def trace_geometry(*, b: int, s: int, d: int, n: int, chunk: int = CHUNK,
                   tile_d: int = TILE_D, variant: str = "fwd"):
    """Capture shim: the exact grid + index maps of
    ``selective_scan_pallas`` — grid (B, D/TD, S/CHUNK), chunk axis
    innermost and sequential (the SSM state is VMEM-carried across chunks),
    A parked across the chunk loop, B/C re-streamed for every channel
    tile."""
    from repro.capture.geometry import KernelGeometry, Operand

    chunk = min(chunk, s)
    tile_d = min(tile_d, d)
    assert s % chunk == 0 and d % tile_d == 0, (s, chunk, d, tile_d)
    grid = (b, d // tile_d, s // chunk)

    def chunk_map(bi, di, ci):
        return (bi, ci, di)

    def a_map(bi, di, ci):
        return (di, 0)

    def bc_map(bi, di, ci):
        return (bi, ci, 0)

    def h_map(bi, di, ci):
        return (bi, di, 0)

    # per grid step: chunk x (discretize + recurrence + C-projection) on
    # (tile_d, n) tiles — ~8 flops per (t, channel, state) element
    flops = 8.0 * chunk * tile_d * n
    return KernelGeometry(
        kernel="mamba_scan", variant=variant, grid=grid,
        operands=(
            Operand("dt", (b, s, d), (1, chunk, tile_d), chunk_map,
                    payload="f32_pos"),
            Operand("a", (d, n), (tile_d, n), a_map),
            Operand("bmat", (b, s, n), (1, chunk, n), bc_map),
            Operand("cmat", (b, s, n), (1, chunk, n), bc_map),
            Operand("x", (b, s, d), (1, chunk, tile_d), chunk_map),
            Operand("y", (b, s, d), (1, chunk, tile_d), chunk_map,
                    is_output=True),
            Operand("h_last", (b, d, n), (1, tile_d, n), h_map,
                    is_output=True),
        ),
        flops_per_step=flops,
    )
