"""jit'd public wrappers for block quantization.

On TPU the Pallas kernel runs natively, for every shape (the kernel takes any
(R, C) with C % BLOCK == 0, which is every shape quantization is defined
for).  Elsewhere — CPU tests, and the dry-run, whose cost_analysis must stay
transparent — the pure-jnp reference path runs unless the caller asks for
the kernel in interpret mode (``repro.kernels.dispatch``); the two agree to
within one int8 code (tests/test_kernels.py).

Also the kernel's trace-capture shim (:func:`trace_geometry`): the grid /
BlockSpec index-map math of ``quantize_pallas`` mirrored into a jax-free
:class:`~repro.capture.geometry.KernelGeometry` (DESIGN.md §2.8; drift
against the kernel is locked by tests/test_capture.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_quant import ref
from repro.kernels.block_quant.block_quant import (
    BLOCK, dequantize_pallas, quantize_pallas,
)
from repro.kernels.dispatch import use_pallas


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def quantize(x: jax.Array, *, use_kernel: bool = False, interpret: bool = False):
    """Flattens to 2-D (rows, C), quantizes per BLOCK along the last axis."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim != 2 else x
    if use_pallas("block_quant", use_kernel=use_kernel, interpret=interpret):
        q, s = quantize_pallas(x2, interpret=interpret)
    else:
        q, s = ref.quantize_ref(x2, BLOCK)
    return q.reshape(shape), s.reshape(*shape[:-1], shape[-1] // BLOCK)


@functools.partial(jax.jit, static_argnames=("dtype", "use_kernel", "interpret"))
def dequantize(q: jax.Array, scales: jax.Array, dtype=jnp.float32, *,
               use_kernel: bool = False, interpret: bool = False):
    shape = q.shape
    q2 = q.reshape(-1, shape[-1]) if q.ndim != 2 else q
    s2 = scales.reshape(q2.shape[0], -1)
    if use_pallas("block_quant", use_kernel=use_kernel, interpret=interpret):
        x = dequantize_pallas(q2, s2, dtype, interpret=interpret)
    else:
        x = ref.dequantize_ref(q2, s2, dtype)
    return x.reshape(shape)


def trace_geometry(*, r: int, c: int, variant: str = "quant"):
    """Capture shim: the exact grid + index maps of ``quantize_pallas`` for
    an (R, C) f32 input — grid (R/TR, C/TC) with the column-tile axis
    innermost, reading f32 tiles and writing the int8 payload + one f32
    absmax scale per quantization block.  Geometries tile exactly, so the
    shim takes only shapes the kernel's tiles divide."""
    from repro.capture.geometry import KernelGeometry, Operand
    from repro.kernels.block_quant.block_quant import _tiles

    assert c % BLOCK == 0, f"C={c} must be a multiple of {BLOCK}"
    tr, tc = _tiles(r, c)
    assert r % tr == 0 and c % tc == 0, (r, c, tr, tc)
    grid = (r // tr, c // tc)

    def tile_map(i, j):
        return (i, j)

    # per grid step: abs + max-reduce + scale + round + clip over the tile
    flops = 5.0 * tr * tc
    return KernelGeometry(
        kernel="block_quant", variant=variant, grid=grid,
        operands=(
            Operand("x", (r, c), (tr, tc), tile_map,
                    payload="f32_act_sparse"),
            Operand("q", (r, c), (tr, tc), tile_map, elem_bytes=1,
                    is_output=True, payload="int8_quant"),
            Operand("scales", (r, c // BLOCK), (tr, tc // BLOCK), tile_map,
                    is_output=True, payload="f32_scales"),
        ),
        flops_per_step=flops,
    )


def wire_bytes(shape, dtype_bytes: int = 2, block: int = BLOCK) -> int:
    """Compressed wire size: int8 payload + f32 scale per block."""
    import numpy as np

    n = int(np.prod(shape))
    return n + 4 * (n // block)
