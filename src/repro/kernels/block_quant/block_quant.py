"""Pallas TPU kernel: per-block absmax int8 quantize / dequantize.

This is DaeMon's link-compression unit on TPU: it fuses into the
pre-collective copy of page-granularity transfers (bulk weight all-gathers,
gradient reduce-scatters, KV-page migrations).  Each 128-lane sub-block
reduces its absmax on the VPU, so the MXU stays free for the overlapped
compute.

Layout contract: input (R, C), C % BLOCK == 0; grid (cdiv(R, TR),
cdiv(C, TC)); every VMEM tile holds TC/BLOCK complete quantization blocks.
The scales tile (TR, TC/BLOCK) must be lane-legal on TPU: its last dim is
either the full C/BLOCK (TC == C, the usual case: whole rows per tile) or
a multiple of 128 (TC == WIDE_TC, only when 32 whole rows overflow the
tile budget).  Edge tiles that overhang R or C are padded by Pallas; rows
and blocks are independent, so the padding never reaches a kept value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128  # quantization block (lane-aligned)
TILE_BYTES = 2 * 1024 * 1024  # f32 bytes of one input tile in VMEM
ROW_ALIGN = 32  # int8 sublane packing: row tiles are a multiple of 32
WIDE_TC = BLOCK * 128  # column tile whose scales tile is 128 lanes wide


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (TR, TC)
    tr, tc = x.shape
    xb = x.reshape(tr, tc // BLOCK, BLOCK)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = absmax / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xb / safe), -127, 127)
    q_ref[...] = q.reshape(tr, tc).astype(jnp.int8)
    s_ref[...] = scale[..., 0].astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)  # (TR, TC)
    s = s_ref[...]  # (TR, TC/BLOCK)
    tr, tc = q.shape
    x = q.reshape(tr, tc // BLOCK, BLOCK) * s[..., None]
    x_ref[...] = x.reshape(tr, tc).astype(out_dtype)


def _tiles(r: int, c: int):
    """(TR, TC) for an (R, C) input: whole rows while ROW_ALIGN of them fit
    TILE_BYTES, else WIDE_TC columns; as many aligned rows as fit."""
    tc = c if ROW_ALIGN * c * 4 <= TILE_BYTES else WIDE_TC
    tr = TILE_BYTES // (tc * 4) // ROW_ALIGN * ROW_ALIGN
    return min(tr, r), tc


def quantize_pallas(x: jax.Array, *, interpret: bool = False):
    """x: (R, C) -> (q int8 (R, C), scales f32 (R, C/BLOCK))."""
    r, c = x.shape
    assert c % BLOCK == 0, f"C={c} must be a multiple of {BLOCK}"
    tr, tc = _tiles(r, c)
    grid = (pl.cdiv(r, tr), pl.cdiv(c, tc))
    vma = jax.typeof(x).vma  # varying mesh axes, when called inside shard_map
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tc // BLOCK), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8, vma=vma),
            jax.ShapeDtypeStruct((r, c // BLOCK), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(x)


def dequantize_pallas(q: jax.Array, scales: jax.Array, dtype=jnp.float32,
                      *, interpret: bool = False):
    r, c = q.shape
    assert c % BLOCK == 0 and scales.shape == (r, c // BLOCK)
    tr, tc = _tiles(r, c)
    grid = (pl.cdiv(r, tr), pl.cdiv(c, tc))
    kern = functools.partial(_dequant_kernel, out_dtype=dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tc // BLOCK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), dtype, vma=jax.typeof(q).vma),
        interpret=interpret,
    )(q, scales)
