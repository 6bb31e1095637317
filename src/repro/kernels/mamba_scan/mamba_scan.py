"""Pallas TPU kernel: chunked selective scan (Mamba1-style recurrence).

Grid = (B, D / TD, S / CHUNK) with the sequence-chunk axis innermost and
sequential: the SSM state persists in VMEM scratch across chunk steps
(reset at chunk 0).  Within a chunk the recurrence runs as a fori_loop over
time steps; step t reads row t of each input block straight from its VMEM
ref and writes row t of y straight into the output ref (the TPU lowering
has no value-level dynamic slicing).  The state is held as (N, TD) — the
channels on the 128-wide lane axis — so the dt and x rows broadcast over
the state without a relayout and only the short (1, N) B/C rows turn into
columns.  The working set (CHUNK x TD inputs + N x TD state) stays in VMEM,
the kernel-level analogue of the chunked lax.scan the XLA path uses
(models/mamba.py).

Discretization (da = exp(dt*A), dbx = dt*x*B) happens in-kernel so the big
(S, D, N) tensors are never materialized in HBM — on TPU this kernel turns
the SSM layer from HBM-bound to VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # time steps per grid step
TILE_D = 256  # channels per grid step


def _scan_kernel(dt_ref, a_ref, b_ref, c_ref, x_ref, y_ref, hlast_ref, h_scr, *,
                 chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_t = a_ref[...].astype(jnp.float32).T  # (N, TD)

    def step(t, h):  # h: (N, TD)
        dt = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, TD)
        x = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, TD)
        b_col = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32).T  # (N, 1)
        c_col = c_ref[0, pl.ds(t, 1), :].astype(jnp.float32).T  # (N, 1)
        h = jnp.exp(dt * a_t) * h + b_col * (dt * x)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_col, axis=0, keepdims=True)
        return h

    h_out = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h_out

    @pl.when(ci == n_chunks - 1)
    def _final():
        hlast_ref[0] = h_out.T


def selective_scan_pallas(dt, a, bmat, cmat, x, *, chunk: int = CHUNK,
                          tile_d: int = TILE_D, interpret: bool = False):
    """dt,x: (B,S,D); a: (D,N); bmat,cmat: (B,S,N) -> (y (B,S,D) f32, h_last (B,D,N))."""
    b, s, d = dt.shape
    n = a.shape[1]
    chunk = min(chunk, s)
    tile_d = min(tile_d, d)
    assert s % chunk == 0 and d % tile_d == 0, (s, chunk, d, tile_d)
    n_chunks = s // chunk
    grid = (b, d // tile_d, n_chunks)

    kern = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, tile_d), lambda bi, di, ci: (bi, ci, di)),  # dt
            pl.BlockSpec((tile_d, n), lambda bi, di, ci: (di, 0)),  # a
            pl.BlockSpec((1, chunk, n), lambda bi, di, ci: (bi, ci, 0)),  # B
            pl.BlockSpec((1, chunk, n), lambda bi, di, ci: (bi, ci, 0)),  # C
            pl.BlockSpec((1, chunk, tile_d), lambda bi, di, ci: (bi, ci, di)),  # x
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, tile_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, tile_d, n), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, tile_d), jnp.float32)],
        interpret=interpret,
    )(dt, a, bmat, cmat, x)
    return y, h_last
