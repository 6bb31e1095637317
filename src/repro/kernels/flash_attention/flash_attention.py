"""Pallas TPU flash attention: online-softmax tiling with causal and
sliding-window masking and native GQA (kv-head sharing — no materialized
repeat, unlike the XLA path).

Tiling: grid = (B * H, Sq / BQ, Skv / BK), the KV axis innermost and
*sequential* so the running max / sum / accumulator live in VMEM scratch
across KV steps (TPU grids execute minor-to-major sequentially).  Each step
does a (BQ, D) x (D, BK) MXU matmul for scores and a (BQ, BK) x (BK, D) MXU
matmul for the value gather; masks come from iota comparisons on the VPU.

VMEM budget per step (BQ=BK=128, D<=256, f32):
  q (128*256*4 = 128 KiB) + k,v (2x128 KiB) + acc (128 KiB) + scores (64 KiB)
  << 16 MiB v5e VMEM, leaving room for double-buffered HBM->VMEM prefetch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, scale: float, bq: int, bk: int,
                  n_kv_blocks: int):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (BQ, D)
    k = k_ref[0]  # (BK, D)
    v = v_ref[0]  # (BK, D)
    # the MXU multiplies in bf16: f32 operands need the multi-pass
    # full-precision product (the TPU default is one bf16 pass), bf16
    # operands are exact in one pass; both accumulate in f32
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    ) * scale  # (BQ, BK)

    qpos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (BQ, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)  # (BQ, BK)
    # fully-masked rows: m_cur == NEG_INF -> p == exp(0) == 1; zero them
    p = jnp.where(m_cur > NEG_INF / 2, p, 0.0)
    alpha = jnp.where(m_cur > NEG_INF / 2, alpha, 0.0)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32, precision=prec
    )
    m_scr[...] = m_cur

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KVH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0
    g = h // kvh
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    scale = 1.0 / math.sqrt(d)

    # (B, S, H, D) -> (B*H, S, D); kv head for flat head j is j // g
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)

    n_kv = skv // bk
    grid = (b * h, sq // bq, n_kv)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // h) * kvh + (bh % h) // g, ki, 0)

    from jax.experimental.pallas import tpu as pltpu

    kern = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        bq=bq, bk=bk, n_kv_blocks=n_kv,
    )
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max m
            pltpu.VMEM((bq, 1), jnp.float32),  # running denom l
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
