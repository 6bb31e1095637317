"""Per-kernel validation: Pallas (interpret=True on CPU) vs ref.py oracles,
swept over shapes/dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis-or-fallback shim

from repro.kernels.block_quant import ops as bq_ops
from repro.kernels.block_quant import ref as bq_ref
from repro.kernels.block_quant.block_quant import (
    BLOCK, ROW_ALIGN, TILE_BYTES, _tiles, dequantize_pallas, quantize_pallas,
)
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.mamba_scan.mamba_scan import selective_scan_pallas
from repro.kernels.mamba_scan.ref import selective_scan_ref

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------------
# block_quant
# --------------------------------------------------------------------------


@pytest.mark.parametrize("r,c", [
    (8, 128), (256, 512), (300, 256), (1, 1024),
    (8200, 128),  # row tiles overhang R: the last one is padded
    (40, 25600),  # wider than one column tile: row and column tiles overhang
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_quant_matches_ref(r, c, dtype):
    x = (jax.random.normal(jax.random.key(r * c), (r, c), jnp.float32) * 3).astype(dtype)
    q_p, s_p = quantize_pallas(x, interpret=True)
    q_r, s_r = bq_ref.quantize_ref(x)
    # scales may differ by 1 ULP (fast-math reciprocal in the compiled path),
    # flipping exact .5 boundaries by +-1 code: require <=1 code difference
    # and <0.1% mismatching elements.
    qp, qr = np.asarray(q_p, np.int32), np.asarray(q_r, np.int32)
    assert np.abs(qp - qr).max() <= 1
    assert (qp != qr).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=1e-6)
    x_p = dequantize_pallas(q_p, s_p, jnp.float32, interpret=True)
    x_r = bq_ref.dequantize_ref(q_r, s_r, jnp.float32)
    # +-1 code -> up to one scale step apart
    np.testing.assert_allclose(
        np.asarray(x_p), np.asarray(x_r), atol=float(np.asarray(s_r).max()) * 1.01
    )


def test_block_quant_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (64, 512), jnp.float32)
    q, s = bq_ops.quantize(x)
    xr = bq_ops.dequantize(q, s)
    # absmax int8: |err| <= scale/2 = absmax/254 per block
    blocks = np.asarray(x).reshape(64, 4, 128)
    bound = np.abs(blocks).max(-1) / 254 + 1e-7
    err = np.abs(np.asarray(xr) - np.asarray(x)).reshape(64, 4, 128).max(-1)
    assert (err <= bound * 1.01).all()


@pytest.mark.parametrize("r,c", [(512, 2048), (61440, 6912), (5120, 17408), (829440, 128),
                                 (300, 256), (1, 1024)])
def test_block_quant_tiles_are_tpu_legal(r, c):
    """Every tile the TPU lowering sees: rows a multiple of 32 (int8
    packing) or all of R; the scales tile's last dim all of C/BLOCK or a
    multiple of 128 lanes; an f32 input tile within TILE_BYTES."""
    tr, tc = _tiles(r, c)
    assert tr == r or tr % ROW_ALIGN == 0
    assert tc == c or (tc // BLOCK) % 128 == 0
    assert tr * tc * 4 <= TILE_BYTES or tr == r


def test_block_quant_zero_block():
    x = jnp.zeros((8, 256), jnp.float32)
    q, s = quantize_pallas(x, interpret=True)
    assert np.asarray(q).sum() == 0
    xr = dequantize_pallas(q, s, interpret=True)
    assert np.asarray(xr).sum() == 0


@settings(max_examples=20, deadline=None)
@given(
    r=st.integers(1, 64),
    cb=st.integers(1, 6),
    scale=st.floats(1e-3, 1e3),
)
def test_block_quant_property_roundtrip(r, cb, scale):
    """Property: round-trip error is within the absmax/254 bound for any
    shape and dynamic range."""
    c = cb * 128
    x = np.random.default_rng(r * cb).normal(size=(r, c)).astype(np.float32) * scale
    q, s = bq_ref.quantize_ref(jnp.asarray(x))
    xr = np.asarray(bq_ref.dequantize_ref(q, s))
    bound = np.abs(x.reshape(r, cb, 128)).max(-1, keepdims=True) / 254 + 1e-9
    assert (np.abs(xr - x).reshape(r, cb, 128) <= bound * 1.01 + 1e-7).all()


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


ATTN_CASES = [
    # (B, Sq, Skv, H, KVH, D, causal, window)
    (1, 128, 128, 2, 2, 64, True, 0),
    (2, 256, 256, 4, 2, 64, True, 0),  # GQA
    (1, 256, 256, 2, 1, 128, True, 128),  # SWA
    (1, 128, 256, 2, 2, 64, False, 0),  # cross-ish (non-causal, longer kv)
    (2, 128, 128, 4, 4, 32, True, 0),
]


@pytest.mark.parametrize("b,sq,skv,h,kvh,d,causal,window", ATTN_CASES)
def test_flash_attention_matches_ref(b, sq, skv, h, kvh, d, causal, window):
    ks = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, kvh, d), jnp.float32)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, bq=128, bk=128, interpret=True
    )
    expect = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    expect = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=2e-2
    )


def test_flash_attention_matches_model_path():
    """Kernel vs the chunked-jnp production path (models.nn.attention)."""
    from repro.models import nn

    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.float32)
    out_k = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    out_m = nn.attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m), atol=3e-5)


# --------------------------------------------------------------------------
# mamba selective scan
# --------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,d,n,chunk", [
    (1, 128, 256, 16, 128),
    (2, 256, 256, 16, 128),
    (1, 256, 512, 8, 64),
])
def test_mamba_scan_matches_ref(b, s, d, n, chunk):
    ks = jax.random.split(jax.random.key(s * d), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[1], (d, n)) * 0.5)
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    x = jax.random.normal(ks[4], (b, s, d))
    y_p, h_p = selective_scan_pallas(dt, a, bm, cm, x, chunk=chunk, tile_d=256, interpret=True)
    y_r, h_r = selective_scan_ref(dt, a, bm, cm, x)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r), atol=1e-4, rtol=1e-4)


def test_mamba_scan_matches_model_chunked_path():
    """Kernel oracle vs the production chunked associative scan in models."""
    from repro.models.mamba import intra_chunk_scan

    b, s, d, n = 1, 64, 32, 8
    ks = jax.random.split(jax.random.key(1), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)))
    a = -jnp.exp(jax.random.normal(ks[1], (d, n)) * 0.3)
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    x = jax.random.normal(ks[4], (b, s, d))
    da = jnp.exp(dt[..., None] * a)
    dbx = (dt * x)[..., None] * bm[:, :, None, :]
    h_all, h_last = intra_chunk_scan(da, dbx, jnp.zeros((b, d, n)))
    y_assoc = jnp.einsum("bsdn,bsn->bsd", h_all, cm)
    y_ref, h_ref = selective_scan_ref(dt, a, bm, cm, x)
    np.testing.assert_allclose(np.asarray(y_assoc), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref), atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_mamba_scan_property_decay_bounds(seed):
    """Property: with |C|<=1, |B|<=1, |x|<=1 and decay in (0,1), the state is
    bounded by dt_sum and the scan never produces non-finite values."""
    rng = np.random.default_rng(seed)
    b, s, d, n = 1, 32, 16, 4
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, d))), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(d, n)), jnp.float32))
    bm = jnp.asarray(rng.uniform(-1, 1, (b, s, n)), jnp.float32)
    cm = jnp.asarray(rng.uniform(-1, 1, (b, s, n)), jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (b, s, d)), jnp.float32)
    y, h = selective_scan_ref(dt, a, bm, cm, x)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()


# --------------------------------------------------------------------------
# wrapper dispatch (repro.kernels.dispatch): off the TPU the kernel runs
# only in interpret mode, and only when asked for
# --------------------------------------------------------------------------


def _wrapper_calls():
    x = jnp.ones((8, 256), jnp.float32)
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    dt = jnp.full((1, 128, 256), 0.1)
    a = -jnp.ones((256, 4))
    bm = jnp.ones((1, 128, 4))
    return {
        "block_quant": lambda **kw: bq_ops.quantize(x, **kw),
        "flash_attention": lambda **kw: fa_ops.flash_attention(q, q, q, **kw),
        "mamba_scan": lambda **kw: ms_ops.selective_scan(dt, a, bm, bm, dt, **kw),
    }


@pytest.mark.parametrize("kernel", ["block_quant", "flash_attention", "mamba_scan"])
def test_use_kernel_without_tpu_raises(kernel):
    call = _wrapper_calls()[kernel]
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        call(use_kernel=True)


@pytest.mark.parametrize("kernel", ["block_quant", "flash_attention", "mamba_scan"])
def test_use_kernel_interpret_matches_default_path(kernel):
    call = _wrapper_calls()[kernel]
    kern = jax.tree.leaves(call(use_kernel=True, interpret=True))
    plain = jax.tree.leaves(call())
    for k, p in zip(kern, plain):
        np.testing.assert_allclose(np.asarray(k, np.float32), np.asarray(p, np.float32),
                                   atol=1e-4, rtol=1e-4)
