"""Decode attention as a grouped-query einsum over the cache as stored
(``nn.decode_attention``) against the formulation it replaced, kept here as
the oracle: K and V repeated per query head (``nn.repeat_kv``), then the
same bf16 einsums with f32 accumulation.  Outputs are bf16, so the two may
differ by a rounding of the last bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import nn

B, W, DH = 2, 24, 16
BF16_ULP = 2.0 ** -7  # bf16's spacing relative to a value, at its widest


def _ring_mask(pos, w):
    return pos - jnp.mod(pos - jnp.arange(w), w) >= 0


def _repeat_kv_core(q, k, v, mask):
    """The core as it was (``_decode_attn_abs``): K and V repeated per head."""
    _, _, h, dh = q.shape
    k = nn.repeat_kv(k, h)
    v = nn.repeat_kv(v, h)
    scores = jnp.einsum(
        "bqhd,bshd->bhs", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    scores = jnp.where(mask[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum(
        "bhs,bshd->bhd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return o[:, None].astype(q.dtype)


def _repeat_kv_full(q, k, v, pos):
    """The full-attention branch as it was, ``nn.attention``, over the
    filled slots alone (the masked ones weigh nothing)."""
    return nn.attention(q, k[:, :pos + 1], v[:, :pos + 1], causal=False)


# (mask, write position): a ring with unfilled slots, a wrapped ring, and a
# full-attention cache filled up to the position
MASKS = {"ring_unfilled": ("ring", 9), "ring_wrapped": ("ring", 2 * W + 5),
         "full": ("full", 13)}


@pytest.mark.parametrize("mask_kind", list(MASKS))
@pytest.mark.parametrize("h,kvh", [(32, 8), (40, 8), (36, 36)])
def test_grouped_core_matches_repeat_kv(h, kvh, mask_kind):
    kind, pos = MASKS[mask_kind]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(h * 100 + kvh + pos), 3)
    q = jax.random.normal(kq, (B, 1, h, DH), jnp.bfloat16)
    k = jax.random.normal(kk, (B, W, kvh, DH), jnp.bfloat16)
    v = jax.random.normal(kv, (B, W, kvh, DH), jnp.bfloat16)
    if kind == "ring":
        mask = _ring_mask(jnp.asarray(pos, jnp.int32), W)
        want = _repeat_kv_core(q, k, v, mask)
    else:
        mask = jnp.arange(W) <= pos
        want = _repeat_kv_full(q, k, v, pos)
    got = nn.decode_attention(q, k, v, mask)
    assert got.shape == want.shape == (B, 1, h, DH) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=BF16_ULP, atol=BF16_ULP)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen3-14b"])
def test_decode_step_matches_repeat_kv(arch, monkeypatch):
    """A reduced model's decode steps (GQA 8/2; danube's ring of 16 slots
    wraps) give the same logits with the grouped core and with the oracle."""
    cfg = dataclasses.replace(get_config(arch).reduced(), num_heads=8, num_kv_heads=2)
    params = nn.init_params(M.model_specs(cfg), jax.random.key(0), jnp.bfloat16)
    prompt, steps = 8, 12
    slots = cfg.window if cfg.attn_kind == "swa" else prompt + steps
    tokens = jax.random.randint(jax.random.key(1), (B, prompt + steps), 0,
                                cfg.vocab_size, jnp.int32)

    def run():  # traced anew, so that the patched core is the one compiled
        step = jax.jit(lambda c, t, p: M.decode_step(cfg, params, c, t, p))
        _, cache = M.prefill(cfg, params, {"tokens": tokens[:, :prompt]})
        cache = jax.tree.map(  # (layers, B, S, KVH, Dh): room for the steps
            lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, slots - prompt), (0, 0), (0, 0)]),
            cache)
        out = []
        for i in range(steps):
            logits, cache = step(cache, tokens[:, prompt + i],
                                 jnp.asarray(prompt + i, jnp.int32))
            out.append(logits)
        return np.asarray(jnp.stack(out), np.float32)

    got = run()
    monkeypatch.setattr(nn, "decode_attention", _repeat_kv_core)
    want = run()
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * BF16_ULP * np.abs(want).max())
