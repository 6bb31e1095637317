"""Decode attention as a grouped-query einsum over the cache as stored
(``nn.decode_attention``) against the formulation it replaced, kept here as
the oracle: K and V repeated per query head (``nn.repeat_kv``), then the
same bf16 einsums with f32 accumulation.  Outputs are bf16, so the two may
differ by a rounding of the last bit.

The decode step reads the stacked cache where it lies and writes every
layer's new token once, after the layer scan (``transformer.trunk_decode``).
Its oracle is the decode as it was: each layer's slab scanned in, the token
written into it, the repeat-kv core over every slot, the slab re-stacked."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import nn, transformer

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


def _write_then_attend(cfg, p, x, cache, pos):
    """A GQA layer's decode attention as it was: the token written into the
    layer's slab at ``pos % w``, then the repeat-kv core over every slot."""
    q, k_new, v_new = transformer.gqa_qkv(cfg, p, x, pos[None], decode=True)
    w = cache["k"].shape[1]
    k = cache["k"].at[:, pos % w].set(k_new[:, 0])
    v = cache["v"].at[:, pos % w].set(v_new[:, 0])
    mask = _ring_mask(pos, w) if cfg.attn_kind == "swa" else jnp.arange(w) <= pos
    o = _repeat_kv_core(q, k, v, mask)
    out = jnp.einsum("bsk,kd->bsd", o.reshape(o.shape[0], 1, -1), p["wo"].astype(x.dtype))
    return out, {"k": k, "v": v}


def _scanned_trunk_decode(cfg, params, x, caches, pos):
    """``trunk_decode`` as it was: every layer's cache scanned in and the
    layer's written cache scanned out and re-stacked."""
    new = {}
    for seg in transformer.segments(cfg):
        def body(xx, scanned, _seg=seg):
            p_l, c_l = scanned
            return transformer.apply_block_decode(cfg, p_l, xx, c_l, pos, is_moe=_seg.is_moe)

        x, new[seg.name] = jax.lax.scan(body, x, (params[seg.name], caches[seg.name]))
    return x, new


def _old_decode(monkeypatch):
    """Route ``M.decode_step`` through the decode as it was (GQA layers write
    then attend; MLA layers are as they always were)."""
    monkeypatch.setattr(transformer, "gqa_decode_token", _write_then_attend)
    monkeypatch.setattr(transformer, "trunk_decode", _scanned_trunk_decode)


def _model(arch, **changes):
    cfg = dataclasses.replace(get_config(arch).reduced(), **changes)
    return cfg, nn.init_params(M.model_specs(cfg), jax.random.key(0), jnp.bfloat16)


def _prefilled(cfg, params, tokens, prompt, slots):
    """The prompt's cache, padded along the slot axis to ``slots``."""
    _, cache = M.prefill(cfg, params, {"tokens": tokens[:, :prompt]})
    return jax.tree.map(  # (layers, B, S, ...): room for the steps
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, slots - c.shape[2])]
                          + [(0, 0)] * (c.ndim - 3)), cache)


def _step(cfg, params):  # traced anew, so that a patched decode is the one compiled
    return jax.jit(lambda c, t, p: M.decode_step(cfg, params, c, t, p))


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen3-14b"])
def test_decode_step_matches_repeat_kv(arch, monkeypatch):
    """A reduced model's decode steps (GQA 8/2; danube's ring of 16 slots
    wraps) give the same logits as the old write-then-attend decode with
    the repeat-kv core."""
    cfg, params = _model(arch, num_heads=8, num_kv_heads=2)
    prompt, steps = 8, 12
    slots = cfg.window if cfg.attn_kind == "swa" else prompt + steps
    tokens = jax.random.randint(jax.random.key(1), (B, prompt + steps), 0,
                                cfg.vocab_size, jnp.int32)

    def run():
        step = _step(cfg, params)
        cache = _prefilled(cfg, params, tokens, prompt, slots)
        out = []
        for i in range(steps):
            logits, cache = step(cache, tokens[:, prompt + i],
                                 jnp.asarray(prompt + i, jnp.int32))
            out.append(logits)
        return np.asarray(jnp.stack(out), np.float32)

    got = run()
    _old_decode(monkeypatch)
    want = run()
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * BF16_ULP * np.abs(want).max())


# (arch, config changes): danube's ring of 16 slots wraps; qwen3 attends to
# the whole cache; dbrx with a leading dense layer has two segments
WRITES = {"danube": ("h2o-danube-1.8b", {}), "qwen3": ("qwen3-14b", {}),
          "dbrx_two_segments": ("dbrx-132b", {"num_layers": 3, "first_dense_layers": 1})}


@pytest.mark.parametrize("name", list(WRITES))
def test_post_scan_write_matches_old_write(name, monkeypatch):
    """Step by step from the same cache, the written cache equals the one the
    old decode writes: every layer's slot ``pos % w`` holds that layer's new
    K and V (bit-equal in the first layer, whose input no attention has
    touched; within bf16 rounding after it) and every other slot is
    bit-unchanged."""
    arch, changes = WRITES[name]
    cfg, params = _model(arch, **changes)
    assert len(transformer.segments(cfg)) == (2 if name == "dbrx_two_segments" else 1)
    prompt, steps = 8, 12
    slots = cfg.window if cfg.attn_kind == "swa" else prompt + steps
    tokens = jax.random.randint(jax.random.key(2), (B, prompt + steps), 0,
                                cfg.vocab_size, jnp.int32)
    cache = _prefilled(cfg, params, tokens, prompt, slots)
    new_step = _step(cfg, params)
    new_step(cache, tokens[:, 0], jnp.asarray(0, jnp.int32))  # compiled before the patch
    _old_decode(monkeypatch)
    old_step = _step(cfg, params)
    first = transformer.segments(cfg)[0].name
    for i in range(steps):
        pos = prompt + i
        args = (tokens[:, pos], jnp.asarray(pos, jnp.int32))
        _, got = new_step(cache, *args)
        _, want = old_step(cache, *args)
        slot = pos % slots
        others = np.arange(slots) != slot
        for seg in cache:
            for kv in ("k", "v"):
                c, g, w = (np.asarray(a[seg][kv], np.float32) for a in (cache, got, want))
                np.testing.assert_array_equal(g[:, :, others], c[:, :, others])
                np.testing.assert_array_equal(w[:, :, others], c[:, :, others])
                np.testing.assert_allclose(g[:, :, slot], w[:, :, slot], rtol=0,
                                           atol=4 * BF16_ULP * np.abs(w[:, :, slot]).max())
                if seg == first:
                    np.testing.assert_array_equal(g[0, :, slot], w[0, :, slot])
        cache = got


def test_mla_decode_untouched(monkeypatch):
    """A latent (MLA) cache keeps the scanned-in, scanned-out decode: cache
    and logits are bit-identical to the decode as it was."""
    cfg, params = _model("deepseek-v2-lite-16b")
    assert cfg.attn_kind == "mla" and len(transformer.segments(cfg)) == 2
    prompt, steps = 8, 6
    tokens = jax.random.randint(jax.random.key(3), (B, prompt + steps), 0,
                                cfg.vocab_size, jnp.int32)

    def run():
        step = _step(cfg, params)
        cache = _prefilled(cfg, params, tokens, prompt, prompt + steps)
        out = []
        for i in range(steps):
            logits, cache = step(cache, tokens[:, prompt + i],
                                 jnp.asarray(prompt + i, jnp.int32))
            out.append(logits)
        return np.asarray(jnp.stack(out), np.float32), jax.tree.map(np.asarray, cache)

    got_logits, got_cache = run()
    _old_decode(monkeypatch)
    want_logits, want_cache = run()
    np.testing.assert_array_equal(got_logits, want_logits)
    jax.tree.map(np.testing.assert_array_equal, got_cache, want_cache)
