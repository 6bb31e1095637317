"""One KV-cache contract across the families (``models/kv_cache``).

A reduced model is prefilled with a prompt, its cache grown for the steps to
come as the serving driver grows it (``launch.serve._grow_cache``), and
decoded one token at a time.  Each step's logits must match the
teacher-forced prefill of the same prefix: one causal forward over the whole
sequence gives every prefix's last logits at once.  Activations are bf16, so
the two agree within a few bf16 roundings of the largest logit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import _grow_cache, serve
from repro.models import model as M
from repro.models import nn

B, PROMPT, STEPS, FRAMES = 2, 8, 12, 12
BF16_ULP = 2.0 ** -7


def _danube():
    """Sliding window 16, prompt 8, generated past the window: the cache
    grows to the window's 16 slots and wraps.  Grown to all 31 positions
    instead (the growth before this module), the ring mask saw no window and
    the logits were off by up to 3.5 from step 9 on."""
    return "h2o-danube-1.8b", {}, 23


def _qwen3():
    """Full attention: the cache grows to every position."""
    return "qwen3-14b", {}, STEPS


def _dbrx_two_segments():
    """A leading dense layer makes two segments, each grown and written.
    Routing is dropless here: expert capacity depends on how many tokens are
    routed together, so a capped forward and a decode step drop different
    assignments whatever the cache does."""
    return "dbrx-132b", {"num_layers": 3, "first_dense_layers": 1, "capacity_factor": 4.0}, STEPS


def _deepseek_mla():
    """Latent (MLA) cache: ``ckv`` and ``krope`` grow on their slot axis."""
    return "deepseek-v2-lite-16b", {"capacity_factor": 4.0}, STEPS


def _internvl2_prefix():
    """Vision prefix: positions run past the text, and the cache holds them."""
    return "internvl2-76b", {}, STEPS


def _zamba2_hybrid():
    """Shared attention between Mamba2 groups: only the attention cache
    grows.  The growth before this module guessed slots from rank and padded
    the SSM conv tails too, and the decode step raised."""
    return "zamba2-1.2b", {}, STEPS


def _whisper():
    """Encoder-decoder: self K/V grow, cross K/V over the same frames stay
    as they are.  The growth before this module zero-padded the cross K/V,
    which the cross attention then weighed, off by about 0.3."""
    return "whisper-base", {}, STEPS


def _falcon_mamba():
    """SSM alone: nothing grows.  The growth before this module padded the
    conv tails, and the decode step raised."""
    return "falcon-mamba-7b", {}, STEPS


CASES = {f.__name__[1:]: f for f in (_danube, _qwen3, _dbrx_two_segments, _deepseek_mla,
                                     _internvl2_prefix, _zamba2_hybrid, _whisper, _falcon_mamba)}


def _extras(cfg):
    """The non-token inputs: the same frames or patches for every prefix."""
    if cfg.family == "audio":
        return {"frames": jax.random.normal(jax.random.key(2), (B, FRAMES, cfg.d_model),
                                            jnp.bfloat16)}
    if cfg.family == "vlm":
        return {"patches": jax.random.normal(
            jax.random.key(2), (B, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)}
    return {}


@pytest.mark.parametrize("name", list(CASES))
def test_grown_decode_matches_prefill(name):
    arch, changes, steps = CASES[name]()
    cfg = dataclasses.replace(get_config(arch).reduced(), **changes)
    params = nn.init_params(M.model_specs(cfg), jax.random.key(0), jnp.bfloat16)
    n = PROMPT + steps
    tokens = jax.random.randint(jax.random.key(1), (B, n), 0, cfg.vocab_size, jnp.int32)
    extras = _extras(cfg)
    prefix = cfg.num_prefix_tokens if cfg.family == "vlm" else 0

    hidden, _, _ = jax.jit(lambda b: M.forward_hidden(cfg, params, b, training=False))(
        {"tokens": tokens, **extras})
    want = np.asarray(M.logits_at(cfg, params, hidden), np.float32)[:, PROMPT - 1:]

    logits, cache = jax.jit(lambda b: M.prefill(cfg, params, b))(
        {"tokens": tokens[:, :PROMPT], **extras})
    cache = _grow_cache(cfg, cache, prefix + n)
    step = jax.jit(lambda c, t, p: M.decode_step(cfg, params, c, t, p))
    got = [logits]
    for i in range(steps):
        logits, cache = step(cache, tokens[:, PROMPT + i],
                             jnp.asarray(prefix + PROMPT + i, jnp.int32))
        got.append(logits)
    got = np.stack([np.asarray(g, np.float32) for g in got], axis=1)
    assert got.shape == want.shape == (B, steps + 1, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * BF16_ULP * np.abs(want).max())


# one architecture per family; danube generates past its window of 16
SERVED = {"dense": "h2o-danube-1.8b", "moe": "deepseek-v2-lite-16b", "vlm": "internvl2-76b",
          "ssm": "falcon-mamba-7b", "hybrid": "zamba2-1.2b", "audio": "whisper-base"}


@pytest.mark.parametrize("family", list(SERVED))
def test_serve_runs_every_family(family):
    """The serving driver prefills, grows the cache and decodes for every
    family (before this module the ssm and hybrid families raised)."""
    assert get_config(SERVED[family]).family == family
    r = serve(SERVED[family], reduced=True, batch=2, prompt_len=8, gen_tokens=20)
    assert r["tokens"].shape == (2, 20) and r["logits_finite"]
