"""Serving driver: batched prefill -> decode with the DaeMon movement engine
on the KV/weight path.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import movement as mv
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import nn
from repro.runtime import sharding as shd


def serve(
    arch: str,
    *,
    reduced: bool = True,
    batch: int = 4,
    prompt_len: int = 64,
    gen_tokens: int = 32,
    movement: str = "daemon",
    mesh_shape=None,
    seed: int = 0,
):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(mesh_shape or (1, 1))
    rules = shd.base_rules(mesh, fsdp=True)
    shd.activate(mesh, rules)
    specs = M.model_specs(cfg)

    master = nn.init_params(specs, jax.random.key(seed))
    # the f32 master is only the source of the working copy: drop it, so a
    # full-width model does not hold both for the whole run
    params = mv.working_copy(master, mv.DAEMON_DEFAULT) if movement == "daemon" else master
    del master

    rng = np.random.default_rng(seed)
    total_len = prompt_len + gen_tokens
    batch_in = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    if cfg.family == "vlm":
        batch_in["patches"] = jnp.zeros((batch, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch_in["frames"] = jnp.zeros((batch, prompt_len, cfg.d_model), jnp.bfloat16)
    prefix = cfg.num_prefix_tokens if cfg.family == "vlm" else 0

    # compile both programs ahead of the timed runs, so neither timing
    # includes a compile
    t0 = time.perf_counter()
    prefill = jax.jit(lambda p, b: M.prefill(cfg, p, b)).lower(params, batch_in).compile()
    cache_shapes = jax.eval_shape(lambda c: _grow_cache(cfg, c, total_len),
                                  prefill.out_info[1])
    tok_shape = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    decode = jax.jit(steps_lib.make_decode_step(cfg), donate_argnums=(1,)).lower(
        params, cache_shapes, tok_shape, pos_shape).compile()
    t_compile = time.perf_counter() - t0

    jax.block_until_ready(prefill(params, batch_in))  # warm-up
    t0 = time.perf_counter()
    logits, cache = prefill(params, batch_in)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    # prefill builds a cache sized for the prompt; decode appends in a cache
    # sized total_len: re-home the prefill cache into the bigger buffers
    cache = _grow_cache(cfg, cache, total_len)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready((tok, cache))
    out_tokens = [tok]
    finite = jnp.all(jnp.isfinite(logits))
    t0 = time.perf_counter()
    for i in range(gen_tokens - 1):
        pos = jnp.asarray(prompt_len + prefix + i, jnp.int32)
        tok, step_logits, cache = decode(params, cache, tok, pos)
        finite &= jnp.all(jnp.isfinite(step_logits))
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    shd.deactivate()
    toks = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    return {
        "tokens": toks,
        "first_logits": np.asarray(logits, np.float32),
        "logits_finite": bool(finite),
        "compile_s": t_compile,
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / max(gen_tokens - 1, 1),
        "tokens_per_s": batch * (gen_tokens - 1) / max(t_decode, 1e-9),
    }


def _grow_cache(cfg, cache, total_len: int):
    """Pad seq-dim (axis 2: [L/inv, B, S, ...]) cache buffers up to
    total_len.  SWA ring caches are window-sized and stay put; SSM states
    have no seq dim and are untouched."""

    def grow(x):
        if x.ndim < 3:
            return x
        if cfg.attn_kind == "swa" and x.shape[2] == cfg.window:
            return x  # ring buffer
        if x.ndim >= 4 and x.shape[2] < total_len:
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, total_len - x.shape[2])
            return jnp.pad(x, pad)
        if x.ndim == 3 and cfg.attn_kind == "mla" and x.shape[1] < total_len:
            return x  # MLA caches are (L, B, S, R): handled by the 4-D branch
        return x

    return jax.tree.map(grow, cache)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--movement", default="daemon", choices=["baseline", "daemon"])
    a = ap.parse_args()
    enable_compile_cache()
    r = serve(
        a.arch, reduced=a.reduced, batch=a.batch, prompt_len=a.prompt_len,
        gen_tokens=a.gen, movement=a.movement,
    )
    print(
        f"compile {r['compile_s']:.2f}s; prefill {r['prefill_s']:.3f}s; decode {r['decode_s_per_token']*1e3:.1f} ms/tok; "
        f"{r['tokens_per_s']:.1f} tok/s; generated shape {r['tokens'].shape}"
    )


if __name__ == "__main__":
    main()
