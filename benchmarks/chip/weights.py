"""Seeded weights for a dense decoder, made by the benchmark and not by the
program, so that the plain reference can make the same f32 master from the
seed alone.

The tree is laid out as the program's dense trunk takes it (one stacked
segment ``seg0``); ``check_layout`` holds it against the program's own
parameter specs at set-up.  Each leaf draws from its own key, folded from
the seed key and the leaf's path, so a leaf's values do not depend on what
other leaves exist.  Matrices are N(0, 1/fan_in), the embedding is
N(0, 1/hidden) (so a tied head gives unit-scale logits), norm scales are 1.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including seeds wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(m: dict) -> dict:
    """Leaf shapes from the configuration's sizes (``model`` of a cell)."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, kvh, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f, v = m["intermediate_size"], m["vocab_size"]
    tree = {
        "embed": (v, d),
        "ln_f": (d,),
        "seg0": {
            "ln1": (L, d),
            "ln2": (L, d),
            "attn": {"wq": (L, d, h * dh), "wk": (L, d, kvh * dh),
                     "wv": (L, d, kvh * dh), "wo": (L, h * dh, d)},
            "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
        },
    }
    if not m["tie_word_embeddings"]:
        tree["lm_head"] = (d, v)
    return tree


def _leaf(path: str, shape, key, d_model: int):
    if path.rsplit("/", 1)[-1] in ("ln1", "ln2", "ln_f"):
        return jnp.ones(shape, jnp.float32)
    fan_in = d_model if path == "embed" else shape[-2]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)


def master(m: dict, key: jax.Array) -> dict:
    """The f32 master tree; call inside ``jax.jit`` to make it in one call."""

    def build(node, prefix):
        if isinstance(node, dict):
            return {k: build(v, f"{prefix}/{k}" if prefix else k) for k, v in node.items()}
        return _leaf(prefix, node, key, m["hidden_size"])

    return build(shapes(m), "")


def check_layout(m: dict, program_shapes: dict) -> None:
    """Raise unless the program's parameter tree has exactly these shapes."""
    ours = jax.tree.map(tuple, shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    theirs = jax.tree.map(lambda s: tuple(s.shape), program_shapes)
    if ours != theirs:
        raise ValueError(f"weight layout differs from the program's: {ours} != {theirs}")
