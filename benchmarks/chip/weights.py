"""Seeded weights, made by the benchmark and not by the program, so that a
plain reference can make the same f32 values from the seed alone.

A configuration's model-files module (``models/<name>.py``) lays out the
tree as the program takes it and says how each leaf is drawn; it holds the
layout against the program's own parameter specs at set-up with
``check_layout``.  Each leaf draws from its own key, folded from the seed
key and the leaf's path, so a leaf's values do not depend on what other
leaves exist.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including seeds wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def normal(key: jax.Array, path: str, shape, fan_in: int) -> jax.Array:
    """N(0, 1/fan_in) in f32, from the leaf's own key."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)


def build(tree: dict, leaf, prefix: str = "") -> dict:
    """``leaf(path, shape)`` at every leaf of a tree of shapes; a path joins
    the keys from the root with ``/``."""
    return {k: build(v, leaf, f"{prefix}/{k}" if prefix else k) if isinstance(v, dict)
            else leaf(f"{prefix}/{k}" if prefix else k, v)
            for k, v in tree.items()}


def check_layout(ours: dict, program_shapes: dict) -> None:
    """Raise unless the program's parameter tree has exactly the shapes of
    ``ours``, a tree of shape tuples."""
    ours = jax.tree.map(tuple, ours, is_leaf=lambda x: isinstance(x, tuple))
    theirs = jax.tree.map(lambda s: tuple(s.shape), program_shapes)
    if ours != theirs:
        raise ValueError(f"weight layout differs from the program's: {ours} != {theirs}")
