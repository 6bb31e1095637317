"""The dense decoder, for configurations that name ``"model_files":
"dense"``: a Llama-style pre-norm block (RMSNorm, rotary attention with
grouped key/value heads and an optional sliding window, SwiGLU MLP), its
seeded weights, its plain f32 reference and the operations and bytes it
needs.  ``m`` is a cell's ``model``: the configuration's sizes in the
source's keys.

Weights (``shapes``, ``master``, ``check_layout``): laid out as the
program's dense trunk takes them (one stacked segment ``seg0``).  Matrices
are N(0, 1/fan_in), the embedding is N(0, 1/hidden) (so a tied head gives
unit-scale logits), norm scales are 1.

Reference (``reference``): written from the published description and
independent of the program's ``models/`` code.  Every matmul runs at
``highest`` precision; one sequence at a time, layer by layer under
``lax.scan``, attention in blocks of query rows.

Counts, from the configuration's shapes alone:

- FLOPs: 2 per matmul parameter per token, plus attention over the (query,
  key) pairs that the causal mask and the sliding window keep: 2*Dh for the
  score and 2*Dh for the weighted value, per head.  The LM head counts only
  where logits are needed (the last prompt position in prefill, every
  decode step).
- Decode bytes per step: every weight at bf16 (the configuration's
  precision) once, the embedding rows of the batch's tokens, and the bf16
  keys and values of every position each request attends to, plus the new
  ones written.
- The attention core of a decode step: its score and weighted-sum FLOPs,
  and the keys and values it reads and writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
import weights
from count import BF16, attended, causal_pairs

NORMS = ("ln1", "ln2", "ln_f")
# the names the program's dense serving path gives its parts
# (``jax.named_scope``), which ``scope_split.op_scopes`` labels by
SCOPES = ("embed", "layers", "attn_proj", "attn_core", "mlp", "norm", "lm_head", "sample")


# -- seeded weights ---------------------------------------------------------

def shapes(m: dict) -> dict:
    """Leaf shapes from the configuration's sizes."""
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


def master(m: dict, key: jax.Array) -> dict:
    """The f32 master tree; call inside ``jax.jit`` to make it in one call."""

    def leaf(path, shape):
        if path.rsplit("/", 1)[-1] in NORMS:
            return jnp.ones(shape, jnp.float32)
        fan_in = m["hidden_size"] if path == "embed" else shape[-2]
        return weights.normal(key, path, shape, fan_in)

    return weights.build(shapes(m), leaf)


def check_layout(m: dict, program_shapes: dict) -> None:
    """Raise unless the program's parameter tree has exactly these shapes."""
    weights.check_layout(shapes(m), program_shapes)


# -- plain f32 reference ----------------------------------------------------

def _rope(x, pos, theta):
    """x: (S, H, Dh); rotate the two halves of each head (Llama's rotate_half)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m, x, p):
    s = x.shape[0]
    h, kvh, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    pos = jnp.arange(s)
    a = p["attn"]
    y = ref.rms(x, p["ln1"], eps)
    q = _rope((y @ a["wq"]).reshape(s, h, dh), pos, m["rope_theta"])
    k = _rope((y @ a["wk"]).reshape(s, kvh, dh), pos, m["rope_theta"])
    v = (y @ a["wv"]).reshape(s, kvh, dh)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    o = ref.causal_attention(q, k, v, m.get("sliding_window") or 0)
    x = x + o @ a["wo"]
    y = ref.rms(x, p["ln2"], eps)
    f = p["ffn"]
    g = y @ f["w_gate"]
    return x + ((g * jax.nn.sigmoid(g)) * (y @ f["w_up"])) @ f["w_down"]


@functools.partial(jax.jit, static_argnums=0)
def _logits_at(mkey, mst, tokens, read):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        x = mst["embed"][tokens]
        x, _ = jax.lax.scan(lambda c, p: (_layer(m, c, p), None), x, mst["seg0"])
        x = ref.rms(x[read], mst["ln_f"], m["rms_norm_eps"])
        head = mst["embed"].T if m["tie_word_embeddings"] else mst["lm_head"]
        return x @ head


def reference(m: dict, key: jax.Array):
    """``logits(tokens, read)``: the f32 logits (len(read), V) of one
    sequence at positions ``read``, with the weights made from ``key`` as
    ``master`` makes them.  The f32 master is made once and held by the
    function; drop the function to free it."""
    mst = jax.jit(lambda k: master(m, k))(key)
    mkey = ref.static(m)

    def logits(tokens: np.ndarray, read: np.ndarray) -> jax.Array:
        return _logits_at(mkey, mst, jnp.asarray(ref.padded(tokens)),
                          jnp.asarray(read, jnp.int32))

    return logits


# -- counts -----------------------------------------------------------------

def layer_matmul_params(m: dict) -> int:
    d, h, kvh, dh = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    attn = d * h * dh + 2 * d * kvh * dh + h * dh * d
    return attn + 3 * d * m["intermediate_size"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def weight_bytes(m: dict) -> int:
    """Every weight once at bf16: layers with their norms, the head, ln_f.
    A separate (untied) input embedding is read a row at a time, not here."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    return BF16 * (L * (layer_matmul_params(m) + 2 * d) + head_params(m) + d)


def attn_flops_per_pair(m: dict) -> int:
    return 4 * m["num_attention_heads"] * m["head_dim"] * m["num_hidden_layers"]


def kv_bytes_per_token(m: dict) -> int:
    return BF16 * 2 * m["num_key_value_heads"] * m["head_dim"] * m["num_hidden_layers"]


def prefill_flops(m: dict, batch: int, prompt: int) -> int:
    per_seq = (2 * m["num_hidden_layers"] * layer_matmul_params(m) * prompt
               + attn_flops_per_pair(m) * causal_pairs(m, prompt)
               + 2 * head_params(m))
    return batch * per_seq


def decode_flops(m: dict, batch: int, pos: int) -> int:
    """One decode step: every request writes position ``pos``."""
    per_seq = (2 * (m["num_hidden_layers"] * layer_matmul_params(m) + head_params(m))
               + attn_flops_per_pair(m) * attended(m, pos))
    return batch * per_seq


def decode_bytes(m: dict, batch: int, pos: int) -> int:
    return weight_bytes(m) + BF16 * batch * m["hidden_size"] + attn_core_bytes(m, batch, pos)


def attn_core_flops(m: dict, batch: int, pos: int) -> int:
    """Scores and weighted sums of one decode step at position ``pos``."""
    return attn_flops_per_pair(m) * batch * attended(m, pos)


def attn_core_bytes(m: dict, batch: int, pos: int) -> int:
    """bf16 keys and values of one decode step: those attended to, read,
    and the new ones, written."""
    return kv_bytes_per_token(m) * batch * (attended(m, pos) + 1)
