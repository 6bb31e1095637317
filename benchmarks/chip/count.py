"""Operations and bytes that the algorithm needs, from the configuration's
shapes alone: what any program serving this model must do, not what this
program happens to do.  A share computed from these can only rise
toward 100% as the program does less waste.

- FLOPs: 2 per matmul parameter per token, plus attention over the (query,
  key) pairs that the causal mask and the sliding window keep: 2*Dh for the
  score and 2*Dh for the weighted value, per head.  The LM head counts only
  where logits are needed (the last prompt position in prefill, every
  decode step).
- Decode bytes per step: every weight at bf16 (the configuration's
  precision) once, the embedding rows of the batch's tokens, and the bf16
  keys and values of every position each request attends to, plus the new
  ones written.
"""
from __future__ import annotations

BF16 = 2


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


def attended(m: dict, pos: int) -> int:
    """Keys the query at absolute position ``pos`` attends to (itself included)."""
    w = m.get("sliding_window") or 0
    return min(pos + 1, w) if w else pos + 1


def causal_pairs(m: dict, n: int) -> int:
    """(query, key) pairs of a causal pass over n tokens, window applied."""
    w = m.get("sliding_window") or 0
    if not w or n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w


def attn_flops_per_pair(m: dict) -> int:
    return 4 * m["num_attention_heads"] * m["head_dim"] * m["num_hidden_layers"]


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


def kv_bytes_per_token(m: dict) -> int:
    return BF16 * 2 * m["num_key_value_heads"] * m["head_dim"] * m["num_hidden_layers"]


def decode_bytes(m: dict, batch: int, pos: int) -> int:
    kv = kv_bytes_per_token(m) * batch * (attended(m, pos) + 1)
    return weight_bytes(m) + BF16 * batch * m["hidden_size"] + kv

