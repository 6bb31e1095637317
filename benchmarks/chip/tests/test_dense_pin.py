"""danube's seeded weights, plain reference, counts and metric readers,
pinned to the values they gave before the dense layout's code became a
model-files module: the seeded f32 master and the reference's logits of the
tiny configuration (digests of their bytes), every count function at
danube's and the tiny sizes, and each reader on one record built by hand.
The module has to reproduce all of them exactly."""
import hashlib

import jax
import numpy as np
import pytest

import cell
import peaks
import scope_split
import weights
from tiny import model as tiny_model

DANUBE = cell.model_sizes(cell.load_json(cell.HERE / "configs" / "danube.json"))
TINY = cell.model_sizes(tiny_model())
READERS = ("decode_step_ms", "decode_roofline", "mfu.gen", "device_idle.gen",
           "weight_bytes_per_token.gen")


def _digest(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]


def _master(mf, m, seed):
    return jax.jit(lambda k: mf.master(m, k))(weights.seed_key(seed))


def _logits(mf, m, seed, tokens, read):
    return mf.reference(m, weights.seed_key(seed))(tokens, read)


def _positions():
    """Three batches of 255 decode steps: prompts 512, 1024, 2048."""
    return [n + j for n in (512, 1024, 2048) for j in range(255)]


def _rec(mf):
    positions = _positions()
    split = {"attn_core": 1.9, "attn_proj": 0.5, "mlp": 2.6, "lm_head": 0.16,
             "layer_loop": 7.9, "unscoped": 4.0, "norm": 0.03}
    red = {"window_s": 30.0, "busy_s": 27.8,
           "programs": {"jit_decode_step": {"n": len(positions), "device_s": 17.2},
                        "jit_prefill": {"n": 3, "device_s": 1.6}},
           "scopes": {"jit_decode_step": split}}
    return {"model": DANUBE, "model_files": mf,
            "traffic": cell.load_json(cell.HERE / "traffic" / "chat-decode.json"),
            "peaks": peaks.PEAKS["TPU v5 lite"], "trace": red,
            "work": {"batch": 16, "prefills": [512, 1024, 2048], "decode_positions": positions},
            "weight_bytes": 3_662_397_440}


def _counts(mf, m):
    out = {"layer_matmul_params": mf.layer_matmul_params(m), "head_params": mf.head_params(m),
           "weight_bytes": mf.weight_bytes(m), "kv_bytes_per_token": mf.kv_bytes_per_token(m),
           "attn_flops_per_pair": mf.attn_flops_per_pair(m)}
    for n in (1, 24, 511, 4096, 8192):
        out[f"prefill_flops.{n}"] = mf.prefill_flops(m, 3, n)
    for pos in (0, 15, 16, 700, 5000):
        for name in ("decode_flops", "decode_bytes", "attn_core_flops", "attn_core_bytes"):
            out[f"{name}.{pos}"] = getattr(mf, name)(m, 16, pos)
    return out


def _tokens(m, n=40):
    return np.random.default_rng(5).integers(0, m["vocab_size"], n, dtype=np.int32)


def observed_master(mf):
    out = {}
    for tied in (False, True):
        m = cell.model_sizes(tiny_model(tied=tied, window=0 if tied else 16))
        flat = jax.tree_util.tree_flatten_with_path(_master(mf, m, 3))[0]
        for path, leaf in flat:
            out[f"{int(tied)}/" + "/".join(k.key for k in path)] = _digest(leaf)
    return out


def observed_logits(mf):
    out = {}
    for tied in (False, True):
        m = cell.model_sizes(tiny_model(tied=tied, window=0 if tied else 16))
        out[int(tied)] = _digest(_logits(mf, m, 3, _tokens(m), np.arange(20, 40)))
    return out


def observed_counts(mf):
    return {name: _counts(mf, m) for name, m in (("danube", DANUBE), ("tiny", TINY))}


def observed_readers(mf):
    rec = _rec(mf)
    out = {name: cell.metric_reader(name)(rec) for name in READERS}
    out.update({name: read(rec) for name, read in scope_split.METRICS.items()})
    return out


PINNED_MASTER = {
    '0/embed': 'fbe2efcde501cf4d',
    '0/lm_head': '972b5e711bdf402d',
    '0/ln_f': '2f20cd03c9cd392a',
    '0/seg0/attn/wk': 'bcbb7a13b80eb7be',
    '0/seg0/attn/wo': '1487253ede2c2f42',
    '0/seg0/attn/wq': 'a4bbc810d73b5958',
    '0/seg0/attn/wv': '2990a21b972f1dee',
    '0/seg0/ffn/w_down': '4a533de7cef67456',
    '0/seg0/ffn/w_gate': '042b6ecd29bb8d13',
    '0/seg0/ffn/w_up': '676783fd8e95048a',
    '0/seg0/ln1': '02722f124d0f1736',
    '0/seg0/ln2': '02722f124d0f1736',
    '1/embed': 'fbe2efcde501cf4d',
    '1/ln_f': '2f20cd03c9cd392a',
    '1/seg0/attn/wk': 'bcbb7a13b80eb7be',
    '1/seg0/attn/wo': '1487253ede2c2f42',
    '1/seg0/attn/wq': 'a4bbc810d73b5958',
    '1/seg0/attn/wv': '2990a21b972f1dee',
    '1/seg0/ffn/w_down': '4a533de7cef67456',
    '1/seg0/ffn/w_gate': '042b6ecd29bb8d13',
    '1/seg0/ffn/w_up': '676783fd8e95048a',
    '1/seg0/ln1': '02722f124d0f1736',
    '1/seg0/ln2': '02722f124d0f1736',
}
PINNED_LOGITS = {
    0: '59873f5360847486',
    1: '2f5762a52fa86b7f',
}
PINNED_COUNTS = {
    'danube': {
        'attn_core_bytes.0': 1966080,
        'attn_core_bytes.15': 16711680,
        'attn_core_bytes.16': 17694720,
        'attn_core_bytes.5000': 4027514880,
        'attn_core_bytes.700': 690094080,
        'attn_core_flops.0': 3932160,
        'attn_core_flops.15': 62914560,
        'attn_core_flops.16': 66846720,
        'attn_core_flops.5000': 16106127360,
        'attn_core_flops.700': 2756444160,
        'attn_flops_per_pair': 245760,
        'decode_bytes.0': 3500610560,
        'decode_bytes.15': 3515356160,
        'decode_bytes.16': 3516339200,
        'decode_bytes.5000': 7526159360,
        'decode_bytes.700': 4188738560,
        'decode_flops.0': 55976919040,
        'decode_flops.15': 56035901440,
        'decode_flops.16': 56039833600,
        'decode_flops.5000': 72079114240,
        'decode_flops.700': 58729431040,
        'head_params': 81920000,
        'kv_bytes_per_token': 61440,
        'layer_matmul_params': 69468160,
        'prefill_flops.1': 10495672320,
        'prefill_flops.24': 240794664960,
        'prefill_flops.4096': 47160742379520,
        'prefill_flops.511': 5208684625920,
        'prefill_flops.8192': 100504236195840,
        'weight_bytes': 3498562560,
    },
    'tiny': {
        'attn_core_bytes.0': 8192,
        'attn_core_bytes.15': 69632,
        'attn_core_bytes.16': 69632,
        'attn_core_bytes.5000': 69632,
        'attn_core_bytes.700': 69632,
        'attn_core_flops.0': 8192,
        'attn_core_flops.15': 131072,
        'attn_core_flops.16': 131072,
        'attn_core_flops.5000': 131072,
        'attn_core_flops.700': 131072,
        'attn_flops_per_pair': 512,
        'decode_bytes.0': 191104,
        'decode_bytes.15': 252544,
        'decode_bytes.16': 252544,
        'decode_bytes.5000': 252544,
        'decode_bytes.700': 252544,
        'decode_flops.0': 2891776,
        'decode_flops.15': 3014656,
        'decode_flops.16': 3014656,
        'decode_flops.5000': 3014656,
        'decode_flops.700': 3014656,
        'head_params': 16384,
        'kv_bytes_per_token': 256,
        'layer_matmul_params': 36864,
        'prefill_flops.1': 542208,
        'prefill_flops.24': 11120640,
        'prefill_flops.4096': 1912516608,
        'prefill_flops.511': 238522368,
        'prefill_flops.8192': 3825119232,
        'weight_bytes': 180864,
    },
}
PINNED_READERS = {
    'decode_attn_core_ms': 2.4836601307189543,
    'decode_attn_roofline': 63.969617582417584,
    'decode_layer_loop_ms': 10.326797385620916,
    'decode_matmul_ms': 4.261437908496733,
    'decode_roofline': 26.066205571173015,
    'decode_step_ms': 22.483660130718953,
    'decode_unscoped_ms': 5.228758169934641,
    'device_idle.gen': 7.333333333333336,
    'mfu.gen': 4.210629849275127,
    'weight_bytes_per_token.gen': 228.89984,
}


@pytest.fixture(scope="module")
def mf():
    return cell.model_files("dense")


def test_master_is_pinned(mf):
    assert observed_master(mf) == PINNED_MASTER


def test_reference_is_pinned(mf):
    assert observed_logits(mf) == PINNED_LOGITS


def test_counts_are_pinned(mf):
    assert observed_counts(mf) == PINNED_COUNTS


def test_readers_are_pinned(mf):
    assert observed_readers(mf) == PINNED_READERS
