"""The dense model-files module's counts (and count.py's window arithmetic)
against sums worked out by hand, for danube and for a tied, windowless MHA
model (MiniCPM-2B's published widths, arXiv:2404.06395, at 20 layers), and
against the program's own parameter count."""
import pytest

import cell
import count

dense = cell.model_files("dense")

DANUBE = cell.model_sizes(cell.load_json(cell.HERE / "configs" / "danube.json"))
MINICPM = {"num_hidden_layers": 20, "hidden_size": 2304, "num_attention_heads": 36,
           "num_key_value_heads": 36, "head_dim": 64, "intermediate_size": 5760,
           "vocab_size": 122753, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": True, "sliding_window": None}


def test_danube_by_hand():
    # q 2560x2560, k and v 2560x640 each, o 2560x2560, gate/up/down 2560x6912
    layer = 2560 * 2560 * 2 + 2560 * 640 * 2 + 3 * 2560 * 6912
    assert layer == 69_468_160
    assert dense.layer_matmul_params(DANUBE) == layer
    assert dense.head_params(DANUBE) == 2560 * 32000
    # weights at bf16: 24 layers with two norms each, the head, ln_f
    assert dense.weight_bytes(DANUBE) == 2 * (24 * (layer + 2 * 2560) + 81_920_000 + 2560)
    # 2 FLOPs per parameter per token + 4*H*Dh per kept (q, k) pair per layer
    pairs_8192 = 4096 * 4097 // 2 + 4096 * 4096  # the 4096 window binds after 4096
    assert count.causal_pairs(DANUBE, 8192) == pairs_8192
    assert dense.prefill_flops(DANUBE, 1, 8192) == (
        2 * 24 * layer * 8192 + 4 * 32 * 80 * 24 * pairs_8192 + 2 * 2560 * 32000)
    # decode at position 5000 attends to the 4096 window; KV 2560 B/token/layer
    assert count.attended(DANUBE, 5000) == 4096
    assert dense.kv_bytes_per_token(DANUBE) == 2 * 2 * 8 * 80 * 24
    assert dense.decode_bytes(DANUBE, 16, 5000) == (
        dense.weight_bytes(DANUBE) + 2 * 16 * 2560 + 61_440 * 16 * 4097)


def test_minicpm_by_hand():
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760  # MHA: 36 heads x 64 = 2304
    assert layer == 61_046_784
    assert dense.layer_matmul_params(MINICPM) == layer
    assert dense.head_params(MINICPM) == 2304 * 122_753  # tied: the embedding
    assert count.causal_pairs(MINICPM, 3072) == 3072 * 3073 // 2  # no window
    assert count.attended(MINICPM, 3583) == 3584
    assert dense.decode_flops(MINICPM, 4, 3583) == 4 * (
        2 * (20 * layer + 2304 * 122_753) + 4 * 36 * 64 * 20 * 3584)
    assert dense.prefill_flops(MINICPM, 2, 4) == 2 * (
        2 * 20 * layer * 4 + 4 * 36 * 64 * 20 * 10 + 2 * 2304 * 122_753)


@pytest.mark.parametrize("m", [DANUBE, MINICPM], ids=["danube", "minicpm"])
def test_matches_program_param_count(m):
    from repro.models import model as M

    conf = dict(m, arch="h2o-danube-1.8b" if m is DANUBE else "minicpm-2b",
                reduced=["num_hidden_layers"])
    cfg = cell.model_config(conf)
    d, L = m["hidden_size"], m["num_hidden_layers"]
    embed = 0 if m["tie_word_embeddings"] else m["vocab_size"] * d
    expect = L * (dense.layer_matmul_params(m) + 2 * d) + dense.head_params(m) + d + embed
    assert M.param_count(cfg) == expect
