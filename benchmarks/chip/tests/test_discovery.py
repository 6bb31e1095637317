"""A new traffic mix, per-layer metric, weight layout or cell is found by its
name alone: dropping files in (and entries into BENCHMARK.json) needs no
edit of any file of the benchmark that is already there.  A configuration's
sizes are held against the program's registry for every architecture it
builds, dense, MoE and MLA."""
import hashlib
import json
import shutil

import pytest

import cell
import scope_split


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmarks" / "chip").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts and "tests" not in p.parts}


def test_new_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cell.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", "tests"))
    bench = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    before = _digest(root)

    chip = root / "benchmarks" / "chip"
    (chip / "traffic" / "tiny-chat.json").write_text(json.dumps(
        {"kind": "serve", "batch": 2, "prompt_lens": [64], "gen_tokens": 8,
         "check_requests": 1}))
    (chip / "metrics" / "new_share.py").write_text(
        "def read(rec):\n    return 100.0 * rec['trace']['busy_s'] / rec['trace']['window_s']\n")
    bench["workloads"].append({"name": "danube.tiny-chat", "config": "danube",
                               "traffic": "tiny-chat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "gen_tok_s":
            m["workloads"].append("danube.tiny-chat")
    bench["per_layer"].append({"name": "new_share", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "gen_tok_s", "workloads": ["danube.tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cell.load("danube.tiny-chat", root)
    assert c["traffic"]["prompt_lens"] == [64]
    assert c["model"]["hidden_size"] == 2560
    assert [m["name"] for m in c["end_to_end"]] == ["gen_tok_s", "setup_s"]
    assert [m["name"] for m in c["per_layer"]] == ["new_share"]
    read = cell.metric_reader("new_share", c["dir"])
    assert read({"trace": {"busy_s": 1.0, "window_s": 4.0}}) == 25.0
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_cell_of_the_benchmark_loads():
    bench = cell.benchmark()
    for w in bench["workloads"]:
        c = cell.load(w["name"])
        assert (cell.HERE / f"{c['traffic']['kind']}_loop.py").exists()
        assert c["per_layer"], w["name"]
        names = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        for m in c["per_layer"]:
            assert callable(cell.metric_reader(m["name"], c["dir"]))
        cell.model_config(c["conf"])  # sizes agree with the program's registry


# DeepSeek-V2-Lite's config.json as the model-configs catalog holds it
# (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json)
DSV2_LITE = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy",
    "v_head_dim": 128, "vocab_size": 102400,
}


def _dsv2(**changes):
    """A configuration file for the program's deepseek-v2-lite-16b: the
    source's keys, ``head_dim`` as the program counts it (the query and key
    head, qk_nope + qk_rope; the source has no such key), and the program's
    norm epsilon (1e-5) listed as a change."""
    conf = dict(DSV2_LITE, arch="deepseek-v2-lite-16b", model_files="latent_moe", head_dim=192,
                reduced=["rms_norm_eps"])
    conf.update(changes)
    return conf


# A second weight layout, as a later configuration would bring it: its counts
# read the MoE and MLA sizes (a sketch, not DeepSeek's full arithmetic).
LATENT_MOE = '''
import jax.numpy as jnp

import weights
from count import BF16, attended

SCOPES = ("embed", "layers", "attn_core", "router", "experts", "lm_head")


def shapes(m):
    L, d, e = m["num_hidden_layers"], m["hidden_size"], m["n_routed_experts"]
    return {"embed": (m["vocab_size"], d),
            "experts": {"w_up": (L, e, d, m["moe_intermediate_size"])},
            "lm_head": (d, m["vocab_size"])}


def master(m, key):
    return weights.build(shapes(m), lambda path, shape: weights.normal(key, path, shape,
                                                                        shape[-2]))


def check_layout(m, program_shapes):
    weights.check_layout(shapes(m), program_shapes)


def reference(m, key):
    mst = master(m, key)
    return lambda tokens, read: mst["embed"][jnp.asarray(tokens)[read]] @ mst["lm_head"]


def _active_params(m):
    return (m["num_experts_per_tok"] + m["n_shared_experts"]) * 3 * m["hidden_size"] \\
        * m["moe_intermediate_size"]


def _latent_bytes(m):
    return BF16 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * m["num_hidden_layers"]


def prefill_flops(m, batch, prompt):
    return batch * prompt * 2 * m["num_hidden_layers"] * _active_params(m)


def decode_flops(m, batch, pos):
    return prefill_flops(m, batch, 1)


def decode_bytes(m, batch, pos):
    return BF16 * m["num_hidden_layers"] * _active_params(m) + attn_core_bytes(m, batch, pos)


def attn_core_flops(m, batch, pos):
    return 4 * batch * m["num_attention_heads"] * m["kv_lora_rank"] * attended(m, pos)


def attn_core_bytes(m, batch, pos):
    return _latent_bytes(m) * batch * (attended(m, pos) + 1)
'''


def test_new_model_files_are_found(tmp_path):
    """A configuration that names a module of its own (``model_files``):
    the module and the configuration are found by name, its sizes are held
    against the registry, and the metric readers count with its module; no
    file that was there changes."""
    import jax

    import weights

    root = tmp_path / "checkout"
    shutil.copytree(cell.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache", "tests"))
    bench = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    before = _digest(root)

    chip = root / "benchmarks" / "chip"
    (chip / "models" / "latent_moe.py").write_text(LATENT_MOE)
    (chip / "configs" / "dsv2lite.json").write_text(json.dumps(
        _dsv2(num_hidden_layers=7, reduced=["num_hidden_layers", "rms_norm_eps"])))
    (chip / "traffic" / "tiny-chat.json").write_text(json.dumps(
        {"kind": "serve", "batch": 32, "prompt_lens": [1024], "gen_tokens": 4,
         "check_requests": 1}))
    bench["configs"].append({"name": "dsv2lite", "source": "test",
                             "file": "benchmarks/chip/configs/dsv2lite.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "dsv2lite.tiny-chat", "config": "dsv2lite",
                               "traffic": "tiny-chat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("gen_tok_s", "mfu.gen"):
            m["workloads"].append("dsv2lite.tiny-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cell.load("dsv2lite.tiny-chat", root)
    mf, m = c["model_files"], c["model"]
    assert mf.__file__ == str(chip / "models" / "latent_moe.py")
    assert m["n_routed_experts"] == 64 and m["rope_scaling"]["factor"] == 40
    cfg = cell.model_config(c["conf"])
    assert (cfg.num_layers, cfg.num_experts, cfg.top_k, cfg.kv_lora_rank) == (7, 64, 6, 512)
    mf.check_layout(m, jax.eval_shape(lambda k: mf.master(m, k), weights.seed_key(2**40 + 5)))

    # 576 bf16 latent values a token a layer, 7 layers, 32 requests, 4096 keys
    assert mf.attn_core_bytes(m, 32, 4095) == 2 * 576 * 7 * 32 * 4097
    assert [p["name"] for p in c["per_layer"]] == ["mfu.gen"]
    rec = {"model": m, "model_files": mf, "traffic": c["traffic"],
           "peaks": {"bf16_flops": 1e12}, "trace": {"window_s": 2.0},
           "work": {"batch": 32, "prefills": [1024], "decode_positions": [1024, 1025, 1026]}}
    active = 2 * 7 * (6 + 2) * 3 * 2048 * 1408  # FLOPs per token
    expect = 100.0 * 32 * (1024 + 3) * active / (2.0 * 1e12)
    assert cell.metric_reader("mfu.gen", c["dir"])(rec) == pytest.approx(expect, rel=1e-12)
    # the scope split labels by the names the module lists
    hlo = ('HloModule jit_decode_step\n\nENTRY %main (x: bf16[8]) -> bf16[8] {\n'
           '  ROOT %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%f, '
           'metadata={op_name="jit(decode_step)/layers/while/body/router/top_k"}\n}\n')
    assert scope_split.program_tables([hlo], mf.SCOPES)[0] == {
        "jit_decode_step": {"fusion.1": "router"}}
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


MOE_MLA = {"n_routed_experts": ("num_experts", 32), "num_experts_per_tok": ("top_k", 4),
           "moe_intermediate_size": ("moe_d_ff", 1024), "n_shared_experts": ("num_shared_experts", 1),
           "first_k_dense_replace": ("first_dense_layers", 2),
           "kv_lora_rank": ("kv_lora_rank", 256), "qk_nope_head_dim": ("qk_nope_dim", 64),
           "qk_rope_head_dim": ("qk_rope_dim", 32), "v_head_dim": ("v_head_dim", 64)}


def test_source_sizes_match_the_registry():
    cfg = cell.model_config(_dsv2())
    assert (cfg.num_experts, cfg.top_k, cfg.moe_d_ff, cfg.num_shared_experts,
            cfg.first_dense_layers) == (64, 6, 1408, 2, 1)
    assert (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (512, 128, 64, 128)


@pytest.mark.parametrize("key", sorted(MOE_MLA))
def test_moe_and_mla_sizes_are_checked(key):
    """A MoE or MLA size that differs from the registry's is refused unless
    ``reduced`` lists it; listed, it is what the program is built with."""
    field, value = MOE_MLA[key]
    with pytest.raises(ValueError, match=key):
        cell.model_config(_dsv2(**{key: value}))
    cfg = cell.model_config(_dsv2(**{key: value, "reduced": ["rms_norm_eps", key]}))
    assert getattr(cfg, field) == value


@pytest.mark.parametrize("key", ["head_dim", "n_routed_experts", "kv_lora_rank"])
def test_a_size_left_out_must_be_unset_in_the_registry(key):
    conf = _dsv2()
    del conf[key]
    with pytest.raises(ValueError, match=f"leaves out {key}"):
        cell.model_config(conf)
    conf["reduced"].append(key)  # listing it does not help: there is no value to build with
    with pytest.raises(ValueError, match=f"leaves out {key}"):
        cell.model_config(conf)
