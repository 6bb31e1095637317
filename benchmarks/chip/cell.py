"""A cell's parts, found by name.  ``BENCHMARK.json`` at the checkout's root
names each cell's configuration and traffic mix and lists the metrics; the
parts themselves are files of their own:

- ``configs/<config>.json``  the model's sizes as run, in the source's keys,
                             and ``model_files``, the name of its module;
- ``models/<model_files>.py`` everything the benchmark knows of one weight
                             layout: seeded weights (``shapes``, ``master``,
                             ``check_layout``), the plain f32 reference
                             (``reference``) and counts (``prefill_flops``,
                             ``decode_flops``, ``decode_bytes``,
                             ``attn_core_flops``, ``attn_core_bytes``);
- ``traffic/<traffic>.json`` the traffic mix (lengths, batch, loop);
- ``metrics/<metric>.py``    a per-layer metric's reader, ``read(rec)``;
- ``limits/<workload>.json`` the limit of each number compared for ``correct``.

Adding a cell, a mix, a metric or a weight layout adds files and entries;
no file here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import replace
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# source key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "window",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
}
# keys of a configuration file that the harness reads itself
OWN_KEYS = ("arch", "model_files", "reduced")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def model_sizes(conf: dict) -> dict:
    """The configuration as the model is built from it: every key of the
    file but the harness's own, and every key of ``FIELDS`` (None where the
    file leaves it out)."""
    out = dict.fromkeys(FIELDS)
    out.update((k, v) for k, v in conf.items() if k not in OWN_KEYS)
    return out


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry's
    architecture with the file's sizes.  A key of ``FIELDS`` that the file
    states has to equal the registry's value or be listed in ``reduced`` (a
    key stated as null counts as 0); a key that the file leaves out has to
    be zero or unset in the registry.  Anything else is an error."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    kw = {}
    for key, field in FIELDS.items():
        ours = getattr(base, field)
        if key not in conf:
            if ours:
                raise ValueError(f"{conf['arch']}: the file leaves out {key}, and the "
                                 f"program's {field} is {ours!r}")
            continue
        value = 0 if conf[key] is None else conf[key]
        if value != ours and key not in conf["reduced"]:
            raise ValueError(f"{conf['arch']}: {key}={value!r} differs from the program's "
                             f"{field}={ours!r} and is not in 'reduced'")
        kw[field] = value
    return replace(base, **kw)


def load(workload: str, root: Path = ROOT) -> dict:
    """Everything one run of ``workload`` needs, read from files."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    here = root / bench["paths"][0]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(root / confs[w["config"]]["file"])
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)]
    limits_path = here / "limits" / f"{workload}.json"
    return {
        "name": workload,
        "chips": w["chips"],
        "conf": conf,
        "model": model_sizes(conf),
        "model_files": model_files(conf["model_files"], here),
        "traffic": traffic,
        "end_to_end": e2e,
        "per_layer": per_layer,
        "limits": load_json(limits_path) if limits_path.exists() else None,
        "dir": here,
    }


def model_files(name: str, here: Path = HERE):
    """The module ``models/<name>.py``, loaded once per process."""
    return _load_module(here / "models" / f"{name}.py")


@functools.cache
def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"chip_model_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    """``read(rec)`` of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
