"""A cell's parts, found by name.  ``BENCHMARK.json`` at the checkout's root
names each cell's configuration and traffic mix and lists the metrics; the
parts themselves are files of their own:

- ``configs/<config>.json``  the model's sizes as run, in the source's keys;
- ``traffic/<traffic>.json`` the traffic mix (lengths, batch, loop);
- ``metrics/<metric>.py``    a per-layer metric's reader, ``read(rec)``;
- ``limits/<workload>.json`` the limit of each number compared for ``correct``.

Adding a cell, a mix or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

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
}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def model_sizes(conf: dict) -> dict:
    """The numbers of a configuration file that the model is built from."""
    return {k: conf.get(k) for k in FIELDS}


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry's
    architecture with the file's sizes.  A size that differs from the
    registry's and is not listed in ``reduced`` is an error."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    kw = {}
    for key, field in FIELDS.items():
        value = conf.get(key)
        if key == "sliding_window":
            value = value or 0
        if value != getattr(base, field) and key not in conf["reduced"]:
            raise ValueError(f"{conf['arch']}: {key}={value!r} differs from the program's "
                             f"{field}={getattr(base, field)!r} and is not in 'reduced'")
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
        "traffic": traffic,
        "end_to_end": e2e,
        "per_layer": per_layer,
        "limits": load_json(limits_path) if limits_path.exists() else None,
        "dir": here,
    }


def metric_reader(name: str, here: Path = HERE):
    """``read(rec)`` of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
