"""A new traffic mix, per-layer metric or cell is found by its name alone:
dropping files in (and entries into BENCHMARK.json) needs no edit of any
file of the benchmark that is already there."""
import hashlib
import json
import shutil

import cell


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
