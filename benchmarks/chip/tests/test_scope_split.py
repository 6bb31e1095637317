"""scope_split.py's labels, on HLO text written by hand and on danube's
decode steps compiled for a TPU v5e; its reduction, on planes built by hand
and on the small trace recorded on a TPU v5e; and its per-step numbers on a
record built by hand."""
import collections
import gzip
import hashlib
import json
from pathlib import Path

import pytest

import cell
import peaks
import scope_split
import trace

MS = 1_000_000  # ns
DATA = Path(__file__).parent / "data"
RECORDED = DATA / "v5e_small.xplane.pb"
DANUBE = cell.model_sizes(cell.load_json(cell.HERE / "configs" / "danube.json"))
SCOPES = cell.model_files("dense").SCOPES


def _op(name, opcode="fusion"):
    return f"%{name} = bf16[16,2560]{{1,0}} {opcode}(bf16[16,2560]{{1,0}} %x)"


def _planes():
    dev = {
        "XLA Modules": [("jit_decode_step(11)", 10 * MS, 50 * MS),
                        ("jit_prefill(22)", 60 * MS, 70 * MS)],
        "XLA Ops": [(_op("while.1", "while"), 10 * MS, 50 * MS),
                    (_op("fusion.2"), 12 * MS, 20 * MS),
                    (_op("copy.3", "copy"), 25 * MS, 30 * MS),
                    (_op("mystery.9"), 40 * MS, 45 * MS),
                    (_op("fusion.2"), 60 * MS, 70 * MS),
                    (_op("fusion.2"), 200 * MS, 210 * MS)],
    }
    host = {"python": [("window", 0, 100 * MS), ("decode", 9 * MS, 51 * MS)]}
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


TABLES = {"jit_decode_step": {"while.1": "layer_loop", "fusion.2": "attn_core",
                              "copy.3": "unscoped"},
          "jit_prefill": {"fusion.2": "mlp"}}


def test_self_time_missing_ops_and_sums():
    red = scope_split.reduce_scopes(_planes(), TABLES)
    dec = red["scopes"]["jit_decode_step"]
    # the loop keeps the 40 ms its body ops (8 + 5 + 5) leave uncovered
    assert dec["layer_loop"] == pytest.approx(0.022)
    assert dec["attn_core"] == pytest.approx(0.008)
    # copy.3 is unscoped by its table; mystery.9 is in no table
    assert dec["unscoped"] == pytest.approx(0.010)
    # one instruction name, two programs: each program's own label
    assert red["scopes"]["jit_prefill"] == {"mlp": pytest.approx(0.010)}
    assert red["conflict_s"] == 0
    # the labels add up to the busy time: every op counted once, none outside the window
    base = trace.reduce_planes(_planes(), ("decode",))
    total = sum(t for split in red["scopes"].values() for t in split.values())
    assert total == pytest.approx(base["busy_s"])
    assert sum(dec.values()) == pytest.approx(base["programs"]["jit_decode_step"]["device_s"])


def _hlo(module, label):
    return f"""HloModule {module}, is_scheduled=true

ENTRY %main.1 (x: bf16[16,2560]) -> bf16[16,2560] {{
  %x = bf16[16,2560]{{1,0}} parameter(0)
  %copy.3 = bf16[16,2560]{{1,0}} copy(%x)
  ROOT %fusion.2 = bf16[16,2560]{{1,0}} fusion(%copy.3), kind=kLoop, calls=%f, metadata={{op_name="jit(decode_step)/{label}/dot_general"}}
}}
"""


def test_buckets_that_disagree_leave_the_instruction_unscoped():
    texts = [_hlo("jit_decode_step", "attn_core"), _hlo("jit_decode_step", "attn_core"),
             _hlo("jit_decode_step", "mlp"), _hlo("jit_prefill", "mlp")]
    tables, conflicts = scope_split.program_tables(texts, SCOPES)
    assert conflicts == {("jit_decode_step", "fusion.2")}
    assert tables["jit_decode_step"]["fusion.2"] == "unscoped"
    assert tables["jit_prefill"] == {"x": "unscoped", "copy.3": "unscoped", "fusion.2": "mlp"}
    red = scope_split.reduce_scopes(_planes(), tables, conflicts)
    assert red["conflict_s"] == pytest.approx(0.008)
    assert red["scopes"]["jit_decode_step"] == {"unscoped": pytest.approx(0.040)}


def test_labels_from_op_names():
    assert scope_split.label("jit(decode_step)/layers/while/body/closed_call/attn_core/"
                             "broadcast_in_dim", SCOPES) == "attn_core"
    assert scope_split.label("jit(decode_step)/layers/while/body/dynamic_slice",
                             SCOPES) == "layer_loop"
    assert scope_split.label("jit(decode_step)/layers/while", SCOPES) == "layer_loop"
    assert scope_split.label("jit(decode_step)/sample/argmax", SCOPES) == "sample"
    assert scope_split.label("jit(decode_step)/router/top_k", SCOPES) == "unscoped"
    assert scope_split.label("params['embed']", SCOPES) == "unscoped"
    assert scope_split.label(None, SCOPES) == "unscoped"


NESTED_HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/mlp/mul"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %fusion.2 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[4]{0} copy(%fusion.2)
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%gte.1, %copy.3), metadata={op_name="jit(f)/layers/while/body/tuple"}
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.5 = pred[] constant(false)
}

ENTRY %main.6 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.7 = (s32[], f32[4]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}
  ROOT %gte.8 = f32[4]{0} get-tuple-element(%while.7), index=1
}
"""


def test_only_top_level_instructions_are_labelled():
    table = scope_split.op_scopes(NESTED_HLO, SCOPES)
    assert "multiply.1" not in table and "param_0" not in table
    assert table["fusion.2"] == "mlp"  # a bare fusion takes its inner op_name
    assert table["copy.3"] == "unscoped"
    assert table["tuple.4"] == table["while.7"] == "layer_loop"
    assert table["constant.5"] == "unscoped"


# danube.chat-decode's decode step of each bucket, compiled for a described
# v5e as serve_loop.Cell compiles it (full width and depth, batch 16, cache of
# prompt + 256 slots), metadata cut to op_name: the number of instructions of
# each label, and a digest of the whole table
DECODE_LABELS = {
    512: ({"attn_core": 19, "attn_proj": 17, "embed": 3, "layer_loop": 20, "lm_head": 1,
           "mlp": 3, "norm": 10, "sample": 2, "unscoped": 82},
          "d99211e740cfefdebfd380c3375993fed8462d0b69055ed946c81bd29558d420"),
    1024: ({"attn_core": 19, "attn_proj": 17, "embed": 3, "layer_loop": 20, "lm_head": 1,
            "mlp": 3, "norm": 10, "sample": 2, "unscoped": 82},
           "d99211e740cfefdebfd380c3375993fed8462d0b69055ed946c81bd29558d420"),
    2048: ({"attn_core": 19, "attn_proj": 17, "embed": 3, "layer_loop": 17, "lm_head": 1,
            "mlp": 3, "norm": 10, "sample": 2, "unscoped": 88},
           "d3623eaa18c24aaf82fae106acaedf4931364d151f94b1a8364a4eb0aa2f9af6"),
}
# the decode step's costliest device ops on the chip (ledger, PR 14's breakdown)
TOP_OPS = {"copy.9": "unscoped", "copy.10": "unscoped",
           "constant_dynamic-slice_fusion.10": "layer_loop",
           "constant_dynamic-slice_fusion.12": "layer_loop",
           "copy.23": "layer_loop", "copy.26": "layer_loop",
           "fusion.136": "mlp", "fusion.137": "mlp", "bitcast_add_fusion.3": "mlp",
           "dynamic-update-slice.18": "attn_core"}


def _decode_text(prompt):
    return gzip.open(DATA / f"danube_decode_step.{prompt}.v5e.hlo.gz", "rt").read()


@pytest.mark.parametrize("prompt", sorted(DECODE_LABELS))
def test_recorded_decode_step_labels(prompt):
    table = scope_split.op_scopes(_decode_text(prompt), SCOPES)
    counts, digest = DECODE_LABELS[prompt]
    assert dict(collections.Counter(table.values())) == counts
    assert hashlib.sha256(json.dumps(sorted(table.items())).encode()).hexdigest() == digest
    assert {n: table[n] for n in TOP_OPS} == TOP_OPS


def test_recorded_buckets_agree():
    tables, conflicts = scope_split.program_tables(
        [_decode_text(p) for p in sorted(DECODE_LABELS)], SCOPES)
    assert conflicts == set() and list(tables) == ["jit_decode_step"]


def test_recorded_tpu_trace():
    """The planes loaded here give trace.py's reduction exactly as its own
    loader does, and every op of the one program, in no table, is unscoped."""
    planes = trace.load_planes(str(RECORDED), ("step",))
    assert trace.reduce_planes(planes, ("step",)) == trace.reduce_file(str(RECORDED), ("step",))
    red = scope_split.reduce_scopes(planes, {})
    base = trace.reduce_file(str(RECORDED), ("step",))
    assert list(red["scopes"]) == ["jit_work"]
    assert red["scopes"]["jit_work"]["unscoped"] == pytest.approx(base["busy_s"], rel=1e-6)


def _rec(split=None, n=2, positions=(1024, 1025)):
    red = {"programs": {"jit_decode_step": {"n": n, "device_s": 0.1}}}
    if split is not None:
        red["scopes"] = {"jit_decode_step": split}
    return {"model": DANUBE, "model_files": cell.model_files("dense"),
            "peaks": peaks.PEAKS["TPU v5 lite"], "trace": red,
            "work": {"batch": 16, "decode_positions": list(positions)}}


SPLIT = {"attn_core": 0.06, "attn_proj": 0.004, "mlp": 0.01, "lm_head": 0.002,
         "layer_loop": 0.01, "unscoped": 0.008, "norm": 0.001, "embed": 0.0005}
# per step of 2: the attention core; projections + MLP + head; the loop; the rest
BY_HAND = {"decode_attn_core_ms": 30.0, "decode_matmul_ms": 8.0,
           "decode_layer_loop_ms": 5.0, "decode_unscoped_ms": 4.0}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_per_step_ms(name):
    read = scope_split.METRICS[name]
    assert read(_rec(SPLIT)) == pytest.approx(BY_HAND[name])
    assert read(_rec()) is None  # a program without named parts: nothing to read
    assert read(_rec({"norm": 0.001})) is None  # none of the metric's parts
    assert read(_rec(SPLIT, n=0)) is None


def test_attn_roofline():
    read = scope_split.METRICS["decode_attn_roofline"]
    # bytes bind: 61440 B of bf16 K and V per token over 24 layers, 16 requests,
    # 1025 + 1 and 1026 + 1 tokens read and written, at 819 GB/s; the FLOPs
    # (4 * 32 * 80 * 24 per pair) take 2% of that at 197 TFLOP/s
    need = 2 * 2 * 8 * 80 * 24 * 16 * (1026 + 1027) / 819e9
    assert read(_rec(SPLIT)) == pytest.approx(100 * need / 0.06)
    assert read(_rec()) is None
    assert read(_rec(SPLIT, positions=(1024,))) is None  # steps not the window's
    assert read(_rec(dict(SPLIT, attn_core=0.0))) is None


WORK_HLO = """HloModule jit_work, is_scheduled=true

ENTRY %main.1 (x: bf16[512,512]) -> bf16[512,512] {
  %x = bf16[512,512]{1,0} parameter(0)
  %copy-start = (bf16[512,512]{1,0}, bf16[512,512]{1,0}, u32[]) copy-start(%x)
  %copy-done = bf16[512,512]{1,0} copy-done(%copy-start)
  ROOT %fusion = bf16[512,512]{1,0} fusion(%copy-done), kind=kOutput, calls=%f, metadata={op_name="jit(work)/mlp/dot_general"}
}
"""


def test_run_trace_path_hands_readers_scopes(monkeypatch):
    """``run.py --trace 1``'s reduction of the recorded trace, with the
    program's text: trace.py's numbers, and each operation's self time under
    its label, which a per-step reader then reads (the recorded program
    stands in for the decode step)."""
    import run

    red = run.reduce_trace(str(RECORDED), ("step",), [WORK_HLO], SCOPES)
    base = trace.reduce_file(str(RECORDED), ("step",))
    assert {k: v for k, v in red.items() if k not in ("scopes", "conflict_s")} == base
    split = red["scopes"]["jit_work"]
    assert set(split) == {"mlp", "unscoped"} and red["conflict_s"] == 0
    assert sum(split.values()) == pytest.approx(base["busy_s"], rel=1e-6)
    ops = dict(base["device_ops"])
    assert split["mlp"] == pytest.approx(sum(t for k, t in ops.items() if "%fusion " in k))

    monkeypatch.setattr(scope_split, "DECODE", "jit_work")
    n = base["programs"]["jit_work"]["n"]
    rec = {"trace": red}
    assert scope_split.METRICS["decode_matmul_ms"](rec) == pytest.approx(1e3 * split["mlp"] / n)
    assert scope_split.METRICS["decode_unscoped_ms"](rec) == pytest.approx(
        1e3 * split["unscoped"] / n)
