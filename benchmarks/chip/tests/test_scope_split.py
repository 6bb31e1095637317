"""scope_split.py's reduction, on planes built by hand and on the small
trace recorded on a TPU v5e, and its per-step numbers on a record built by
hand."""
from pathlib import Path

import pytest

import cell
import peaks
import scope_split
import trace

MS = 1_000_000  # ns
RECORDED = Path(__file__).parent / "data" / "v5e_small.xplane.pb"
DANUBE = cell.model_sizes(cell.load_json(cell.HERE / "configs" / "danube.json"))


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
    tables, conflicts = scope_split.program_tables(texts)
    assert conflicts == {("jit_decode_step", "fusion.2")}
    assert tables["jit_decode_step"]["fusion.2"] == "unscoped"
    assert tables["jit_prefill"] == {"x": "unscoped", "copy.3": "unscoped", "fusion.2": "mlp"}
    red = scope_split.reduce_scopes(_planes(), tables, conflicts)
    assert red["conflict_s"] == pytest.approx(0.008)
    assert red["scopes"]["jit_decode_step"] == {"unscoped": pytest.approx(0.040)}


def test_recorded_tpu_trace():
    """The planes loaded here give trace.py's reduction exactly as its own
    loader does, and every op of the one program, in no table, is unscoped."""
    planes = scope_split.load_planes(str(RECORDED), ("step",))
    assert trace.reduce_planes(planes, ("step",)) == trace.reduce_file(str(RECORDED), ("step",))
    red = scope_split.reduce_scopes(planes, {})
    base = trace.reduce_file(str(RECORDED), ("step",))
    assert list(red["scopes"]) == ["jit_work"]
    assert red["scopes"]["jit_work"]["unscoped"] == pytest.approx(base["busy_s"], rel=1e-6)


def _rec(split=None, n=2, positions=(1024, 1025)):
    red = {"programs": {"jit_decode_step": {"n": n, "device_s": 0.1}}}
    if split is not None:
        red["scopes"] = {"jit_decode_step": split}
    return {"model": DANUBE, "peaks": peaks.PEAKS["TPU v5 lite"], "trace": red,
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
