"""trace.py's reduction, on planes built by hand and on a small trace
recorded on a TPU v5e (``tests/data/v5e_small.xplane.pb``)."""
from pathlib import Path

import pytest

import trace

MS = 1_000_000  # ns


def _planes():
    dev = {
        "XLA Modules": [("jit_prefill(123)", 10 * MS, 40 * MS),
                        ("jit_decode_step(456)", 50 * MS, 60 * MS),
                        ("jit_decode_step(456)", 70 * MS, 80 * MS)],
        "XLA Ops": [("fusion.1", 10 * MS, 30 * MS), ("dot.2", 25 * MS, 40 * MS),
                    ("fusion.1", 50 * MS, 60 * MS), ("fusion.1", 70 * MS, 80 * MS),
                    ("outside", 200 * MS, 210 * MS)],
    }
    host = {"python": [("window", 0, 100 * MS), ("prefill", 0, 12 * MS),
                       ("token_to_host", 40 * MS, 49 * MS), ("decode", 49 * MS, 51 * MS),
                       ("token_to_host", 60 * MS, 75 * MS), ("unrelated", 0, 100 * MS)]}
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


def test_busy_idle_programs_and_gaps():
    red = trace.reduce_planes(_planes(), ("prefill", "decode", "token_to_host"))
    assert red["window_s"] == pytest.approx(0.1)
    # busy union: [10, 40] + [50, 60] + [70, 80] ms; the op at 200 ms is outside
    assert red["busy_s"] == pytest.approx(0.05)
    assert red["programs"]["jit_prefill"] == {"n": 1, "device_s": pytest.approx(0.03)}
    assert red["programs"]["jit_decode_step"]["n"] == 2
    assert red["programs"]["jit_decode_step"]["device_s"] == pytest.approx(0.02)
    ops = dict(red["device_ops"])
    assert ops["jit_decode_step/fusion.1"] == pytest.approx(0.02)
    # self time: the part of fusion.1 that dot.2 overlaps counts once
    assert ops["jit_prefill/fusion.1"] + ops["jit_prefill/dot.2"] == pytest.approx(0.03)
    gaps = dict(red["idle_gaps"])
    # [0, 10] under prefill; [40, 50] under token_to_host; [60, 70] likewise;
    # [80, 100] under no span of the benchmark's
    assert gaps["prefill"] == pytest.approx(0.01)
    assert gaps["token_to_host"] == pytest.approx(0.02)
    assert gaps["no host span"] == pytest.approx(0.02)
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def test_no_window_or_no_device_work_is_an_error():
    planes = _planes()
    with pytest.raises(ValueError, match="window"):
        trace.reduce_planes([planes[0], ("/host:CPU", {"python": []})])
    with pytest.raises(ValueError, match="no operation"):
        trace.reduce_planes([("/device:TPU:0", {"XLA Ops": []}), planes[1]])


RECORDED = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_op_label_and_nesting():
    text = ("%while.12 = (s32[]{:T(128)}, bf16[16,1,2560]{2,0,1:T(8,128)(2,1)S(1)}) "
            "while((s32[]{:T(128)}, bf16[16,1,2560]) %tuple.65), condition=%c, body=%b")
    assert trace.op_label(text) == "%while.12 (while)"
    assert trace.op_label("%fusion.58 = s32[2,4]{1,0} fusion(s32[16] %x)") == "%fusion.58 (fusion)"
    # a loop enclosing two body ops keeps only the time they leave uncovered
    got = dict(trace._self_times([("loop", 0, 10), ("a", 1, 4), ("b", 5, 9)]))
    assert got == {"loop": 3, "a": 3, "b": 4}


def test_recorded_tpu_trace():
    """Five calls of a jitted 512x512 matmul under host spans, recorded on
    one TPU v5e; the device clock runs about 1 ms behind the host's, so the
    first call can fall before the host's window."""
    red = trace.reduce_file(str(RECORDED), ("step",))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert 4 <= red["programs"]["jit_work"]["n"] <= 5
    # the one program's span covers its operations and the few ns between them
    assert red["programs"]["jit_work"]["device_s"] == pytest.approx(red["busy_s"], rel=0.05)
    assert red["device_ops"][0][0].startswith("jit_work/%fusion")
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(red["window_s"] - red["busy_s"])
