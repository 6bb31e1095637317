"""Kernel-trace capture tests (DESIGN.md §2.8): recorder determinism,
disjoint operand regions, Pallas block-reuse semantics in the emitted
stream, `.npz` roundtrip through the standard replay path, captured
workloads inside '+' mixes, measured compressibility ordering, the fig8
grid declaration, and drift locks between the ops.py geometry shims and
the kernels' own tiling constants."""
import numpy as np
import pytest

from repro.capture import (
    CAPTURED,
    assign_regions,
    capture,
    capture_meta,
    clear_capture_cache,
    measured_compressibility_of,
)
from repro.capture.workloads import CapturedKernel
from repro.core.sim import (
    SimConfig,
    available_workloads,
    compressibility_of,
    fig8_kernels_spec,
    generate,
    get_workload,
    register_trace_file,
    run_one,
)

KERNELS = ("fa_prefill", "fa_decode", "mamba_fwd", "bq_quant")


# ---------------- registration & out-of-the-box use ----------------


def test_captured_workloads_registered_at_import():
    assert set(KERNELS) <= set(available_workloads())
    for name in KERNELS:
        assert CAPTURED[name].description == get_workload(name).description


def test_run_one_works_out_of_the_box():
    m = run_one("fa_prefill", "daemon", n_accesses=2_000)
    assert m.accesses == 2_000 - 2_000 % 4  # n_cores=4 threads
    assert m.cycles > 0


def test_captured_workload_valid_in_mixes():
    cfg = SimConfig(n_ccs=2)
    m = run_one("fa_prefill+st", "daemon", cfg, n_accesses=2_000)
    assert len(m.per_cc) == 2
    assert {cc["workload"] for cc in m.per_cc} == {"fa_prefill", "st"}


def test_capture_meta_carries_source_kernel():
    meta = capture_meta("bq_quant")
    assert meta["kernel"] == "block_quant"
    assert meta["grid"] == (2, 1)  # (512, 2048): two 256-row whole-row tiles
    assert meta["n_accesses"] > 0
    assert set(meta["operands"]) == {"x", "q", "scales"}


# ---------------- determinism ----------------


def test_recorder_determinism_bit_identical():
    a = capture("fa_prefill").trace
    clear_capture_cache()
    b = capture("fa_prefill").trace
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_generate_deterministic_and_seed_rotates_phase():
    g1, a1, w1 = generate("mamba_fwd", seed=3, n=5_000)
    g2, a2, w2 = generate("mamba_fwd", seed=3, n=5_000)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(g1, g2)
    np.testing.assert_array_equal(w1, w2)
    _, a3, _ = generate("mamba_fwd", seed=4, n=5_000)
    assert not np.array_equal(a1, a3)  # rotated replay phase


# ---------------- geometry / regions ----------------


def test_operand_regions_disjoint_and_page_aligned():
    for name in KERNELS:
        geom = CAPTURED[name].build_geometry()
        bases = assign_regions(geom)
        spans = sorted(
            (bases[op.name], bases[op.name] + op.nbytes, op.name)
            for op in geom.operands)
        for base, _, opname in spans:
            assert base % 4096 == 0, (name, opname)
        for (_, end_a, op_a), (start_b, _, op_b) in zip(spans, spans[1:]):
            assert end_a <= start_b, (name, op_a, op_b)


def test_block_runs_crossing_line_boundaries_keep_every_line():
    # a 40-byte run starting at byte 40 spans lines 0 AND 64; the line
    # emission must follow the run's actual byte span, not just its length
    from repro.capture.geometry import Operand, block_line_addrs

    op = Operand("z", shape=(4, 20), block=(1, 10), elem_bytes=4,
                 index_map=lambda i, j: (i, j))
    lines = block_line_addrs(op, base=0, block_idx=(0, 1))  # bytes 40..79
    np.testing.assert_array_equal(lines, [0, 64])


def test_trace_addresses_stay_inside_assigned_regions():
    for name in KERNELS:
        res = capture(name)
        geom = res.geom
        spans = {op.name: (res.regions[op.name],
                           res.regions[op.name] + op.nbytes)
                 for op in geom.operands}
        addrs = res.addrs
        covered = np.zeros(len(addrs), bool)
        for lo, hi in spans.values():
            covered |= (addrs >= (lo // 64) * 64) & (addrs < hi)
        assert covered.all(), name


def test_blocks_tile_arrays_exactly():
    # every operand's index map must stay within the block grid over the
    # whole launch grid (a drifted shim would walk out of bounds)
    for name in KERNELS:
        geom = CAPTURED[name].build_geometry()
        for op in geom.operands:
            n_blocks = tuple(s // b for s, b in zip(op.shape, op.block))
            for step in geom.steps():
                idx = op.index_map(*step)
                assert all(0 <= i < n for i, n in zip(idx, n_blocks)), (
                    name, op.name, step, idx)


# ---------------- stream structure (the captured signature) ----------------


def test_tile_bursts_are_line_dense():
    # inside a tile burst consecutive accesses step by exactly one line —
    # the high-spatial-reuse half of the captured signature
    _, addrs, _ = capture("fa_prefill").trace
    deltas = np.diff(addrs)
    assert (deltas == 64).mean() > 0.9


def test_inter_tile_jumps_present():
    # ... and the abrupt-jump half: region switches / tile jumps far apart
    _, addrs, _ = capture("fa_prefill").trace
    deltas = np.abs(np.diff(addrs))
    assert (deltas > 4096).sum() >= 100


def test_parked_q_tile_not_refetched():
    # flash q block is parked across the whole KV loop: q-region traffic
    # must be one fetch per (bh, qi), not per grid step
    res = capture("fa_prefill")
    geom = res.geom
    q = next(op for op in geom.operands if op.name == "q")
    n_q_fetches = geom.grid[0] * geom.grid[1]  # (bh, qi) combinations
    assert res.moved_bytes["q"] == n_q_fetches * q.block_nbytes


def test_output_writebacks_emitted_as_writes():
    _, addrs, writes = capture("bq_quant").trace
    assert writes.any()
    res = capture("bq_quant")
    lo = res.regions["q"]
    hi = lo + next(op for op in res.geom.operands if op.name == "q").nbytes
    in_q = (addrs >= lo) & (addrs < hi)
    assert writes[in_q].all()  # q region is write-only
    assert not writes[~in_q & (addrs < res.regions["q"])].any()  # x read-only


# ---------------- npz roundtrip ----------------


def test_npz_roundtrip_through_register_trace_file(tmp_path):
    from repro.capture import save_kernel_trace

    path = str(tmp_path / "fa_prefill_cap.npz")
    save_kernel_trace("fa_prefill", path)
    spec = register_trace_file(path)
    direct = generate("fa_prefill", seed=7, n=4_000)
    replay = spec(7, 0, 4_000)
    for a, b in zip(direct, replay):
        np.testing.assert_array_equal(a, b)
    assert spec.compressibility == pytest.approx(
        compressibility_of("fa_prefill"))


# ---------------- measured compressibility ----------------


def test_compressibility_measured_and_ordered():
    comps = {name: compressibility_of(name) for name in KERNELS}
    for name, c in comps.items():
        assert c >= 1.0, (name, c)
    # the headline distinction: block_quant's int8 payload compresses,
    # dense f32 attention states don't
    assert comps["bq_quant"] > comps["fa_prefill"] + 0.2
    assert comps["bq_quant"] > comps["fa_decode"] + 0.2
    # measurement is cached on the spec's lazy callable
    assert compressibility_of("bq_quant") == comps["bq_quant"]
    assert measured_compressibility_of("bq_quant") == pytest.approx(
        comps["bq_quant"])


# ---------------- shim drift locks ----------------


def test_shim_constants_match_kernels():
    import importlib

    bq = importlib.import_module("repro.kernels.block_quant.block_quant")
    fa = importlib.import_module(
        "repro.kernels.flash_attention.flash_attention")
    ms = importlib.import_module("repro.kernels.mamba_scan.mamba_scan")

    fa_geom = CAPTURED["fa_prefill"].build_geometry()
    q = next(op for op in fa_geom.operands if op.name == "q")
    assert q.block[1] == fa.DEFAULT_BQ or q.block[1] == q.shape[1]
    ms_geom = CAPTURED["mamba_fwd"].build_geometry()
    dt = next(op for op in ms_geom.operands if op.name == "dt")
    assert dt.block[1] == min(ms.CHUNK, dt.shape[1])
    assert dt.block[2] == min(ms.TILE_D, dt.shape[2])
    bq_geom = CAPTURED["bq_quant"].build_geometry()
    sc = next(op for op in bq_geom.operands if op.name == "scales")
    x = next(op for op in bq_geom.operands if op.name == "x")
    assert x.shape[1] // sc.shape[1] == bq.BLOCK
    tr, tc = bq._tiles(*x.shape)
    assert x.block == (tr, tc) and sc.block == (tr, tc // bq.BLOCK)
    assert bq_geom.grid == (x.shape[0] // tr, x.shape[1] // tc)


def test_fa_gqa_kv_sharing_matches_kernel_math():
    # the kv index map must reproduce flash_attention_pallas's GQA head
    # mapping: flat head j reads kv head j // g
    geom = CAPTURED["fa_prefill"].build_geometry()
    cfg = CAPTURED["fa_prefill"].config
    h, kvh = cfg["h"], cfg["kvh"]
    g = h // kvh
    k = next(op for op in geom.operands if op.name == "k")
    for bh in range(geom.grid[0]):
        idx = k.index_map(bh, 0, 0)
        assert idx[0] == (bh // h) * kvh + (bh % h) // g


# ---------------- fig8 grid ----------------


def test_fig8_spec_axes():
    sw = fig8_kernels_spec(n_accesses=2_000)
    assert sw.axes["workload"] == KERNELS
    assert "page" in sw.axes["scheme"] and "daemon" in sw.axes["scheme"]
    assert sw.axes["link_bw_frac"] == (0.125, 0.5, 1.0)


def test_unknown_captured_kernel_fails_fast():
    with pytest.raises(KeyError, match="catalog"):
        capture("not_a_kernel")


def test_catalog_entry_is_lazy():
    entry = CAPTURED["fa_prefill"]
    assert isinstance(entry, CapturedKernel)
    assert entry.module.startswith("repro.kernels.")
