# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
# Simulator sections run as declarative Sweeps on the parallel sweep engine
# (docs/SWEEPS.md) and merge their grids into BENCH_sim.json at the repo
# root.  ``--quick`` shrinks every grid for CI smoke runs; ``--only`` selects
# sections by name; ``--list`` prints the registered policies, workloads,
# and sections without running anything.
from __future__ import annotations

import os
import sys
import time

# support both `python -m benchmarks.run` and `python benchmarks/run.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def list_registries(section_names) -> None:
    """--list: the registered policies (component matrix), workloads
    (metadata), and benchmark sections."""
    from repro.capture import CAPTURED, capture_meta
    from repro.core.sim import (
        available_controllers,
        available_placements,
        available_policies,
        available_topologies,
        available_workloads,
        build_topology,
        compressibility_of,
        get_controller,
        get_placement,
        get_policy,
        get_workload,
        topology_description,
    )
    from repro.core.sim.config import SimConfig

    print("policies (name: granularity/partitioning/up-uplink/compression"
          "/throttle[/flags]):")
    for name in available_policies():
        p = get_policy(name)
        flags = []
        if p.free_transfers:
            flags.append("free")
        if not p.page_carries_requests:
            flags.append("race")
        if p.line_share is not None:
            flags.append(f"line_share={p.line_share}")
        if p.fabric is not None:
            flags.append(f"fab-{p.fabric}")
        comp = "/".join([p.granularity, p.partitioning,
                         f"up-{p.uplink_partitioning}", p.compression,
                         "throttle" if p.throttle else "nothrottle"]
                        + flags)
        print(f"  {name:18s} {comp:44s} {p.description}")
    print("workloads (name: compressibility, description):")
    for name in available_workloads():
        if name in CAPTURED:
            continue  # listed below with full source-kernel metadata
        w = get_workload(name)
        print(f"  {name:18s} x{compressibility_of(name):<4.1f} {w.description}")
    print("captured kernel workloads (source-kernel metadata, DESIGN.md §2.8):")
    for name in CAPTURED:
        m = capture_meta(name)
        grid = "x".join(str(g) for g in m["grid"])
        print(f"  {name:18s} {m['kernel']}/{m['variant']:8s} grid={grid:10s} "
              f"{m['n_accesses']} accesses, "
              f"{m['footprint'] >> 10} KiB footprint, "
              f"x{m['compressibility']:.2f} measured, "
              f"operands={','.join(m['operands'])}")
    print("controllers (name: thresholds, description — DESIGN.md §2.12):")
    _cfg = SimConfig()
    for name in available_controllers():
        c = get_controller(name)(_cfg)
        th = ",".join(f"{k}={v}" for k, v in sorted(c.thresholds().items()))
        print(f"  {name:18s} {th:44s} {c.description}")
    print("placements (name: allocator, description — DESIGN.md §2.13):")
    for name in available_placements():
        p = get_placement(name)
        print(f"  {name:18s} {p.allocator:44s} {p.description}")
    print("topologies (name: ports/hops at 2 CCs x 2 MCs, description — "
          "DESIGN.md §2.11):")
    for name in available_topologies():
        spec = build_topology(name, n_ccs=2, n_mcs=2)
        hops = len(spec.down_paths[(0, 0)])
        print(f"  {name:18s} {len(spec.ports)} ports, {hops} hop"
              f"{'s' if hops != 1 else ''}  {topology_description(name)}")
    print("sections:")
    print("  " + ",".join(section_names))


def main() -> None:
    import argparse

    from benchmarks import (
        engine_bench,
        fig2_schemes,
        fig4_multijob,
        fig4_robustness,
        fig5_scalability,
        fig6_ablation,
        fig7_uplink,
        fig8_kernels,
        fig9_serving,
        fig10_topology,
        fig11_controllers,
        fig12_memside,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny grids (CI smoke): 5-10x fewer simulated accesses")
    ap.add_argument("--only", default="",
                    help="comma-separated section names to run")
    ap.add_argument("--workers", type=int, default=None,
                    help="sweep worker processes (default: all cores)")
    ap.add_argument("--engine", choices=("python", "batch"), default="python",
                    help="sweep cell engine: per-cell oracle event loop or "
                         "the lockstep batch core (bit-identical; uncovered "
                         "cells fall back to the oracle automatically)")
    ap.add_argument("--list", action="store_true",
                    help="print registered policies, workloads, and sections")
    args = ap.parse_args()

    n_fig2 = 2_000 if args.quick else 20_000
    n_fig4 = 1_500 if args.quick else 15_000
    # fig6 needs >= 1000 accesses/thread so the 'ph' workload actually
    # alternates phases (epoch = 500 accesses)
    n_fig6 = 4_000 if args.quick else 20_000
    # fig7 needs >= 1000 accesses/thread so the 'wh' workload actually
    # churns its local page cache (writebacks are the traffic under test)
    n_fig7 = 4_000 if args.quick else 20_000
    # fig8 needs >= 2000 accesses/thread so a captured-kernel replay window
    # spans several tile bursts (the inter-tile jumps are the structure
    # under test; one flash tile alone is ~512 line accesses)
    n_fig8 = 8_000 if args.quick else 40_000
    # fig9 quick shrinks the request count AND the per-phase slice sizes
    # (request latency scales with phase length, so the quick grid stays
    # deep in the same load regimes at ~1/4 the simulated accesses)
    fig9_kw = (dict(n_requests=24, prefill_accesses=512, decode_steps=3,
                    decode_accesses=128) if args.quick
               else dict(n_requests=96, prefill_accesses=1024,
                         decode_steps=4, decode_accesses=256))
    # fig10 needs >= 1000 accesses/thread so pointer-chase demand misses
    # and the streaming bulk actually overlap on the shared trunks
    n_fig10 = 4_000 if args.quick else 20_000
    # fig11 reuses the fig6/fig7 grid sizing for its synthetic halves and
    # 2x that for the captured-kernel half (fig8's sizing rationale)
    n_fig11 = 4_000 if args.quick else 20_000
    # fig12 needs >= 1000 accesses/thread so the finite pools actually fill
    # (capacity pressure and eviction churn are the dynamics under test)
    n_fig12 = 4_000 if args.quick else 20_000
    w = args.workers
    eng = args.engine
    sections = [
        ("fig2", lambda: fig2_schemes.run(n_accesses=n_fig2, workers=w, engine=eng)),
        ("fig4_top", lambda: fig4_robustness.run(n_accesses=n_fig4, workers=w, engine=eng)),
        ("fig4_bottom", lambda: fig4_multijob.run(n_accesses=n_fig4, workers=w, engine=eng)),
        ("sweep_jitter", lambda: fig4_robustness.run_jitter(n_accesses=n_fig4, workers=w, engine=eng)),
        ("sweep_nmcs", lambda: fig4_robustness.run_nmcs(n_accesses=n_fig4, workers=w, engine=eng)),
        ("fig5", lambda: fig5_scalability.run(n_accesses=n_fig4, workers=w, engine=eng)),
        ("fig6", lambda: fig6_ablation.run(n_accesses=n_fig6, workers=w, engine=eng)),
        ("fig7", lambda: fig7_uplink.run(n_accesses=n_fig7, workers=w, engine=eng)),
        ("fig7_wshare", lambda: fig7_uplink.run_wshare(n_accesses=n_fig7, workers=w, engine=eng)),
        ("fig8", lambda: fig8_kernels.run(n_accesses=n_fig8, workers=w, engine=eng)),
        ("fig9", lambda: fig9_serving.run(workers=w, engine=eng, **fig9_kw)),
        ("fig10", lambda: fig10_topology.run(n_accesses=n_fig10, workers=w, engine=eng)),
        ("fig11", lambda: fig11_controllers.run(n_accesses=n_fig11, workers=w, engine=eng)),
        ("fig12", lambda: fig12_memside.run(n_accesses=n_fig12, workers=w, engine=eng)),
        ("engine_bench", lambda: engine_bench.run(n_accesses=n_fig2)),
    ]
    # opt-in sections: run only when explicitly named in --only (the
    # seed-axis variance grid is ~6x a fig6 run — nightly.yml selects it;
    # a bare `run.py` keeps the canonical ledger sections)
    optin = [
        ("fig6_var", lambda: fig6_ablation.run_variance(n_accesses=n_fig6, workers=w, engine=eng)),
    ]
    section_names = [s[0] for s in sections] + [s[0] for s in optin]
    if args.list:
        list_registries(section_names)
        return
    if args.only:
        keep = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = keep - set(section_names)
        if unknown:
            sys.exit(f"unknown --only section(s) {sorted(unknown)}; "
                     f"choose from {sorted(section_names)} "
                     f"(see `PYTHONPATH=src python -m benchmarks.run --list`)")
        sections = [s for s in sections + optin if s[0] in keep]

    print("name,us_per_call,derived")
    failures = 0
    t_all = time.perf_counter()
    for name, fn in sections:
        t0 = time.perf_counter()
        try:
            for tag, us, derived in fn():
                print(f"{tag},{us:.1f},{derived}")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
        # per-section wall-clock on stderr: the ledger carries the same
        # numbers as non-gated wall_* keys (docs/SWEEPS.md)
        print(f"[wall] {name}: {time.perf_counter() - t0:.2f}s",
              file=sys.stderr)
    print(f"[wall] total ({args.engine} engine): "
          f"{time.perf_counter() - t_all:.2f}s", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
