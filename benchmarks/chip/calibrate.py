#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,...,12 --control-seeds 21,22,23 [--out <file.json>]

For every seed of ``--seeds`` it runs the cell's set-up and a window of
``--seconds`` (long enough to finish the mix's longest requests) and reads
the numbers that ``correct`` compares: the lower readings.  For every seed
of ``--control-seeds`` it does the same with the control switched on (the
program's own int8 working copy, ``DAEMON_AGGRESSIVE``, in place of bf16):
the upper readings.  Each row also says whether the run came out
``correct`` against the cell's limits file.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
import cell as cell_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    c = cell_lib.load(a.workload)
    jax = run.setup_jax()
    devices, peaks_row = run.require_chips(jax, c["chips"])
    from repro.core import movement as mv

    rows = []
    for kind, seeds, movement in (("program", a.seeds, None),
                                  ("control", a.control_seeds, mv.DAEMON_AGGRESSIVE)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            out = run.run_cell(c, seed, a.seconds, False, devices, peaks_row, movement=movement)
            row = {"kind": kind, "seed": seed, "correct": out["correct"],
                   "readings": out["readings"], "attempted": out["attempted"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for kind, pick in (("program", max), ("control", min)):
        for name in sorted({k for r in rows for k in r["readings"]}):
            vals = [r["readings"][name] for r in rows if r["kind"] == kind
                    and r["readings"].get(name) is not None]
            if vals:
                summary[f"{kind}.{name}.{pick.__name__}"] = pick(vals)
    print(json.dumps({"workload": a.workload, "summary": summary}), flush=True)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
