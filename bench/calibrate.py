#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--controls bf16_activations,int4_operands] [--seconds 3]

For each seed it runs the cell as ``run.py`` does, for a short window,
and then again with each control — the plain reference at a lower
precision (``reference.CONTROLS``) put in the program's place — and
prints one JSON line per run with the numbers the check compared.  The
limits in a configuration file lie above the program's readings and
below the controls' (``PERF.md`` gives both).  The benchmark's own runs
never run a control.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.find_cell(REPO, args.workload)
    controls = [None] + [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in controls:
            t = time.perf_counter()
            keep = (Path(args.keep_trace) / f"{args.workload}-{seed}"
                    if args.keep_trace and control is None else None)
            r = harness.run(cell, seed, args.seconds, bool(args.trace),
                            t_process=t, control=control, keep_trace=keep)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": control, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
