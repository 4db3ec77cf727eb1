#!/usr/bin/env python3
"""Benchmark of the crossbar program stack on the TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of the repository.  The cell is an entry of
``BENCHMARK.json``'s ``workloads``; ``harness.py`` says how its files are
found and what a run does.  The last line of standard output is the
result as one JSON object; the last lines of standard error give each
number the correctness check compared, beside its limit.  Off a TPU, or
with fewer chips than the cell asks for, the run exits with code 3 and
prints no result.
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


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    try:
        cell = harness.find_cell(REPO, args.workload)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_process=T_PROCESS)
    except harness.NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    checks = result.pop("checks")
    result["checks"] = checks            # the compared numbers come last
    print(json.dumps(result), flush=True)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
