"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  paper — HURRY Figs 1/6/7/8 + accuracy (simulator-derived)
  lm    — LM train/serve step wall-times on reduced configs

``--section paper`` (or ``lm``) runs one section only.  These are host
timings of analytical models and of the seed LM stack, not the chip
benchmark (``bench/run.py``).
"""

from __future__ import annotations

import argparse

SECTIONS = ("all", "paper", "lm")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--section", choices=SECTIONS, default="all")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    rows = []
    if args.section in ("all", "paper"):
        from benchmarks import fig1_tradeoff, paper_figs
        for fn in fig1_tradeoff.ALL:
            rows.extend(fn())
        for fn in paper_figs.ALL:
            rows.extend(fn())
    if args.section in ("all", "lm"):
        from benchmarks import lm_step
        rows.extend(lm_step.run())

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.6g}")


if __name__ == "__main__":
    main()
