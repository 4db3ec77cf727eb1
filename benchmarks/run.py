"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  paper_figs      — HURRY Figs 6/7/8 + accuracy (simulator-derived)
  kernels_bench   — Pallas kernel microbenches (interpret mode on CPU)
  program_bench   — compiled-program serving (compile once, us per batch)
  api_bench       — repro.api lifecycle (compile / save / load / run)
  attention_bench — sequence prefill: crossbar attention vs flash
  lm_step         — LM train/serve step wall-times on reduced configs

``--section kernels`` (etc.) runs one section only; the persisted
sections (``bench_io.SECTIONS``) also write their rows to
``BENCH_<section>.json`` so future PRs can diff timings.  When a
persisted section is requested *explicitly* and a previous
``BENCH_<section>.json`` exists, a one-line timing delta against it is
printed before the rows are overwritten — regressions surface in CI
logs without manual JSON diffing.
"""

from __future__ import annotations

import argparse

SECTIONS = ("all", "paper", "kernels", "program", "api", "attention", "lm")

# section flag -> (benchmark module name, persisted bench_io section or None)
_RUNNERS = {
    "kernels": ("kernels_bench", "kernels"),
    "program": ("program_bench", "program"),
    "api": ("api_bench", "api"),
    "attention": ("attention_bench", "attention"),
    "lm": ("lm_step", None),
}


def _delta_line(section: str, prev: dict, rows) -> str:
    """One-line steady-state timing delta vs the previous BENCH json."""
    old = {name: entry["us_per_call"]
           for name, entry in prev.get("entries", {}).items()}
    new = {name: us for name, us, _ in rows}
    shared = [n for n in new if n in old and old[n] > 0]
    added, gone = len(new) - len(shared), len(old.keys() - new.keys())
    if not shared:
        return (f"bench[{section}] delta vs previous: no shared rows "
                f"({added} new, {gone} gone)")
    pcts = sorted((new[n] - old[n]) / old[n] * 100 for n in shared)
    med = pcts[len(pcts) // 2]
    worst = max(pcts, key=abs)
    extra = f", {added} new" if added else ""
    extra += f", {gone} gone" if gone else ""
    return (f"bench[{section}] delta vs previous BENCH_{section}.json: "
            f"median {med:+.1f}% / worst {worst:+.1f}% us_per_call "
            f"across {len(shared)} shared rows{extra}")


def _run_section(flag: str, requested: bool) -> list:
    """Run one optional section; persists + prints the delta line.

    Sections are skipped on ImportError only under the "all" default;
    an explicitly requested section must propagate failures.
    """
    mod_name, persist = _RUNNERS[flag]
    try:
        import importlib
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        rows = mod.run()
    except ImportError:
        if requested:
            raise
        return []
    if persist is not None:
        from benchmarks import bench_io
        prev = None
        if requested:
            try:
                prev = bench_io.read_bench_json(persist)
            except (FileNotFoundError, ValueError):
                prev = None
        bench_io.write_bench_json(persist, rows)
        if prev is not None:
            print(_delta_line(persist, prev, rows))
    return rows


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
    for flag in _RUNNERS:
        if args.section in ("all", flag):
            rows.extend(_run_section(flag, requested=args.section == flag))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.6g}")


if __name__ == "__main__":
    main()
