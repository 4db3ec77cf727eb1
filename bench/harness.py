"""The benchmark's harness: cells found by name, one measured run.

``BENCHMARK.json`` at the root of the repository names the cells.  Every
part of a cell is a file found by its name:

* ``configs/<config>.json`` — the model's sizes, its ``HurryConfig``
  (``hurry``), its ``family``, its correctness ``limits`` (a configuration
  without them is never correct), and what was ``reduced``, ``assumed``
  and departed from;
* ``models/<family>.py`` — the program's graph (built with the public
  ``repro.api`` builder), the benchmark's weights and the plain
  reference forward;
* ``traffic/<mix>.json`` — the request mix, read by ``traffic.py``;
* ``metrics/<metric>.py`` — one reader per per-layer metric:
  ``read(ctx) -> float | None``.

A run sets up (weights made and packed on the device, every request
size compiled), serves the mix for ``seconds`` through
``CompiledModel.run``, then checks a seeded sample of what the window
served against the plain reference.  ``--trace 1`` runs the same window
under the profiler and reports the per-layer metrics instead of the
end-to-end ones.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable

import numpy as np

from bench import check, traffic
from bench.peaks import peaks
from bench.reference import CONTROLS, EXACT, Precision, Ref

BENCH_DIR = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """The chips the cell needs are not there."""


# -- finding a cell by name -----------------------------------------------

def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    sizes: dict                 # the configuration file
    family: object              # models/<family>.py
    mix: traffic.Mix
    end_to_end: list[dict]      # BENCHMARK.json metrics this cell reports
    per_layer: list[dict]
    root: Path                  # the benchmark's directory


def find_cell(repo: Path, workload: str, bench_dir: Path | None = None
              ) -> Cell:
    """The cell ``workload`` of ``repo/BENCHMARK.json``, with its files."""
    bench_dir = bench_dir or BENCH_DIR
    spec = json.loads((repo / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; one of "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    sizes = json.loads((repo / configs[w["config"]]["file"]).read_text())
    family = _module(bench_dir / "models" / f"{sizes['family']}.py",
                     f"bench_family_{sizes['family']}")
    mix = traffic.Mix.load(bench_dir / "traffic" / f"{w['traffic']}.json")

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=workload, chips=int(w["chips"]), sizes=sizes,
                family=family, mix=mix, end_to_end=e2e, per_layer=per_layer,
                root=bench_dir)


def reader(cell: Cell, metric: str) -> Callable:
    return _module(cell.root / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric}").read


# -- the chip -------------------------------------------------------------

def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed
    directory, every compiled program kept (small ones too), so that
    only a cell's first run in a checkout compiles."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def seed_key(seed: int):
    import jax

    lo, hi = traffic.seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def bucket(b: int) -> int:
    """The next power of two: the program's batch for ``b`` images, and
    the control's."""
    return 1 << (b - 1).bit_length()


# -- one run --------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One request of the window."""

    size: int
    offset: int
    t_send: float
    t_dispatched: float
    t_done: float = 0.0
    probs: np.ndarray | None = None


def serve(call: Callable, pool: np.ndarray, stream, seconds: float,
          in_flight: int, annotate: Callable) -> tuple[list[Served], float]:
    """Closed loop: keep ``in_flight`` requests outstanding until the
    window closes, then drain.  Returns every request sent (in order)
    and the window's start on the host clock."""
    pending: collections.deque = collections.deque()
    sent: list[Served] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        while len(pending) < in_flight and time.perf_counter() < t_end:
            size, offset = next(stream)
            x = pool[offset:offset + size]
            with annotate("bench.run"):
                t_send = time.perf_counter()
                y = call(x)
                r = Served(size, offset, t_send, time.perf_counter())
            pending.append((r, y))
            sent.append(r)
        if not pending:
            return sent, t0
        r, y = pending.popleft()
        with annotate("bench.fetch"):
            r.probs = np.asarray(y)
            r.t_done = time.perf_counter()


def _null_annotation(name):
    return contextlib.nullcontext()


def make_reference(cell: Cell, prec: Precision = EXACT):
    """The plain reference at ``prec``, jitted: (params, images) -> probs."""
    import jax

    ref = Ref(prec)

    @jax.jit
    def forward(params, x):
        with jax.default_matmul_precision("highest"):
            return cell.family.reference(params, x, cell.sizes, ref)
    return forward


def reference_call(forward, params) -> Callable:
    """The reference in the program's place (the control): pads each
    request to ``bucket`` by edge replication and slices back."""
    def call(x):
        b = x.shape[0]
        xp = np.pad(x, ((0, bucket(b) - b),) + ((0, 0),) * (x.ndim - 1),
                    mode="edge")
        return forward(params, xp)[:b]
    return call


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True,
        control: str | None = None, fault: Callable | None = None,
        cache: bool = True, keep_trace: Path | None = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from repro import api
    from repro.api import HurryConfig

    t_import = time.perf_counter()
    devs = devices(cell.chips, require_tpu)
    peak_rates = peaks(devs[0].device_kind) if trace else None
    cache_dir = use_compile_cache() if cache else None
    sizes, mix = cell.sizes, cell.mix
    shape = (sizes["input_hw"], sizes["input_hw"], sizes["input_ch"])

    key = seed_key(seed)
    init = jax.jit(functools.partial(cell.family.init, sizes=sizes))
    params = jax.block_until_ready(init(jax.random.fold_in(key, 0)))
    model = api.compile(cell.family.graph(sizes),
                        HurryConfig(**sizes["hurry"]), params=params)
    jax.block_until_ready(model.packed)
    t_weights = time.perf_counter()

    make_pool = jax.jit(lambda k: jax.random.normal(
        k, (mix.pool_images,) + shape, jax.numpy.float32))
    pool = np.asarray(make_pool(jax.random.fold_in(key, 1)))
    reference = make_reference(cell)
    if control is not None:
        call = reference_call(make_reference(cell, CONTROLS[control]),
                              params)
    else:
        call = model.run
    if fault is not None:
        call = fault(call)
    for b in mix.distinct_sizes():           # every shape the window uses
        np.asarray(call(pool[:b]))
    t_warm = time.perf_counter()

    annotate = jax.profiler.TraceAnnotation if trace else _null_annotation
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles = _count_compiles()
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        stream = traffic.requests(mix, seed)
        sent, t0 = serve(call, pool, stream, seconds, mix.in_flight,
                         annotate)
        if trace:
            jax.profiler.stop_trace()
        n_compiles = compiles.stop()
        setup_s = t0 - t_process
        t_end = t0 + seconds
        done = [r for r in sent if r.t_done <= t_end]
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:cell.chips])

        summary = None
        if trace:
            from bench import trace as tr
            if keep_trace is not None:
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            dev_ops, spans = tr.load(trace_dir)
            lo = min(s.start_ns for s in spans)
            hi = max(s.end_ns for s in spans)
            summary = tr.summarize(dev_ops, spans, lo, hi)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # correctness, once the window has closed and the peak is read
    buckets = model.buckets
    del model, call
    t_ref = time.perf_counter()
    checked = check.sample(done, mix.check_requests, seed)
    ref_batch = bucket(max(mix.distinct_sizes()))
    numbers = check.compare(
        checked, lambda r: np.asarray(
            reference(params, _padded(pool, r, ref_batch))),
        sizes.get("limits", {}))
    correct = all(check.within(v, lim) for v, lim in numbers.values())
    failed = sum(1 for r in done if not check.well_formed(r.probs, r.size,
                                                          sizes["classes"]))
    correct = correct and failed == 0 and bool(done)
    t_checked = time.perf_counter()

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    print(f"setup: import {t_import - t_process:.3f} s, weights+pack "
          f"{t_weights - t_import:.3f} s, pool+compile/warm "
          f"{t_warm - t_weights:.3f} s, total {setup_s:.3f} s; reference "
          f"{t_checked - t_ref:.3f} s; compiles in window {n_compiles}; "
          f"cache {cache_dir}", file=sys.stderr)
    print(f"window: {len(sent)} sent, {len(done)} done, "
          f"{sum(r.size for r in done)} images", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(sent),
              "failed": failed}
    if trace:
        ctx = Context(cell=cell, summary=summary, sent=sent,
                      buckets=buckets, peak=peak_rates)
        metrics = {}
        for m in cell.per_layer:
            v = reader(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=dev, breakdown={
            "device_ops": [[k, v] for k, v in summary.top_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_by_host]})
    else:
        e2e = {"images_per_s": sum(r.size for r in done) / seconds,
               "request_p95_ms": _p95_ms(done),
               "setup_s": setup_s}
        result.update(metrics={m["name"]: {"value": e2e[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=dev)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    return result


def _padded(pool: np.ndarray, r: Served, batch: int) -> np.ndarray:
    """Request ``r``'s images edge-padded to ``batch``: the copies of the
    last image leave every per-tensor scale, and so every row, as the
    program computed them at its own batch; one reference executable
    serves every request size."""
    x = pool[r.offset:r.offset + r.size]
    return np.pad(x, ((0, batch - r.size),) + ((0, 0),) * 3, mode="edge")


def _p95_ms(done: list[Served]) -> float:
    lat = [(r.t_done - r.t_send) * 1e3 for r in done]
    if len(lat) < 2:
        return float("nan")
    return statistics.quantiles(lat, n=20, method="inclusive")[-1]


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees of a traced run."""

    cell: Cell
    summary: object             # trace.Summary of the traced window
    sent: list[Served]          # every request of the traced window
    buckets: tuple[int, ...]    # the program's batch ladder
    peak: dict                  # peaks.PEAKS entry of the device

    def program_bucket(self, b: int) -> int:
        return min((s for s in self.buckets if s >= b), default=b)

    @functools.cached_property
    def work(self) -> dict:
        """Work of every call the traced window sent, from the reference's
        stage shapes at the batch each call ran (``work.totals``)."""
        from bench import work

        per_bucket = {}
        tot = {"ops": 0.0, "gemm_bound_s": 0.0, "epilogue_bound_s": 0.0}
        for r in self.sent:
            b = self.program_bucket(r.size)
            if b not in per_bucket:
                per_bucket[b] = work.totals(
                    work.stage_shapes(self.cell.family, self.cell.sizes, b),
                    self.peak)
            for k in tot:
                tot[k] += per_bucket[b][k]
        one = work.totals(work.stage_shapes(self.cell.family,
                                            self.cell.sizes, 1), self.peak)
        tot["real_ops"] = one["ops"] * sum(r.size for r in self.sent)
        return tot


class _count_compiles:
    """Counts XLA compilations from construction until ``stop``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0

        def listener(event, duration, **kw):
            if event == self.EVENT:
                self.n += 1
        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def stop(self) -> int:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)
        return self.n
