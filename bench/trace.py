"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  A device
plane (``/device:TPU:<n>``) holds the operations that ran on the chip,
one event per operation on its ``XLA Ops`` line.  A host plane holds the
benchmark's own spans (``jax.profiler.TraceAnnotation``, names starting
``bench.``) on the same clock.

Every device operation falls in one of three classes:

* ``gemm`` — the ``mounted_gemm`` Pallas kernel (static and dynamic
  stages alike);
* ``epilogue`` — the ``fb_epilogue`` Pallas kernel;
* ``glue`` — everything else: im2col, quantization, mount layout, plane
  packing, batch padding and slicing, copies.

On the TPU an op's event is named by its HLO instruction
(``%mounted_gemm.21 = s32[...] custom-call(...)``): a kernel is a custom
call whose instruction is named after the jitted function that issued
it, ``mounted_gemm`` or ``fb_epilogue``.  Only the instruction's own
name counts: its operands name other instructions.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable

CLASS_OF = {"mounted_gemm": "gemm", "fb_epilogue": "epilogue"}
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def op_name(e: Event) -> str:
    """The instruction's name without instance numbers or ``.clone``:
    ``%pad.91.clone = ...`` -> ``pad``."""
    head = e.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.(\d+|clone)", "", head)


def classify(e: Event) -> str:
    """``gemm``, ``epilogue`` or ``glue`` (module docstring)."""
    kind = CLASS_OF.get(op_name(e))
    if kind and "custom-call(" in e.name:
        return kind
    return "glue"


def op_family(e: Event) -> str:
    """A device op's class and instruction name: ``gemm:mounted_gemm``,
    ``glue:convert_convert_fusion``."""
    return f"{classify(e)}:{op_name(e)}"


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals; sorted, disjoint."""
    merged: list[list[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(t, hi)) for s, t in intervals
            if t > lo and s < hi]


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between ``busy`` (disjoint, sorted)."""
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return [(s, t) for s, t in out if t > s]


def load(trace_dir: str) -> tuple[dict[str, list[Event]], list[Event]]:
    """Device ops per device plane, and the host's ``bench.`` spans."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Event(e.name, e.start_ns, e.duration_ns))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
    return {k: v for k, v in devices.items() if v}, spans


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from one traced window."""

    window_s: float
    busy_s: float                       # union of op intervals, mean/chip
    class_s: dict[str, float]           # summed op time per class
    top_ops: list[tuple[str, float]]    # op families by summed time
    idle_by_host: list[tuple[str, float]]   # idle time by host activity


def summarize(devices: dict[str, list[Event]], spans: list[Event],
              lo_ns: float, hi_ns: float, top: int = 10) -> Summary:
    """Reduce one traced window [lo_ns, hi_ns] (the trace's clock)."""
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    busy_total, class_s, fam = 0.0, {"gemm": 0.0, "epilogue": 0.0,
                                     "glue": 0.0}, {}
    idle: dict[str, float] = {}
    host = sorted(spans, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    for ops in devices.values():
        ops = [e for e in ops if e.end_ns > lo_ns and e.start_ns < hi_ns]
        busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo_ns, hi_ns)
        busy_total += length(busy)
        for e in ops:
            s, t = max(e.start_ns, lo_ns), min(e.end_ns, hi_ns)
            class_s[classify(e)] += (t - s) * 1e-9
            f = op_family(e)
            fam[f] = fam.get(f, 0.0) + (t - s) * 1e-9
        for s, t in gaps(busy, lo_ns, hi_ns):
            what = _host_activity(host, starts, (s + t) / 2)
            idle[what] = idle.get(what, 0.0) + (t - s) * 1e-9
    n = len(devices)
    return Summary(
        window_s=(hi_ns - lo_ns) * 1e-9,
        busy_s=busy_total * 1e-9 / n,
        class_s={k: v / n for k, v in class_s.items()},
        top_ops=sorted(((k, v / n) for k, v in fam.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_by_host=sorted(((k, v / n) for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top])


def _host_activity(host: list[Event], starts: list[float], mid: float) -> str:
    """The host span (non-overlapping, sorted by ``starts``) at ``mid``."""
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and host[i].end_ns >= mid:
        return f"host:{host[i].name}"
    return "host:between spans"
