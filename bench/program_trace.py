"""Reduction of a traced window to the program's own spans and scopes.

The program marks its work in two ways (``repro.api.CompiledModel.run``
and ``repro.program.execute``):

* host spans, on the profiler's clock: ``repro.run`` around each call of
  ``CompiledModel.run``, with the children ``repro.run.pad`` (padding to
  the bucket), ``repro.run.call`` (the jitted program's dispatch) and
  ``repro.run.slice`` (the ``[:b]``).  They carry ``request`` (one id per
  call, shared by its four spans), ``batch``, ``bucket`` and, on
  ``repro.run.call``, ``new`` (1 on a bucket's first call);
* device scopes, in the ``op_name`` metadata of the compiled HLO: one
  stage scope ``s<NN>.<buffer>`` and, inside it, one phase scope:
  ``im2col``, ``quantize``, ``mount``, ``gemm`` or ``epilogue``.

Device ops are tied to them in three steps, on one device (the first
device plane of the trace).

1. Each event of the device's ``XLA Modules`` line is one run of one
   executable.  An op belongs to the run whose interval holds its start.
2. Runs are tied to the calls that launched them in launch order.  The
   device runs one queue in order, so the k-th executable launch on the
   host (``PJRT_LoadedExecutable_Execute``) is the k-th run.  The launch
   lies inside one ``repro.run.*`` span: that names the request, and a
   launch inside ``repro.run.call`` is the program at that span's
   ``bucket``.  Where launches and runs do not pair up, runs of the
   program (its HLO module's name) are paired with ``repro.run.call``
   spans in order instead, and the other runs go to no request.
3. An op of a run of the program at bucket ``b`` takes the scope of its
   HLO instruction in the compiled text of that bucket
   (``CompiledModel.compiled_text``).  An instruction with no scope of
   its own takes one from what it reads, depth first: a fusion from
   the root of its fused computation (XLA often leaves the fusion
   itself without metadata), a copy that layout assignment inserted
   from the operand it copies, any other op from its operands in
   order.  Where nothing it reads has a scope (it reads only
   parameters), it takes the scope of the first instruction that reads
   it.

Where the program marks nothing (an older program), every reading comes
out empty.  ``read`` is the one call a traced run makes, before its
trace directory and its model go; the harness does not make it yet
(PERF.md §7 lists the edit and the metrics that would read the result).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

from bench import trace as tr

PHASES = ("im2col", "quantize", "mount", "gemm", "epilogue")
STAGE = re.compile(r"s\d{2,}\.")
LAUNCH = "PJRT_LoadedExecutable_Execute"
MODULES_LINE = "XLA Modules"
BETWEEN = "between spans"


@dataclasses.dataclass(frozen=True)
class Span:
    """A host span: ``repro.*`` or ``bench.*``, with its integer args."""

    name: str
    start_ns: float
    dur_ns: float
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Raw:
    """What the reduction reads of one trace."""

    ops: list[tr.Event]         # device ops (``XLA Ops``)
    runs: list[tr.Event]        # executable runs (``XLA Modules``)
    spans: list[Span]           # ``repro.`` and ``bench.`` host spans
    launches: list[float]       # host start of every executable launch


def load(trace_dir: str) -> Raw:
    """The ops and runs of the first device plane, and the host's spans
    and launches, of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    raw = Raw([], [], [], [])
    planes = sorted(data.planes, key=lambda p: p.name)
    device = next((p.name for p in planes if p.name.startswith("/device:")
                   and any(ln.name == tr.OPS_LINE for ln in p.lines)), None)
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines if plane.name == device else ():
                dest = {tr.OPS_LINE: raw.ops,
                        MODULES_LINE: raw.runs}.get(line.name)
                if dest is not None:
                    dest.extend(tr.Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "bench.")):
                    raw.spans.append(Span(e.name, e.start_ns, e.duration_ns,
                                          {k: v for k, v in e.stats
                                           if isinstance(v, int)}))
                elif e.name == LAUNCH:
                    raw.launches.append(e.start_ns)
    return raw


# -- scopes of the compiled HLO -------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"(?:^|\s)[a-z][\w\-]*\(")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s")


def scope_of(op_name: str) -> tuple[str, str] | None:
    """``(stage, phase)`` of an ``op_name`` with exactly one stage scope
    and one phase scope among its components, else None."""
    parts = op_name.split("/")
    stages = [p for p in parts if STAGE.match(p)]
    phases = [p for p in parts if p in PHASES]
    if len(stages) == 1 and len(phases) == 1:
        return stages[0], phases[0]
    return None


def _operands(rhs: str) -> list[str]:
    """Operand names of an instruction's right-hand side
    (``shape opcode(operands), attributes``; a layout's tiling,
    ``{1,0:T(8,128)}``, holds parentheses too)."""
    m = _OPCODE.search(rhs)
    if not m:
        return []
    depth = 0
    for j in range(m.end() - 1, len(rhs)):
        depth += (rhs[j] == "(") - (rhs[j] == ")")
        if depth == 0:
            return re.findall(r"%([\w.\-]+)", rhs[m.end():j])
    return []


def parse_hlo(text: str) -> dict[str, tuple[str | None, list[str]]]:
    """Instruction name -> (own ``op_name`` or None, what it reads), over
    every computation of the module.  What an instruction reads is the
    root of the computation it calls, if any (a fusion's body), then its
    operands."""
    instrs, roots, comp = {}, {}, None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or line.lstrip().startswith(("HloModule", "//")):
            head = _COMPUTATION.match(line)
            if head and line.rstrip().endswith("{"):
                comp = head.group(1)
            continue
        name, rhs = m.groups()
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name
        op = _OP_NAME.search(rhs)
        called = _CALLS.search(rhs)
        instrs[name] = (op.group(1) if op else None, _operands(rhs),
                        called.group(1) if called else None)
    return {n: (op, ([roots[c]] if c in roots else []) + operands)
            for n, (op, operands, c) in instrs.items()}


def scope_map(text: str) -> dict[str, tuple[str, str]]:
    """Instruction name -> (stage, phase) for every instruction of
    ``text`` that has a scope, its own or inherited (module docstring)."""
    instrs = parse_hlo(text)
    users = collections.defaultdict(list)
    for name, (_, reads) in instrs.items():
        for o in reads:
            users[o].append(name)
    own = {n: scope_of(op) if op else None for n, (op, _) in instrs.items()}

    def walk(start, nxt, found):
        """The first scope in ``found`` from ``start`` along ``nxt``
        (depth first, each instruction visited once)."""
        seen, stack = set(), [start]
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            if found.get(n):
                return found[n]
            stack.extend(reversed(nxt(n)))
        return None

    up = {n: walk(n, lambda n: instrs[n][1] if n in instrs else [], own)
          for n in instrs}
    out = {n: up[n] or walk(n, lambda n: users.get(n, []), up)
           for n in instrs}
    return {n: s for n, s in out.items() if s}


def module_name(text: str) -> str | None:
    """The ``HloModule`` name of a compiled text."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", text)
    return m.group(1) if m else None


def instruction(e: tr.Event) -> str:
    """A device op's HLO instruction name: ``%fusion.12 = ...`` ->
    ``fusion.12``."""
    return e.name.split(" = ", 1)[0].strip().lstrip("%")


def base_name(run: tr.Event) -> str:
    """An executable run's module name without its fingerprint."""
    return run.name.split("(", 1)[0]


# -- the reduction ---------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    """What the per-layer readers take of the program's spans and scopes
    in one traced window."""

    op_s: float                         # all device op time in the window
    phase_s: dict[str, float]           # op time per phase scope
    stage_s: dict[str, float]           # op time per stage scope
    program_s: float                    # op time of the program's runs
    unscoped_s: float                   # ... of which found no scope
    other_s: dict[str, float]           # op time of other executables
    span_ms: dict[str, list[float]]     # per request: repro.run.* lengths
    tail_ms: list[float]                # per request: result tail
    idle_s: dict[str, float]            # idle time by innermost span
    new_calls: int                      # repro.run.call with new=1

    def describe(self, top: int = 5) -> str:
        """One line for standard error."""
        idle = ", ".join(f"{k} {v:.6f} s" for k, v in
                         sorted(self.idle_s.items(), key=lambda kv: -kv[1]))
        stages = ", ".join(f"{k} {v:.6f} s" for k, v in
                           sorted(self.stage_s.items(),
                                  key=lambda kv: -kv[1])[:top])
        other = ", ".join(f"{k} {v:.6f} s" for k, v in
                          sorted(self.other_s.items()))
        scoped = (100 * (1 - self.unscoped_s / self.program_s)
                  if self.program_s else 0.0)
        return (f"program: idle by span: {idle or 'none'}; program op time "
                f"{self.program_s:.6f} s, {scoped:.2f} % scoped, unscoped "
                f"{self.unscoped_s:.6f} s; other executables: "
                f"{other or 'none'}; top stages by self time: "
                f"{stages or 'none'}; new calls {self.new_calls}")


def _innermost(spans: list[Span], lo: float, hi: float
               ) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into (start, end, name) pieces, each named after the
    innermost span over it (the one that started last), or ``BETWEEN``.
    Spans of one thread nest; the pieces are disjoint and cover
    [lo, hi]."""
    out: list[tuple[float, float, str]] = []
    cur = lo

    def emit(t, name):
        nonlocal cur
        t = min(max(t, cur), hi)
        if t > cur:
            out.append((cur, t, name))
            cur = t

    stack: list[Span] = []
    for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            top = stack.pop()
            emit(top.end_ns, top.name)
        emit(s.start_ns, stack[-1].name if stack else BETWEEN)
        stack.append(s)
    while stack:
        top = stack.pop()
        emit(top.end_ns, top.name)
    emit(hi, BETWEEN)
    return out


def idle_by_span(ops: list[tr.Event], spans: list[Span], lo: float,
                 hi: float) -> dict[str, float]:
    """Every idle nanosecond of [lo, hi] (no op running) by the
    innermost host span over it; the parts sum to the idle time."""
    busy = tr.clip(tr.union((e.start_ns, e.end_ns) for e in ops), lo, hi)
    pieces = _innermost(spans, lo, hi)
    out: dict[str, float] = {}
    i = 0
    for s, t in tr.gaps(busy, lo, hi):
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < t:
            a, b, name = pieces[j]
            d = min(b, t) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
            j += 1
    return out


def _enclosing(spans: list[Span], starts: list[float], t: float
               ) -> Span | None:
    """The innermost of nested ``spans`` (sorted by start) holding ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].start_ns <= t <= spans[i].end_ns:
            return spans[i]
        i -= 1
    return None


def _assign(raw: Raw, program: str | None
            ) -> tuple[list[int | None], list[int | None]]:
    """Per run (``raw.runs`` sorted by start): the request that launched
    it, and its bucket if it is the program (module docstring, step 2)."""
    runs = raw.runs
    request: list[int | None] = [None] * len(runs)
    bucket: list[int | None] = [None] * len(runs)
    subs = sorted((s for s in raw.spans
                   if s.name.startswith("repro.run.")),
                  key=lambda s: s.start_ns)
    if raw.launches and len(raw.launches) == len(runs):
        starts = [s.start_ns for s in subs]
        for k, t in enumerate(sorted(raw.launches)):
            s = _enclosing(subs, starts, t)
            if s is None:
                continue
            request[k] = s.args.get("request")
            if s.name == "repro.run.call":
                bucket[k] = s.args.get("bucket")
        return request, bucket
    calls = [s for s in subs if s.name == "repro.run.call"]
    mains = [k for k, r in enumerate(runs)
             if program and base_name(r) == program]
    for k, s in zip(mains, calls):
        request[k] = s.args.get("request")
        bucket[k] = s.args.get("bucket")
    return request, bucket


def reduce(raw: Raw, texts: dict[int, str], lo: float, hi: float
           ) -> ProgramTrace:
    """Reduce one traced window [lo, hi] (the trace's clock).  ``texts``
    maps a bucket to the compiled HLO text of the program at it."""
    runs = sorted(raw.runs, key=lambda e: e.start_ns)
    raw = dataclasses.replace(raw, runs=runs)
    programs = {module_name(t) for t in texts.values()} - {None}
    request, bucket = _assign(raw, next(iter(programs), None))
    # a module run of a bucket's program keeps that bucket wherever it
    # recurs (a fingerprint is one executable)
    by_name = {runs[k].name: b for k, b in enumerate(bucket) if b}
    scopes = {b: scope_map(t) for b, t in texts.items()}

    ops = sorted((e for e in raw.ops if e.end_ns > lo and e.start_ns < hi),
                 key=lambda e: e.start_ns)
    run_starts = [r.start_ns for r in runs]
    phase_s = dict.fromkeys(PHASES, 0.0)
    stage_s: dict[str, float] = collections.defaultdict(float)
    other_s: dict[str, float] = collections.defaultdict(float)
    op_s = program_s = unscoped_s = 0.0
    last_end: dict[int, float] = {}
    for e in ops:
        d = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
        op_s += d
        k = bisect.bisect_right(run_starts, e.start_ns) - 1
        run = runs[k] if k >= 0 and e.start_ns < runs[k].end_ns else None
        if run is None:
            other_s["(no executable)"] += d
            continue
        if request[k] is not None:
            last_end[request[k]] = max(last_end.get(request[k], 0.0),
                                       e.end_ns)
        b = by_name.get(run.name)
        if b is None and base_name(run) not in programs:
            other_s[base_name(run)] += d
            continue
        program_s += d
        scope = scopes.get(b, {}).get(instruction(e))
        if scope is None:
            unscoped_s += d
            continue
        stage, phase = scope
        phase_s[phase] += d
        stage_s[stage] += d

    spans = [s for s in raw.spans if s.end_ns > lo and s.start_ns < hi]
    span_ms: dict[str, list[float]] = collections.defaultdict(list)
    for s in spans:
        if s.name.startswith("repro.run"):
            span_ms[s.name].append(s.dur_ns * 1e-6)

    # the caller awaits requests in the order it sent them: the k-th
    # bench.fetch waits on the request of the k-th bench.run
    sends = sorted((s for s in spans if s.name == "bench.run"),
                   key=lambda s: s.start_ns)
    fetches = sorted((s for s in spans if s.name == "bench.fetch"),
                     key=lambda s: s.start_ns)
    send_starts = [s.start_ns for s in sends]
    tail_ms = []
    for s in spans:
        r = s.args.get("request")
        if s.name != "repro.run" or r not in last_end:
            continue
        i = bisect.bisect_right(send_starts, s.start_ns) - 1
        if 0 <= i < len(fetches) and sends[i].end_ns >= s.end_ns:
            tail_ms.append((fetches[i].end_ns - last_end[r]) * 1e-6)

    return ProgramTrace(
        op_s=op_s, phase_s=phase_s,
        stage_s=dict(stage_s), program_s=program_s, unscoped_s=unscoped_s,
        other_s=dict(other_s), span_ms=dict(span_ms), tail_ms=tail_ms,
        idle_s=idle_by_span(ops, spans, lo, hi),
        new_calls=sum(1 for s in spans if s.name == "repro.run.call"
                      and s.args.get("new") == 1))


def read(trace_dir: str, model, input_shape: tuple, lo: float, hi: float
         ) -> ProgramTrace:
    """The window [lo, hi] of the trace under ``trace_dir``, with the
    compiled text of every bucket its ``repro.run.call`` spans name,
    read from ``model`` (``CompiledModel.compiled_text``, where the
    model has it) for requests of ``input_shape`` float32 images."""
    import jax
    import numpy as np

    raw = load(trace_dir)
    buckets = {s.args["bucket"] for s in raw.spans
               if s.name == "repro.run.call" and "bucket" in s.args}
    texts = {}
    if hasattr(model, "compiled_text"):
        for b in sorted(buckets):
            texts[b] = model.compiled_text(
                jax.ShapeDtypeStruct((b,) + tuple(input_shape), np.float32))
    return reduce(raw, texts, lo, hi)

