"""The reduction of the program's own spans and scopes
(``program_trace.py``)."""

import itertools
import statistics

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace as tr

HLO = """\
HloModule jit__lambda, entry_computation_layout={(f32[4,8]{1,0})->f32[4,2]{1,0}}

%fused_computation (param_0: f32[4,8]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %neg.1 = f32[4,8]{1,0} negate(%param_0), metadata={op_name="jit(<lambda>)/s00.conv/im2col/neg"}
}

%fused_quantize (param_0.2: f32[4,8], param_1.3: f32[]) -> s8[4,8] {
  %param_0.2 = f32[4,8]{1,0} parameter(0)
  %constant.9 = f32[]{:T(128)} constant(127), metadata={op_name="jit(<lambda>)/s01.fc/quantize/clip"}
  %clamp.1 = f32[4,8]{1,0} clamp(%param_0.2, %param_0.2, %param_0.2), metadata={op_name="jit(<lambda>)/s00.conv/quantize/clip"}
  ROOT %convert.29 = s8[4,8]{1,0} convert(%clamp.1)
}

ENTRY %main.10 (v.1: f32[4,8]) -> f32[4,2] {
  %v.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="v"}
  %copy.7 = f32[4,8]{0,1:T(8,128)} copy(%v.1)
  %fusion.1 = f32[4,8]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(<lambda>)/s00.conv/im2col/neg"}
  %copy.6 = f32[4,8]{0,1} copy(%fusion.1)
  %constant.73 = f32[]{:T(128)} constant(1e-08)
  %fusion.2 = s8[4,8]{1,0:T(8,128)(4,1)} fusion(%copy.6, %constant.73), kind=kLoop, calls=%fused_quantize
  %pad.3 = s8[8,128]{1,0} pad(%fusion.2, %constant.73), padding=0_4x0_120, metadata={op_name="jit(<lambda>)/s00.conv/jit(mounted_gemm)/mount/pad"}
  %mounted_gemm.4 = s32[8,128]{1,0:T(8,128)S(1)} custom-call(%pad.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/s00.conv/jit(mounted_gemm)/gemm/mounted_gemm/pallas_call"}
  ROOT %fb_epilogue.5 = f32[4,2]{1,0} custom-call(%mounted_gemm.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/s01.fc/epilogue/jit(fb_epilogue)/fb_epilogue/pallas_call"}
}
"""


def ev(name, start, end):
    return tr.Event(name, float(start), float(end - start))


def span(name, start, end, **args):
    return pt.Span(name, float(start), float(end - start), args)


def op(instr, start, end):
    return ev(f"%{instr} = f32[4,8] op(f32[4,8] %x)", start, end)


def window(launches=True) -> pt.Raw:
    """Two requests on a synthetic clock (ns).  Request 1 (3 images,
    bucket 4) pads: it launches a convert and a pad, the program, and a
    slice.  Request 2 (4 images) launches the program alone."""
    runs = [ev("jit_convert_element_type(11)", 20, 30),
            ev("jit__pad(22)", 35, 45),
            ev("jit__lambda(33)", 60, 300),
            ev("jit_dynamic_slice(44)", 310, 320),
            ev("jit__lambda(33)", 430, 600)]
    ops = [op("convert.1", 20, 30), op("pad.1", 35, 45),
           op("fusion.1", 60, 100), op("copy.6", 100, 110),
           op("fusion.2", 110, 130), op("pad.3", 130, 140),
           op("mounted_gemm.4", 140, 200), op("fb_epilogue.5", 200, 280),
           op("copy.7", 280, 290), op("mystery.9", 290, 300),
           op("dynamic-slice.1", 310, 320),
           op("fusion.1", 430, 470), op("fusion.2", 470, 490),
           op("pad.3", 490, 500), op("mounted_gemm.4", 500, 560),
           op("fb_epilogue.5", 560, 600)]
    spans = [span("bench.run", 0, 100),
             span("repro.run", 10, 95, request=1, batch=3, bucket=4),
             span("repro.run.pad", 12, 40, request=1),
             span("repro.run.call", 40, 80, request=1, bucket=4, new=1),
             span("repro.run.slice", 80, 94, request=1),
             span("bench.fetch", 100, 400),
             span("bench.run", 402, 450),
             span("repro.run", 405, 445, request=2, batch=4, bucket=4),
             span("repro.run.pad", 406, 410, request=2),
             span("repro.run.call", 410, 440, request=2, bucket=4, new=0),
             span("repro.run.slice", 440, 444, request=2),
             span("bench.fetch", 450, 700)]
    return pt.Raw(ops=ops, runs=runs, spans=spans,
                  launches=[15, 30, 50, 85, 420] if launches else [])


def test_scopes_of_the_compiled_text():
    """Own scopes, a fusion without metadata taking its body's root's
    (not its operand's), a copy inheriting its operand's, a copy of a
    parameter inheriting its reader's, and layouts whose tiling holds
    parentheses."""
    m = pt.scope_map(HLO)
    assert pt.module_name(HLO) == "jit__lambda"
    assert m["fusion.1"] == m["copy.6"] == m["copy.7"] == ("s00.conv",
                                                           "im2col")
    assert m["fusion.2"] == ("s00.conv", "quantize")
    assert m["pad.3"] == ("s00.conv", "mount")
    assert m["mounted_gemm.4"] == ("s00.conv", "gemm")
    assert m["fb_epilogue.5"] == ("s01.fc", "epilogue")
    assert m["constant.73"] == ("s00.conv", "quantize")   # its first reader
    assert pt.scope_of("jit(f)/s03.x/gemm/mount/pad") is None  # two phases
    assert pt.instruction(op("pad.91.clone", 0, 1)) == "pad.91.clone"


@pytest.mark.parametrize("launches", [True, False],
                         ids=["launch-order", "call-spans"])
def test_reduction_of_a_synthetic_window(launches):
    p = pt.reduce(window(launches), {4: HLO}, 0, 700)
    assert p.op_s == pytest.approx(440e-9)
    assert p.phase_s == pytest.approx({"im2col": 100e-9, "quantize": 40e-9,
                                       "mount": 20e-9, "gemm": 120e-9,
                                       "epilogue": 120e-9})
    assert p.stage_s == pytest.approx({"s00.conv": 280e-9,
                                       "s01.fc": 120e-9})
    assert p.program_s == pytest.approx(410e-9)
    assert p.unscoped_s == pytest.approx(10e-9)
    assert p.other_s == pytest.approx({"jit_convert_element_type": 10e-9,
                                       "jit__pad": 10e-9,
                                       "jit_dynamic_slice": 10e-9})
    assert p.span_ms["repro.run.pad"] == pytest.approx([28e-6, 4e-6])
    assert p.span_ms["repro.run.call"] == pytest.approx([40e-6, 30e-6])
    # request 1's last op is its slice's (ends 320) where launches tie
    # runs to requests, else its program's (ends 300)
    first = 80e-6 if launches else 100e-6
    assert p.tail_ms == pytest.approx([first, 100e-6])
    assert p.new_calls == 1
    assert "new calls 1" in p.describe()


def test_idle_by_innermost_span_sums_to_the_idle_time():
    raw = window()
    p = pt.reduce(raw, {4: HLO}, 0, 700)
    assert p.idle_s == pytest.approx({
        "bench.run": 13e-9, "repro.run": 3e-9, "repro.run.pad": 17e-9,
        "repro.run.call": 35e-9, "bench.fetch": 190e-9,
        pt.BETWEEN: 2e-9})
    busy = tr.length(tr.union((e.start_ns, e.end_ns) for e in raw.ops))
    assert sum(p.idle_s.values()) == pytest.approx((700 - busy) * 1e-9)


def test_shares_spans_and_tail_of_a_synthetic_window():
    """What per-layer metrics would read: three phase shares of all
    op time, the mean ``repro.run.pad`` and ``repro.run.call``, and the
    mean result tail."""
    p = pt.reduce(window(), {4: HLO}, 0, 700)
    shares = {ph: 100 * p.phase_s[ph] / p.op_s
              for ph in ("im2col", "quantize", "mount")}
    assert shares == pytest.approx({"im2col": 100 * 100 / 440,
                                    "quantize": 100 * 40 / 440,
                                    "mount": 100 * 20 / 440})
    assert statistics.fmean(p.span_ms["repro.run.pad"]) == \
        pytest.approx(16e-6)
    assert statistics.fmean(p.span_ms["repro.run.call"]) == \
        pytest.approx(35e-6)
    assert statistics.fmean(p.tail_ms) == pytest.approx(90e-6)


def test_a_program_without_spans_or_scopes_reads_empty():
    """An older program: no ``repro.`` spans, no compiled text."""
    raw = window()
    raw = pt.Raw(ops=raw.ops, runs=raw.runs, launches=raw.launches,
                 spans=[s for s in raw.spans if s.name.startswith("bench.")])
    p = pt.reduce(raw, {}, 0, 700)
    assert not any(p.phase_s.values()) and p.program_s == 0
    assert p.span_ms == {} and p.tail_ms == [] and p.stage_s == {}
    assert sum(p.other_s.values()) == pytest.approx(p.op_s)
    assert sum(p.idle_s.values()) == pytest.approx(260e-9)


def test_read_a_cpu_trace_of_served_requests(tmp_path):
    """``read`` on a CPU profiler trace of requests served through
    ``CompiledModel.run`` inside ``bench.run``/``bench.fetch`` spans: the
    program's spans are found and the compiled text of each bucket the
    window ran is fetched (the CPU trace has no device ops)."""
    import jax
    import numpy as np

    from repro import api
    from repro.api import HurryConfig

    model = api.compile("alexnet", HurryConfig(array_rows=511))
    shape = model.program.input_shape(1)[1:]
    pool = np.zeros((8,) + shape, np.float32)
    for b in (1, 3):
        np.asarray(model.run(pool[:b]))
    fetched = []
    text_of = model.compiled_text

    def compiled_text(x, **kw):
        fetched.append(x.shape[0])
        return text_of(x, **kw)
    model.compiled_text = compiled_text
    jax.profiler.start_trace(str(tmp_path))
    sent, _ = harness.serve(model.run, pool, itertools.cycle([(3, 0), (1, 4)]),
                            0.2, 1, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    spans = pt.load(str(tmp_path)).spans
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    p = pt.read(str(tmp_path), model, shape, lo, hi)
    assert sorted(fetched) == [1, 4]
    assert len(p.span_ms["repro.run"]) == len(sent) >= 2
    for name in ("repro.run.pad", "repro.run.call", "repro.run.slice"):
        assert len(p.span_ms[name]) == len(sent)
    assert p.op_s == 0 and p.new_calls == 0


def test_resnet18_program_is_scoped_on_cpu():
    """Every instruction of the entry computation of the CIFAR ResNet-18
    program, compiled for the CPU, maps to one stage and one phase
    (after the inheritance of the module docstring), and no ``op_name``
    carries two stages or two phases."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.api import HurryConfig

    model = api.compile("resnet18", HurryConfig(array_rows=511))
    text = model.compiled_text(jax.ShapeDtypeStruct(
        model.program.input_shape(2), jnp.float32))
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    names = [m.group(1) for m in map(pt._INSTR.match, entry.splitlines())
             if m]
    scopes = pt.scope_map(text)
    assert len(names) > 500
    assert [n for n in names if n not in scopes] == []
    stages = {scopes[n][0] for n in names}
    assert len(stages) == len(model.program.stages()) == 21
    assert {scopes[n][1] for n in names} == set(pt.PHASES)
    for op_name, _ in pt.parse_hlo(text).values():
        parts = (op_name or "").split("/")
        assert sum(1 for c in parts if pt.STAGE.match(c)) <= 1, op_name
        assert sum(c in pt.PHASES for c in parts) <= 1, op_name
