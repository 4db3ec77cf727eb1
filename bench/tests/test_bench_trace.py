"""The trace reduction: interval union, idle gaps by host activity, and
kernel classification."""

import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def test_union_and_gaps():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert merged == [(0, 3), (5, 8), (10, 12)]
    assert tr.length(merged) == 8
    assert tr.clip(merged, 1, 11) == [(1, 3), (5, 8), (10, 11)]
    assert tr.gaps(merged, -1, 13) == [(-1, 0), (3, 5), (8, 10), (12, 13)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_classification():
    """Events are named by their HLO instruction; operands do not count."""
    gemm = ev("%mounted_gemm.21 = s32[256,128]{1,0:T(8,128)} custom-call("
              "s8[256,27]{1,0} %copy.348, s8[27,128]{1,0} %pad.58)", 0, 1)
    epi = ev("%fb_epilogue.22 = f32[256,64]{1,0} custom-call(s32[256,64]"
             "{1,0} %mounted_gemm.22, f32[1,1]{1,0} %bitcast.9)", 0, 1)
    pad = ev("%pad.91.clone = s8[64,1479]{0,1} pad(s8[64,1152]{0,1} "
             "%reshape.526, s8[] %constant.122), padding=0_0x0_327", 0, 1)
    use = ev("%broadcast_in_dim.409 = f32[4,4,4,512,1]{3,2,1,0,4} reshape("
             "f32[64,512]{1,0} %fb_epilogue.36)", 0, 1)
    other = ev("%custom-call.99 = f32[4,4]{1,0} custom-call(f32[4,4]{1,0} "
               "%slice-done.20)", 0, 1)
    assert [tr.classify(e) for e in (gemm, epi, pad, use, other)] == [
        "gemm", "epilogue", "glue", "glue", "glue"]
    assert tr.op_name(pad) == "pad"
    assert tr.op_family(epi) == "epilogue:fb_epilogue"


def test_summary_on_a_synthetic_window():
    ops = {"/device:TPU:0": [
        ev("%mounted_gemm.1 = s32[8,128] custom-call(s8[8,8] %a)", 10, 20),
        ev("%fb_epilogue.2 = f32[8,128] custom-call(s32[8,128] %b)", 30, 10),
        ev("%fusion.7 = s8[8,8] fusion(f32[8,8] %c)", 60, 10),
        ev("%fusion.8 = s8[8,8] fusion(f32[8,8] %d)", 65, 10),  # overlaps
    ]}
    spans = [ev("bench.run", 0, 50), ev("bench.fetch", 50, 50)]
    s = tr.summarize(ops, spans, 0, 100)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)
    assert s.class_s == pytest.approx({"gemm": 20e-9, "epilogue": 10e-9,
                                       "glue": 20e-9})
    assert dict(s.idle_by_host) == pytest.approx(
        {"host:bench.run": 10e-9, "host:bench.fetch": 45e-9})


def test_recorded_trace():
    """A slice of a traced window of ``resnet18-cifar10.online-mixed`` on a
    TPU v5e (``data/trace_slice.json``): six host spans, three requests,
    and the device ops under them, names cut to the instruction and its
    operand list.  Each request runs the 21 stages once: 21 GEMM and 21
    epilogue kernels."""
    rec = json.loads((DATA / "trace_slice.json").read_text())
    ops = {p: [tr.Event(*e) for e in evs] for p, evs in rec["devices"].items()}
    spans = [tr.Event(*e) for e in rec["spans"]]
    counts = {}
    for e in next(iter(ops.values())):
        counts[tr.classify(e)] = counts.get(tr.classify(e), 0) + 1
    assert counts["gemm"] == counts["epilogue"] == 3 * 21
    assert counts == rec["expect"]["counts"]
    lo = min(e.start_ns for e in spans)
    hi = max(e.end_ns for e in spans)
    s = tr.summarize(ops, spans, lo, hi)
    assert s.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert s.window_s == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
