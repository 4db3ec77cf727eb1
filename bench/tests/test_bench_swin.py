"""Swin-T (``swin``) in the benchmark, on the CPU.

At a small size (32x32 images in 4x4 patches: an 8x8 token grid, window
4, depths (2, 2), widths 16/32, heads 2/4, 10 classes), so that the
first stage has a shifted block and the second is one window, on
seeded weights: the program against the plain reference, within the
configuration's own ``prob_err`` limit, and the lower-precision controls
and five wrong models (no shift, no shift mask, a zero bias table, the
merge's x1 and x2 swapped, no patch norm) outside it; the relative
position index and the shift mask against a hand-built case; the work
counts of the published size against a hand count; the graph and
weights against the zoo's; the program's scopes; the check that decides
``correct`` on a tiny cell; and the new readers.
"""

import functools
import json
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, work
from bench import program_trace as pt
from bench.harness import _module
from bench.reference import CONTROLS, EXACT, Ref
from bench.tests.test_bench_correctness import (alter_one_answer,
                                                drop_half_the_batch)
from bench.tests.tiny import BENCH, make_tree

SMALL = dict(input_hw=32, patch=4, window=4, depths=[2, 2], embed_dim=16,
             heads=[2, 4], classes=10)
# the tiny cell: a 4x4 grid of 2x2 windows, then one window after merging
TINY = dict(input_hw=16, patch=4, window=2, depths=[2, 1], embed_dim=8,
            heads=[1, 2], classes=10)
SEED = 2**33 + 31


def _config(**cut):
    sizes = json.loads((BENCH / "configs" / "swin-t-224.json").read_text())
    sizes.update(cut)
    return sizes, _module(BENCH / "models" / f"{sizes['family']}.py",
                          f"bench_family_{sizes['family']}")


# TOL: the configuration's own limit, which the chip run calibrated
# (PERF.md §4): the program must meet it at any size, and a control or
# a wrong model must not.
TOL = _config()[0]["limits"]["prob_err"]


@pytest.fixture(scope="module")
def small():
    sizes, family = _config(**SMALL)
    params = jax.jit(functools.partial(family.init, sizes=sizes))(
        jax.random.PRNGKey(7))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(8),
                                     (8, 32, 32, 3)))
    return sizes, family, params, x


@pytest.fixture(scope="module")
def reference(small):
    return _reference(small)


def _reference(small, prec=EXACT):
    sizes, family, params, x = small
    ref = Ref(prec)
    return np.asarray(jax.jit(lambda p, v: family.reference(
        p, v, sizes, ref))(params, x))


def _program(small, graph=None, params=None):
    from repro import api
    from repro.api import HurryConfig

    sizes, family, p, x = small
    model = api.compile(graph or family.graph(sizes),
                        HurryConfig(**sizes["hurry"]), params=params or p)
    return np.asarray(model.run(x))


def test_program_is_within_the_limit(small, reference):
    err = check.prob_err(_program(small), reference)
    assert err <= TOL, err


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_outside_the_limit(small, reference, control):
    err = check.prob_err(_reference(small, CONTROLS[control]), reference)
    assert err > TOL, err


def _graph(sizes, *, shift=True, patch_norm=True):
    """The family's graph, or a wrong model: no shifted blocks, or no
    layer norm on the patch embedding."""
    from repro.api import NetworkBuilder

    dim, eps, win = sizes["embed_dim"], sizes["ln_eps"], sizes["window"]
    nb = NetworkBuilder("wrong", input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    entry = nb.conv(dim, k=sizes["patch"], stride=sizes["patch"], padding=0,
                    name="patch")
    if patch_norm:
        entry = nb.layernorm(eps=eps, name="patch_norm")
    for s, (depth, heads) in enumerate(zip(sizes["depths"], sizes["heads"])):
        if s:
            nb.layernorm(pre=True, eps=eps, name=f"s{s}_merge_norm")
            dim *= 2
            entry = nb.linear(dim, merge=True, bias=False,
                              name=f"s{s}_merge")
        for i in range(depth):
            n = f"s{s}b{i}"
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln1")
            nb.attention(heads, window=win,
                         shift=win // 2 if i % 2 and shift else 0,
                         name=f"{n}_attn")
            r1 = nb.residual(entry, name=f"{n}_res1")
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln2")
            nb.linear(dim * sizes["mlp_ratio"], name=f"{n}_fc1")
            nb.gelu(approx="erf", name=f"{n}_gelu")
            nb.linear(dim, name=f"{n}_fc2")
            entry = nb.residual(r1, name=f"{n}_res2")
    nb.layernorm(eps=eps, name="norm")
    nb.seqpool(mode="mean", name="pool")
    nb.fc(sizes["classes"], name="head")
    nb.softmax(name="softmax")
    return nb.build()


def _swapped_merge(x, grid):
    """Patch merging with x1 and x2 swapped: (0, 0), (0, 1), (1, 0),
    (1, 1)."""
    b, _, c = x.shape
    x = x.reshape(b, grid, grid, c)
    parts = [x[:, i::2, j::2] for i, j in ((0, 0), (0, 1), (1, 0), (1, 1))]
    return jnp.concatenate(parts, axis=-1).reshape(b, -1, 4 * c)


@pytest.mark.parametrize("fault", ["no_shift", "no_mask", "zero_bias",
                                   "merge_order", "no_patch_norm"])
def test_wrong_model_is_outside_the_limit(small, reference, fault,
                                          monkeypatch):
    from repro.program import execute

    sizes, _, params, _ = small
    graph = _graph(sizes, shift=fault != "no_shift",
                   patch_norm=fault != "no_patch_norm")
    if fault == "no_patch_norm":
        params = {k: v for k, v in params.items() if k != "patch_norm"}
    if fault == "zero_bias":
        params = {k: (dict(v, relb=jnp.zeros_like(v["relb"]))
                      if "relb" in v else v) for k, v in params.items()}
    if fault == "no_mask":
        monkeypatch.setattr(execute, "shift_mask", lambda *a: None)
    if fault == "merge_order":
        monkeypatch.setattr(execute, "space_to_depth", _swapped_merge)
    err = check.prob_err(_program(small, graph, params), reference)
    assert err > TOL, err


def test_the_right_model_built_by_hand_is_the_family_graph(small):
    sizes = small[0]
    assert _graph(sizes).layers == small[1].graph(sizes).layers


# -- the window tables, by hand -------------------------------------------

# a 2x2 window: tokens (0,0) (0,1) (1,0) (1,1); the (3x3)-entry table's
# row of (dy, dx) = query less key is (dy + 1) * 3 + dx + 1
REL_INDEX_2 = [[4, 3, 1, 0],
               [5, 4, 2, 1],
               [7, 6, 4, 3],
               [8, 7, 5, 4]]
# a 4x4 map rolled by 1 has nine regions: rows {0, 1}, {2}, {3} by
# columns {0, 1}, {2}, {3}; each 2x2 window's tokens by region
REGIONS_4_2_1 = [[0, 0, 0, 0], [1, 2, 1, 2], [3, 3, 6, 6], [4, 5, 7, 8]]


def test_relative_index_and_mask_against_a_hand_built_case():
    from repro.program.sequence import rel_index, shift_mask

    family = _config()[1]
    assert rel_index(2).tolist() == REL_INDEX_2
    assert family.relative_index(2).tolist() == REL_INDEX_2
    want = np.array([[[0.0 if a == b else -100.0 for b in r] for a in r]
                     for r in REGIONS_4_2_1], np.float32)
    np.testing.assert_array_equal(shift_mask(4, 2, 1), want)
    np.testing.assert_array_equal(family.shift_mask(4, 2, 1, -100.0), want)
    assert shift_mask(4, 2, 0) is None


# -- work counts, graph and weights ---------------------------------------

def test_swin_t_stage_counts():
    """The published size, against a hand count: per image 4,490,566,656
    MAC and 1,824 dynamic (image, window, head) mounts."""
    sizes, family = _config()
    shapes = work.stage_shapes(family, sizes, 2)
    assert len(shapes) == 77
    dyn = [s for s in shapes if s.count > 1]
    assert len(dyn) == 24
    assert sum(s.count for s in dyn) == 2 * 1824
    qk = next(s for s in shapes if s.name == "s0b0_attn.qk")
    assert (qk.m, qk.k, qk.n, qk.count) == (49, 32, 49, 2 * 64 * 3)
    pv = next(s for s in shapes if s.name == "s3b1_attn.pv")
    assert (pv.m, pv.k, pv.n, pv.count) == (49, 49, 32, 2 * 1 * 24)
    merge = next(s for s in shapes if s.name == "s1_merge")
    assert (merge.m, merge.k, merge.n) == (2 * 784, 384, 192)
    per_image = 3136 * 48 * 96 + 768 * 1000          # patch, head
    for c, t, nw, heads, depth in ((96, 3136, 64, 3, 2),
                                   (192, 784, 16, 6, 2),
                                   (384, 196, 4, 12, 6),
                                   (768, 49, 1, 24, 2)):
        block = 12 * t * c * c + 2 * nw * heads * 49 * 49 * 32
        per_image += depth * block + (t * 4 * c * c // 2 if c > 96 else 0)
    assert per_image == 4_490_566_656
    assert sum(s.m * s.k * s.n * s.count for s in shapes) == 2 * per_image


def test_graph_and_weights_match_the_zoo():
    from repro.api import zoo

    sizes, family = _config()
    want = zoo.swin_graph()
    assert family.graph(sizes).layers == want.layers
    ours = jax.eval_shape(functools.partial(family.init, sizes=sizes),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(want.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert ([x.shape for x in jax.tree.leaves(ours)]
            == [x.shape for x in jax.tree.leaves(theirs)])
    assert sum(x.size for x in jax.tree.leaves(ours)) == 28_288_354
    assert sizes["params"] == 28_288_354 and sizes["reduced"] == []


# -- scopes ---------------------------------------------------------------

def test_small_program_is_scoped_on_cpu(small):
    """Every instruction of the small Swin program's entry computation,
    compiled for the CPU, maps to one stage and one phase; the window
    partition and roll sit under ``window`` in ``im2col``, the bias and
    mask under ``relbias`` and the window reverse under ``unwindow`` in
    ``epilogue``, and the merge's space-to-depth under ``merge`` in
    ``im2col``."""
    from repro import api
    from repro.api import HurryConfig

    sizes, family, params, _ = small
    model = api.compile(family.graph(sizes), HurryConfig(**sizes["hurry"]),
                        params=params)
    text = model.compiled_text(jax.ShapeDtypeStruct((2, 32, 32, 3),
                                                    jnp.float32))
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    names = [m.group(1) for m in map(pt._INSTR.match, entry.splitlines())
             if m]
    scopes = pt.scope_map(text)
    assert [n for n in names if n not in scopes] == []
    assert len({scopes[n][0] for n in names}) == len(model.program.stages())
    own = [op for op, _ in pt.parse_hlo(text).values() if op]
    for sub, phase, stage in (("window", "im2col", r"\.probs$|\.ctx$"),
                              ("relbias", "epilogue", r"\.probs$"),
                              ("unwindow", "epilogue", r"\.ctx$"),
                              ("merge", "im2col", r"\.s1_merge$")):
        ops = [op for op in own if f"/{sub}/" in op]
        assert ops, sub
        for op in ops:
            st, ph = pt.scope_of(op)
            assert ph == phase and re.search(stage, st), (sub, op)


# -- the check that decides ``correct``, on a tiny cell -------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The tiny benchmark tree with a tiny Swin cell added, under this
    configuration's own limit."""
    root = make_tree(tmp_path_factory.mktemp("bench"))
    sizes, _ = _config(**TINY, name="tiny-swin")
    (root / "bench" / "configs" / "tiny-swin.json").write_text(
        json.dumps(sizes))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-swin", "source": "test",
                            "file": "bench/configs/tiny-swin.json",
                            "reduced": sorted(TINY), "why": "test"})
    spec["workloads"].append({"name": "tiny-swin.tiny-mix",
                              "config": "tiny-swin", "traffic": "tiny-mix",
                              "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(tree, **kw):
    """One run of the tiny cell.  Its window is long enough that a worker
    loaded by the other tests' processes still finishes requests in it
    (a request takes a fraction of a second here)."""
    cell = harness.find_cell(tree, "tiny-swin.tiny-mix", tree / "bench")
    return harness.run(cell, SEED, 2.0, False, t_process=time.perf_counter(),
                       require_tpu=False, cache=False, **kw)


def test_tiny_cell_is_correct(tree):
    r = _run(tree)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["prob_err"]["limit"] == TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_tiny_cell_control_is_not_correct(tree, control):
    r = _run(tree, control=control)
    assert not r["correct"] and r["attempted"] > 0


@pytest.mark.parametrize("fault", [alter_one_answer, drop_half_the_batch],
                         ids=lambda f: f.__name__)
def test_tiny_cell_fault_is_not_correct(tree, fault):
    r = _run(tree, fault=fault)
    assert not r["correct"] and r["attempted"] > 0


# -- readers --------------------------------------------------------------

def _ctx(sizes, family, sent, class_s, window_s):
    cell = types.SimpleNamespace(family=family, sizes=sizes)
    summary = types.SimpleNamespace(class_s=class_s, window_s=window_s,
                                    busy_s=window_s)
    return harness.Context(cell=cell, summary=summary, sent=sent,
                           buckets=(1, 2, 4), peak={
                               "int8_ops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11})


def test_new_readers_read_as_their_namesakes():
    """Each ``swin_*`` reader is its namesake's ``read``, and on Swin's
    shapes it counts every (image, window, head) mount."""
    sizes, family = _config(**SMALL)
    sent = [harness.Served(2, 0, 0.0, 0.0), harness.Served(3, 0, 0.0, 0.0)]
    ctx = _ctx(sizes, family, sent, {"gemm": 2.0, "epilogue": 1.0,
                                     "glue": 5.0}, 4.0)
    names = ("mfu", "gemm_roofline", "epilogue_roofline", "glue_share")
    read = {m: harness._module(BENCH / "metrics" / f"{m}.py", m).read
            for m in names + tuple(f"swin_{m}" for m in names)}
    for m in names:
        assert read[f"swin_{m}"](ctx) == read[m](ctx), m
    assert read["swin_glue_share"](ctx) == pytest.approx(62.5)
    shapes = work.stage_shapes(family, sizes, 4)
    qk = next(s for s in shapes if s.name == "s0b0_attn.qk")
    assert qk.count == 4 * 4 * 2                     # images x windows x heads
    bound = sum(work.totals(work.stage_shapes(family, sizes, b),
                            ctx.peak)["epilogue_bound_s"] for b in (2, 4))
    assert read["swin_epilogue_roofline"](ctx) == pytest.approx(
        100 * bound / 1.0)
    empty = _ctx(sizes, family, [], {"gemm": 0.0, "epilogue": 0.0,
                                     "glue": 0.0}, 0.0)
    assert all(read[f"swin_{m}"](empty) is None for m in names)
