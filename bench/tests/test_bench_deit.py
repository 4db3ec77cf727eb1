"""DeiT-Ti (``vit_prenorm``) in the benchmark, on the CPU.

At a small size (32x32 images in 8x8 patches: 17 tokens with the class
token, width 64, 2 heads, depth 2, 10 classes) on seeded weights: the
program against the plain reference, within the configuration's own
``prob_err`` limit, and the lower-precision controls and four wrong
models (post-norm blocks, no position table, a mean-pooled head, tanh
GELU) outside it; the check that decides ``correct`` on a tiny cell; the
work counts of the published size against a hand count; the graph and
weights against the zoo's; the program's scopes; and the three new
readers on a synthetic summary.
"""

import dataclasses
import functools
import json
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

SMALL = dict(input_hw=32, patch=8, dim=64, depth=2, heads=2, classes=10)
SEED = 2**33 + 29


def _config(**cut):
    sizes = json.loads((BENCH / "configs" / "deit-ti-224.json").read_text())
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


def test_program_is_within_the_limit(small):
    err = check.prob_err(_program(small), _reference(small))
    assert err <= TOL, err


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_outside_the_limit(small, control):
    err = check.prob_err(_reference(small, CONTROLS[control]),
                         _reference(small))
    assert err > TOL, err


def _graph(sizes, *, post_norm=False, pool="cls", approx="erf"):
    """The family's graph, or a wrong model: post-norm blocks (each
    layer norm after its residual), a mean-pooled head, tanh GELU."""
    from repro.api import NetworkBuilder

    dim, eps = sizes["dim"], sizes["ln_eps"]
    nb = NetworkBuilder("wrong", input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    nb.conv(dim, k=sizes["patch"], stride=sizes["patch"], padding=0,
            name="patch")
    entry = nb.embed(name="embed")
    for i in range(sizes["depth"]):
        if not post_norm:
            nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln1")
        nb.attention(sizes["heads"], name=f"b{i}_attn")
        r1 = nb.residual(entry, name=f"b{i}_res1")
        if post_norm:
            r1 = nb.layernorm(eps=eps, name=f"b{i}_ln1")
        else:
            nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln2")
        nb.linear(dim * sizes["mlp_ratio"], name=f"b{i}_fc1")
        nb.gelu(approx=approx, name=f"b{i}_gelu")
        nb.linear(dim, name=f"b{i}_fc2")
        entry = nb.residual(r1, name=f"b{i}_res2")
        if post_norm:
            entry = nb.layernorm(eps=eps, name=f"b{i}_ln2")
    nb.seqpool(mode=pool, name="pool")
    nb.layernorm(pre=True, eps=eps, name="norm")
    nb.fc(sizes["classes"], name="head")
    nb.softmax(name="softmax")
    return nb.build()


@pytest.mark.parametrize("fault", ["post_norm", "no_position_table",
                                   "mean_pool", "tanh_gelu"])
def test_wrong_model_is_outside_the_limit(small, fault):
    sizes, _, params, _ = small
    graph = _graph(sizes, post_norm=fault == "post_norm",
                   pool="mean" if fault == "mean_pool" else "cls",
                   approx="tanh" if fault == "tanh_gelu" else "erf")
    if fault == "no_position_table":
        params = dict(params, embed=dict(
            params["embed"], pos=jnp.zeros_like(params["embed"]["pos"])))
    err = check.prob_err(_program(small, graph, params), _reference(small))
    assert err > TOL, err


def test_the_right_model_built_by_hand_is_the_family_graph(small):
    sizes = small[0]
    assert _graph(sizes).layers == small[1].graph(sizes).layers


# -- the check that decides ``correct``, on a tiny cell -------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The tiny benchmark tree with a tiny DeiT cell added, under this
    configuration's own limit."""
    root = make_tree(tmp_path_factory.mktemp("bench"))
    sizes, _ = _config(**{**SMALL, "depth": 1}, name="tiny-deit")
    (root / "bench" / "configs" / "tiny-deit.json").write_text(
        json.dumps(sizes))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-deit", "source": "test",
                            "file": "bench/configs/tiny-deit.json",
                            "reduced": sorted(SMALL), "why": "test"})
    spec["workloads"].append({"name": "tiny-deit.tiny-mix",
                              "config": "tiny-deit", "traffic": "tiny-mix",
                              "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(tree, **kw):
    cell = harness.find_cell(tree, "tiny-deit.tiny-mix", tree / "bench")
    return harness.run(cell, SEED, 0.3, False, t_process=time.perf_counter(),
                       require_tpu=False, cache=False, **kw)


def test_tiny_cell_is_correct(tree):
    r = _run(tree)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["prob_err"]["limit"] == TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_tiny_cell_control_is_not_correct(tree, control):
    assert not _run(tree, control=control)["correct"]


@pytest.mark.parametrize("fault", [alter_one_answer, drop_half_the_batch],
                         ids=lambda f: f.__name__)
def test_tiny_cell_fault_is_not_correct(tree, fault):
    assert not _run(tree, fault=fault)["correct"]


# -- work counts, graph and weights ---------------------------------------

DEIT_BLOCK_MACS = {   # per image and block: 197 tokens, width 192, 3 heads
    "qkv": 197 * 192 * 576, "qk": 3 * 197 * 64 * 197,
    "pv": 3 * 197 * 197 * 64, "out": 197 * 192 * 192,
    "fc1": 197 * 192 * 768, "fc2": 197 * 768 * 192,
}


def test_deit_ti_stage_counts():
    sizes, family = _config()
    shapes = work.stage_shapes(family, sizes, 2)
    assert len(shapes) == 74
    assert sum(1 for s in shapes if s.count > 1) == 24   # dynamic stages
    qk = next(s for s in shapes if s.name == "b0_attn.qk")
    assert (qk.m, qk.k, qk.n, qk.count) == (197, 64, 197, 6)
    head = shapes[-1]
    assert (head.name, head.m, head.k, head.n) == ("head", 2, 192, 1000)
    macs = sum(s.m * s.k * s.n * s.count for s in shapes)
    per_image = (196 * 768 * 192 + 12 * sum(DEIT_BLOCK_MACS.values())
                 + 192 * 1000)
    assert per_image == 1_253_683_200                  # 1.254 GMAC
    assert macs == 2 * per_image


def test_graph_and_weights_match_the_zoo():
    from repro.api import zoo

    sizes, family = _config()
    want = zoo.deit_graph(depth=12, dim=192, heads=3, mlp_ratio=4,
                          patch=16, input_hw=224, classes=1000, eps=1e-6)
    assert family.graph(sizes).layers == want.layers
    ours = jax.eval_shape(functools.partial(family.init, sizes=sizes),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(want.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert ([x.shape for x in jax.tree.leaves(ours)]
            == [x.shape for x in jax.tree.leaves(theirs)])
    assert sum(x.size for x in jax.tree.leaves(ours)) == 5_717_416
    assert sizes["params"] == 5_717_416 and sizes["reduced"] == []


# -- scopes and readers ---------------------------------------------------

def test_small_program_is_scoped_on_cpu(small):
    """Every instruction of the small DeiT program's entry computation,
    compiled for the CPU, maps to one stage and one phase; the pre-norms
    sit in ``quantize`` phases under a ``prenorm`` scope and the embed
    in the patch stage's ``epilogue`` under ``embed``."""
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
    # attention's dynamic stages carry every phase too
    assert {scopes[n][1] for n in names
            if scopes[n][0].endswith(".probs")} >= {"quantize", "mount",
                                                     "gemm", "epilogue"}
    own = [op for op, _ in pt.parse_hlo(text).values() if op]
    pre = [op for op in own if "/prenorm/" in op]
    emb = [op for op in own if "/embed/" in op]
    assert pre and all(pt.scope_of(op)[1] == "quantize" for op in pre)
    assert emb and all(pt.scope_of(op) == ("s00.embed", "epilogue")
                       for op in emb)


def _ctx(sizes, family, sent, class_s, window_s):
    cell = types.SimpleNamespace(family=family, sizes=sizes)
    summary = types.SimpleNamespace(class_s=class_s, window_s=window_s,
                                    busy_s=window_s)
    return harness.Context(cell=cell, summary=summary, sent=sent,
                           buckets=(1, 2, 4), peak={
                               "int8_ops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11})


def test_new_readers_on_a_synthetic_summary():
    sizes, family = _config(**SMALL)
    sent = [harness.Served(2, 0, 0.0, 0.0), harness.Served(3, 0, 0.0, 0.0)]
    ctx = _ctx(sizes, family, sent, {"gemm": 2.0, "epilogue": 1.0,
                                     "glue": 5.0}, 4.0)
    read = {m: harness._module(BENCH / "metrics" / f"{m}.py", m).read
            for m in ("vit_mfu", "vit_gemm_roofline", "vit_glue_share")}
    assert read["vit_glue_share"](ctx) == pytest.approx(62.5)
    one = work.totals(work.stage_shapes(family, sizes, 1), ctx.peak)
    assert read["vit_mfu"](ctx) == pytest.approx(
        100 * 5 * one["ops"] / 4.0 / 1e12)
    bound = sum(work.totals(work.stage_shapes(family, sizes, b),
                            ctx.peak)["gemm_bound_s"] for b in (2, 4))
    assert read["vit_gemm_roofline"](ctx) == pytest.approx(100 * bound / 2)
    empty = _ctx(sizes, family, [], {"gemm": 0.0, "epilogue": 0.0,
                                     "glue": 0.0}, 0.0)
    assert all(f(empty) is None for f in read.values())
    assert dataclasses.is_dataclass(ctx)
