"""Work counts of both configurations against hand counts, and the
benchmark's graphs and weights against the program's zoo."""

import functools
import json

import jax
import pytest

from bench import work
from bench.harness import _module
from bench.tests.tiny import BENCH

RESNET_MACS = {   # per image: M*K*N of every stage, counted by hand
    "conv0": 1024 * 27 * 64,
    **{f"s0b{b}_conv{c}": 1024 * 576 * 64 for b in (0, 1) for c in (1, 2)},
    "s1b0_proj": 256 * 64 * 128, "s1b0_conv1": 256 * 576 * 128,
    "s2b0_proj": 64 * 128 * 256, "s2b0_conv1": 64 * 1152 * 256,
    "s3b0_proj": 16 * 256 * 512, "s3b0_conv1": 16 * 2304 * 512,
    **{f"s{s}b{b}_conv{c}": m * k * n
       for s, (m, k, n) in ((1, (256, 1152, 128)), (2, (64, 2304, 256)),
                            (3, (16, 4608, 512)))
       for b, c in ((0, 2), (1, 1), (1, 2))},
    "fc": 512 * 10,
}
DEIT_BLOCK_MACS = {   # per image and block: 196 tokens, width 192, 3 heads
    "qkv": 196 * 192 * 576, "qk": 3 * 196 * 64 * 196,
    "pv": 3 * 196 * 196 * 64, "out": 196 * 192 * 192,
    "fc1": 196 * 192 * 768, "fc2": 196 * 768 * 192,
}


def _config(name):
    sizes = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return sizes, _module(BENCH / "models" / f"{sizes['family']}.py",
                          f"bench_family_{sizes['family']}")


def test_resnet18_stage_counts():
    sizes, family = _config("resnet18-cifar10")
    shapes = work.stage_shapes(family, sizes, 1)
    assert len(shapes) == 21
    got = {s.name: s.m * s.k * s.n * s.count for s in shapes}
    assert got == RESNET_MACS
    assert sum(got.values()) == 555_422_720          # 0.555 GMAC per image
    ops4 = sum(work.gemm_ops(s) for s in work.stage_shapes(family, sizes, 4))
    assert ops4 == 4 * 2 * 555_422_720


def test_deit_tiny_stage_counts():
    sizes, family = _config("deit-tiny-224")
    shapes = work.stage_shapes(family, sizes, 2)
    assert len(shapes) == 74
    assert sum(1 for s in shapes if s.count > 1) == 24   # dynamic stages
    qk = next(s for s in shapes if s.name == "b0_attn.qk")
    assert (qk.m, qk.k, qk.n, qk.count) == (196, 64, 196, 6)
    macs = sum(s.m * s.k * s.n * s.count for s in shapes)
    per_image = (196 * 768 * 192 + 12 * sum(DEIT_BLOCK_MACS.values())
                 + 192 * 1000)
    assert per_image == 1_246_563_840                  # 1.247 GMAC
    assert macs == 2 * per_image


def test_byte_counts_by_hand():
    sizes, family = _config("resnet18-cifar10")
    s = {x.name: x for x in work.stage_shapes(family, sizes, 1)}
    assert work.gemm_bytes(s["conv0"]) == 1024 * 27 + 27 * 64 + 4 * 1024 * 64
    assert work.epilogue_bytes(s["conv0"]) == 2 * 4 * 1024 * 64
    # residual read; the last block's conv2 also average-pools to one row
    assert work.epilogue_bytes(s["s0b0_conv2"]) == 3 * 4 * 1024 * 64
    assert work.epilogue_bytes(s["s3b1_conv2"]) == (2 * 4 * 16 * 512
                                                    + 4 * 1 * 512)
    sizes, family = _config("deit-tiny-224")
    s = {x.name: x for x in work.stage_shapes(family, sizes, 1)}
    # P.V per (image, head): int8 probabilities and V, int32 out
    assert work.gemm_bytes(s["b0_attn.pv"]) == 3 * (196 * 196 + 196 * 64
                                                    + 4 * 196 * 64)
    # the last fc2 adds a residual, normalizes and averages 196 tokens
    assert work.epilogue_bytes(s["b11_fc2"]) == (2 * 4 * 196 * 192
                                                 + 4 * 1 * 192)


def test_roofline_bound_takes_the_longer():
    assert work.bound_s(393e12, 0, 393e12, 819e9) == 1.0
    assert work.bound_s(0, 819e9, 393e12, 819e9) == 1.0


@pytest.mark.parametrize("name,zoo_kw,n_params", [
    ("resnet18-cifar10", None, 11_169_162),
    ("deit-tiny-224", dict(depth=12, dim=192, heads=3, mlp_ratio=4,
                           patch=16, input_hw=224, classes=1000), 5_679_016),
])
def test_graph_and_weights_match_the_zoo(name, zoo_kw, n_params):
    from repro.api import zoo

    sizes, family = _config(name)
    want = (zoo.resnet18_graph() if zoo_kw is None
            else zoo.vit_tiny_graph(**zoo_kw))
    assert family.graph(sizes).layers == want.layers
    ours = jax.eval_shape(functools.partial(family.init, sizes=sizes),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(want.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert ([x.shape for x in jax.tree.leaves(ours)]
            == [x.shape for x in jax.tree.leaves(theirs)])
    assert sum(x.size for x in jax.tree.leaves(ours)) == n_params
    assert sizes["params"] == n_params
