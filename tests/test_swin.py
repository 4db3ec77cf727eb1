"""Windowed, hierarchical transformers (Swin-T) on the crossbar program stack.

``zoo.swin_graph`` at a small size (32x32 images in 4x4 patches: an 8x8
token grid, window 4, depths (2, 2), widths 16/32, heads 2/4, 10
classes) compiled clip-free: the program against the jitted functional
oracle, and through a save -> load round trip of format version 7;
``NetworkBuilder``'s checks of windows, shifts and merges, and the grid it
tracks through them; the lowering (window geometry on both
dynamic stages, the merge stage, the patch norm on the patch stage, the
mean pool read by the head); and the window helpers the oracle and the
executor share.
"""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import HurryConfig, NetworkBuilder
from repro.api.zoo import swin_graph
from repro.core.crossbar import make_crossbar_matmul
from repro.program import sequence

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

CLIP_FREE = HurryConfig(array_rows=511)
SMALL = dict(depths=(2, 2), heads=(2, 4), dim=16, window=4, input_hw=32,
             classes=10)


def _model(**sizes):
    graph = swin_graph(**sizes)
    # random biases, gains and bias tables, so every operand is exercised
    model = api.compile(graph, CLIP_FREE,
                        params=smoke.random_params(graph, 0))
    x = jax.random.normal(jax.random.PRNGKey(2), graph.input_shape(3))
    return model, x


@pytest.fixture(scope="module")
def swin():
    return _model(**SMALL)


def _oracle(model, x, logits=False):
    mm = make_crossbar_matmul(CLIP_FREE.crossbar())
    return jax.jit(lambda p, v: model.graph.forward(
        p, v, mm=mm, logits=logits))(model.params, x)


def test_small_swin_bit_exact_against_the_oracle():
    """One stage (shifted windows, the patch norm, the final layer norm
    and the mean pool): the program is the jitted oracle, bit for bit."""
    model, x = _model(**dict(SMALL, depths=(2,), heads=(2,)))
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(_oracle(model, x, True)))
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(_oracle(model, x)))


def test_small_swin_with_a_merge_against_the_oracle(swin):
    """Two stages joined by patch merging: within a few ulp of the
    oracle on the CPU.  The merge has no bias, so in the oracle's one
    fused graph its dequantizing multiply meets the next block's
    residual add, which XLA's CPU backend contracts into one FMA; the
    program's kernel boundary between the two rounds the product first
    (DESIGN.md §9).  Every stage before the merge is bit-exact."""
    model, x = swin
    got = np.asarray(model.run(x, logits=True))
    want = np.asarray(_oracle(model, x, True))
    assert smoke.ulp_error(got, want) <= smoke.FB_TAIL_ULP
    assert (got.argmax(-1) == want.argmax(-1)).all()
    oracle = smoke.oracle_buffers(model.graph, CLIP_FREE, model.params, x)
    before = []
    for name, value, _ in smoke.run_stages(model, x):
        if name == "s1_merge":
            break
        before.append(np.array_equal(np.asarray(value),
                                     np.asarray(oracle[smoke.oracle_name(
                                         name)])))
    assert len(before) == 13 and all(before)


def test_swin_save_load_v7_bit_identical(swin, tmp_path):
    model, x = swin
    path = model.save(str(tmp_path / "swin.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        bias = [z[f"wrb{i}"] for i in meta["bias_stages"]]
    assert meta["version"] == 7
    stages = model.program.stages()
    assert meta["bias_stages"] == [i for i, (g, _) in enumerate(stages)
                                   if g.dyn == "qk"]
    assert [b.shape for b in bias] == [(2, 16, 16)] * 2 + [(4, 16, 16)] * 2
    loaded = api.load(path)
    assert loaded.program.ops == model.program.ops
    assert loaded.graph == model.graph
    for a, b in zip(jax.tree.leaves(loaded.packed),
                    jax.tree.leaves(model.packed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(loaded.run(x)))


def test_swin_lowering(swin):
    """Both dynamic stages of a windowed attention carry its geometry;
    the Q·Kᵀ stage names the bias table, which ``pack`` gathers; the
    merge is a static stage without a bias; the patch norm is the patch
    stage's post-op; the head reads the mean pool."""
    model, _ = swin
    stages = model.program.stages()
    assert len(stages) == 1 + 12 * 2 + 1 + 1
    dyn = [(g, st) for (g, _), st in zip(stages, model.packed.stages)
           if g.kind == "dyn_gemm"]
    assert [(g.window, g.in_hw, g.shift) for g, _ in dyn] == [
        (4, 8, 0)] * 2 + [(4, 8, 2)] * 2 + [(4, 4, 0)] * 4
    for g, st in dyn:
        assert (st.rel_bias is not None) == (g.dyn == "qk")
        if g.dyn == "qk":
            table = model.params[g.param]["relb"]
            np.testing.assert_array_equal(
                st.rel_bias, sequence.rel_bias(table, 4))
    merge = next(g for g, _ in stages if g.merge)
    assert (merge.in_hw, merge.b_key, merge.prenorm) == (8, "",
                                                         "s1_merge_norm")
    st = model.packed.stages[stages.index(next(s for s in stages
                                               if s[0] is merge))]
    assert not np.asarray(st.bias).any()
    patch, posts = stages[0]
    assert [p.kind for p in posts] == ["layernorm"]
    head, _ = stages[-1]
    assert (head.name, head.select, head.src) == ("head", "mean", "norm")


def test_builder_tracks_the_grid_through_merges():
    nb = NetworkBuilder("grid", input_hw=32, input_ch=3)
    nb.conv(16, k=4, stride=4, padding=0, name="patch")
    nb.layernorm(name="pn")
    assert nb._grid("pn") == 8
    nb.attention(2, window=4, shift=2, name="a")
    nb.layernorm(pre=True, name="mn")
    nb.linear(32, merge=True, bias=False, name="m")
    assert nb._grid("m") == 4 and nb._shapes["m"] == ("seq", 16, 32)
    nb.attention(4, window=4, shift=2, name="b")       # one window
    nb.layernorm(pre=True, name="mn2")
    nb.linear(64, merge=True, name="m2")
    assert nb._grid("m2") == 2
    g = nb.build()
    a, m, b, m2 = (l for l in g.layers if l.kind in ("attention", "linear"))
    assert (a.window, a.shift, a.in_hw) == (4, 2, 8)
    assert (m.features_in, m.features_out, m.in_hw, m.bias) == (64, 32, 8,
                                                                 False)
    assert (b.window, b.shift, b.in_hw) == (4, 0, 4)   # no shift published
    assert (m2.features_in, m2.in_hw, m2.bias) == (128, 4, True)
    assert m.prenorm == "mn"
    params = jax.eval_shape(g.init_params, jax.random.PRNGKey(0))
    assert params["mn"]["g"].shape == (64,)
    assert set(params["m"]) == {"w"} and set(params["m2"]) == {"w", "b"}
    assert params["a"]["relb"].shape == (49, 2)


def test_builder_rejects_bad_windows_shifts_and_merges():
    def patched(hw=32, patch=4, embed=False):
        nb = NetworkBuilder("bad", input_hw=hw, input_ch=3)
        nb.conv(16, k=patch, stride=patch, padding=0, name="patch")
        nb.embed(name="e") if embed else nb.layernorm(name="pn")
        return nb

    with pytest.raises(ValueError, match="3x3 window does not tile the 8x8"):
        patched().attention(2, window=3, name="a")
    with pytest.raises(ValueError, match=r"shift 4 is not in \[0, 4\)"):
        patched().attention(2, window=4, shift=4, name="a")
    with pytest.raises(ValueError, match="shift -1"):
        patched().attention(2, window=4, shift=-1, name="a")
    with pytest.raises(ValueError, match="a: windowed.*a class token"):
        patched(embed=True).attention(2, window=4, name="a")
    with pytest.raises(ValueError, match="a shift needs a window"):
        patched().attention(2, shift=2, name="a")
    with pytest.raises(ValueError, match="even square token grid.*5x5"):
        patched(hw=20).linear(32, merge=True, name="m")
    seq = NetworkBuilder("seq", input_seq_dim=16)
    with pytest.raises(ValueError, match="square token grid"):
        seq.attention(2, window=4, name="a")
    with pytest.raises(ValueError, match="m: patch merging.*none"):
        NetworkBuilder("seq", input_seq_dim=16).linear(16, merge=True,
                                                       name="m")


def test_window_helpers_partition_and_merge_exactly():
    """``window_merge`` undoes ``window_qkv_heads``'s roll, partition and
    head split (token t of window w lands back at its raster place), and
    ``space_to_depth`` takes the 2x2 neighbours in x0..x3 order."""
    b, grid, win, heads, d = 2, 8, 4, 2, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (b, grid * grid, 3 * d))
    for shift in (0, 2):
        q, k, v = sequence.window_qkv_heads(x, heads, grid, win, shift)
        assert q.shape == (b * 4 * heads, win * win, d // heads)
        for i, u in enumerate((q, k, v)):
            back = sequence.window_merge(u, heads, grid, win, shift)
            np.testing.assert_array_equal(back, x[..., i * d:(i + 1) * d])
        # window 0 of the rolled map starts at raster token (shift, shift)
        first = x[0, shift * grid + shift, :d // heads]
        np.testing.assert_array_equal(q[0, 0], first)
    m = jnp.arange(2 * 4 * 4 * 1, dtype=jnp.float32).reshape(2, 16, 1)
    s2d = sequence.space_to_depth(m, 4)
    # token (0, 0) of image 0: map positions (0,0) (1,0) (0,1) (1,1)
    assert s2d[0, 0].tolist() == [0.0, 4.0, 1.0, 5.0]
    assert s2d.shape == (2, 4, 4)
