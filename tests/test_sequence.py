"""Sequence subsystem: transformer attention on the crossbar program stack.

ISSUE 5 acceptance: ``api.compile(zoo.vit_tiny(), cfg).run(x)`` is
bit-exact against the jitted functional-oracle forward under a
clip-free config (both sides jitted — FMA contraction, DESIGN.md §5),
a save→load roundtrip of the same model agrees bit-exactly (npz format
with dynamic stages), and the satellites: the fused epilogue's
softmax survives ±1e4-magnitude logits (max-subtraction), crossbar
attention tracks the ``flash_attention`` reference across a seq-len
sweep within clip-free int8 tolerance.

Also covers: the dynamic-operand GEMM program structure (qk/pv stages,
empty packed placeholders, runtime-sized mounts), a linear/gelu/
layernorm/seqpool MLP net isolated from attention, builder sequence-
mode validation, the fb_epilogue GELU modes vs their unfused oracle,
and the XLA route of the post-norm layer norm and the token mean.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import HurryConfig, NetworkBuilder
from repro.api.serialize import VERSION
from repro.api.zoo import vit_tiny
from repro.core.crossbar import make_crossbar_matmul
from repro.kernels import ref
from repro.kernels.fb_epilogue import (fb_epilogue, layer_norm_ordered,
                                       ordered_sum, softmax_ordered,
                                       token_mean)
from repro.kernels.flash_attention import flash_attention
from repro.program import execute
from repro.program.sequence import (attn_scale, shift_mask, split_qkv_heads,
                                    window_bias)

CLIP_FREE = HurryConfig(array_rows=511)      # DESIGN.md §4 predicate holds


def _attn_graph(dim=64, heads=4, name="attn_net"):
    nb = NetworkBuilder(name, input_seq_dim=dim)
    nb.attention(heads, name="attn")
    return nb.build()


def _oracle(graph, logits=False):
    mm = make_crossbar_matmul(CLIP_FREE.crossbar())
    return jax.jit(lambda p, v: graph.forward(p, v, mm=mm, logits=logits))


# ---------------------------------------------------------------------------
# acceptance: vit_tiny bit-exact + save/load roundtrip
# ---------------------------------------------------------------------------

def test_vit_tiny_bit_exact_and_roundtrip(tmp_path):
    """The compiled packed ViT — patchify conv, dynamic-operand
    attention stages, MLP, pooled head — reproduces the functional
    crossbar oracle bitwise (probs AND logits), and survives a
    save→load roundtrip bit-exactly without recompiling."""
    graph = vit_tiny()
    model = api.compile(graph, CLIP_FREE)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(2))
    probs = model.run(x)
    logits = model.run(x, logits=True)
    np.testing.assert_array_equal(
        np.asarray(probs), np.asarray(_oracle(graph)(model.params, x)))
    np.testing.assert_array_equal(
        np.asarray(logits),
        np.asarray(_oracle(graph, logits=True)(model.params, x)))

    path = model.save(str(tmp_path / "vit.npz"))
    meta_version = VERSION
    assert meta_version == 7
    loaded = api.load(path)
    assert loaded.program.ops == model.program.ops
    assert loaded.program.has_dynamic_stages
    np.testing.assert_array_equal(np.asarray(probs),
                                  np.asarray(loaded.run(x)))
    # layer-norm FB params rode next to the planes: the loaded packed
    # stages carry them (the executor never reads the float pytree)
    assert any(st.ln_g is not None for st in loaded.packed.stages)
    # dynamic stages persisted as empty placeholders
    dyn_idx = [i for i, (g, _) in enumerate(model.program.stages())
               if g.kind == "dyn_gemm"]
    assert dyn_idx and all(loaded.packed.stages[i].w8.size == 0
                           for i in dyn_idx)


def test_seq_input_attention_bit_exact():
    """A token-input single-attention net (runtime seq_len): compiled
    dynamic-operand stages == the oracle's vmapped crossbar mm."""
    graph = _attn_graph()
    model = api.compile(graph, CLIP_FREE, buckets=())
    for seq in (8, 24):        # 24: K-pad path (not a mount multiple)
        x = jax.random.normal(jax.random.PRNGKey(seq), (2, seq, 64))
        np.testing.assert_array_equal(
            np.asarray(model.run(x)),
            np.asarray(_oracle(graph)(model.params, x)))


def test_seq_mlp_bit_exact():
    """linear+gelu / linear+residual+layernorm / seqpool+fc+softmax —
    the non-attention sequence FBs, isolated, bit-exact vs oracle."""
    nb = NetworkBuilder("mlp_net", input_seq_dim=48)
    ln0 = nb.linear(48, name="embed")
    nb.linear(96, name="fc1")
    nb.gelu(name="act")
    nb.linear(48, name="fc2")
    nb.residual(ln0, name="res")
    nb.layernorm(name="ln")
    nb.seqpool(name="pool")
    nb.fc(7, name="head")
    nb.softmax(name="sm")
    graph = nb.build()
    model = api.compile(graph, CLIP_FREE, buckets=())
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 10, 48))
    np.testing.assert_array_equal(
        np.asarray(model.run(x)),
        np.asarray(_oracle(graph)(model.params, x)))
    np.testing.assert_array_equal(
        np.asarray(model.run(x, logits=True)),
        np.asarray(_oracle(graph, logits=True)(model.params, x)))


# ---------------------------------------------------------------------------
# satellite: crossbar attention vs flash_attention across seq lengths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", [16, 64])
def test_crossbar_attention_tracks_flash_reference(seq):
    """Mounting activations as int8 planes quantizes q/k/probs/v, so the
    crossbar attention output tracks the fp32 flash-attention reference
    (same projection weights, non-causal) within clip-free tolerance."""
    dim, heads = 64, 4
    graph = _attn_graph(dim, heads)
    model = api.compile(graph, CLIP_FREE, buckets=())
    x = jax.random.normal(jax.random.PRNGKey(seq), (2, seq, dim))
    y_cb = np.asarray(model.run(x))

    p = model.params["attn"]
    qkv = (x.reshape(-1, dim) @ p["wqkv"] + p["bqkv"]).reshape(2, seq, -1)
    q, k, v = (u.reshape(2, heads, seq, dim // heads).transpose(0, 2, 1, 3)
               for u in split_qkv_heads(qkv, heads))
    ctx = flash_attention(q, k, v, causal=False, interpret=True)
    # flash output is (B, S, H, hd) — already token-major, merge directly
    y_fl = np.asarray(ctx.reshape(2, seq, dim) @ p["wo"] + p["bo"])
    rel = np.linalg.norm(y_cb - y_fl) / np.linalg.norm(y_fl)
    assert rel < 0.12, rel
    corr = np.corrcoef(y_cb.ravel(), y_fl.ravel())[0, 1]
    assert corr > 0.99, corr


# ---------------------------------------------------------------------------
# satellite: softmax FB numerical stability on large-magnitude logits
# ---------------------------------------------------------------------------

def test_softmax_epilogue_stable_on_large_logits():
    """±1e4-range logits must not produce inf/nan: exp(1e4) overflows
    f32, so the fused softmax's max-subtraction is load-bearing."""
    key = jax.random.PRNGKey(0)
    y = jax.random.randint(key, (8, 32), -(1 << 20), 1 << 20,
                           dtype=jnp.int32)
    scale = jnp.array([[1e4 / (1 << 20)]], jnp.float32)   # spans ±1e4
    bias = jnp.zeros((32,), jnp.float32)
    out = np.asarray(fb_epilogue(y, scale, bias, None, softmax=True,
                                 interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out.sum(axis=-1), 1.0, atol=1e-6)
    # and it equals the (jitted, max-subtracted) oracle on those inputs
    oracle = jax.jit(functools.partial(ref.fb_epilogue_ref, softmax=True)
                     )(y, scale, bias, None)
    np.testing.assert_array_equal(out, np.asarray(oracle))


# ---------------------------------------------------------------------------
# new fb_epilogue FB modes vs the unfused oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(act="gelu"),
    dict(act="gelu", post_scale=0.125),
])
@pytest.mark.parametrize("with_res", [False, True])
def test_fb_epilogue_sequence_modes_match_oracle(kw, with_res):
    key = jax.random.PRNGKey(0)
    M, N = 32, 48
    y = jax.random.randint(key, (M, N), -20000, 20000, dtype=jnp.int32)
    scale = jnp.array([[0.0123]], jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(1), (N,), jnp.float32)
    res = (jax.random.normal(jax.random.PRNGKey(2), (M, N), jnp.float32)
           if with_res else None)
    out = fb_epilogue(y, scale, bias, res, interpret=True, **kw)
    oracle = jax.jit(functools.partial(ref.fb_epilogue_ref, **kw)
                     )(y, scale, bias, res)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def _pairwise_np(x):
    """The ordered row sum written apart, in numpy float32: halves added
    pairwise, an odd column carried."""
    x = np.asarray(x, np.float32)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = np.concatenate([x[..., :h] + x[..., h:2 * h], x[..., 2 * h:]],
                           axis=-1)
    return x


@pytest.mark.parametrize("kw", [
    dict(eps=1e-5),
    dict(act="gelu", eps=1e-5),
    dict(eps=1e-5, tokens=16),
    dict(tokens=8),
    dict(eps=1e-6),
    dict(act="gelu", eps=1e-6),
], ids=["layernorm", "gelu_layernorm", "layernorm_mean", "mean",
        "layernorm_eps1e-6", "gelu_layernorm_eps1e-6"])
@pytest.mark.parametrize("with_res", [False, True])
def test_ordered_sequence_tails_match_oracle(kw, with_res):
    """A post-norm layer norm and the token mean run in XLA on the
    epilogue kernel's output (DESIGN.md §9): their sums are the fixed
    pairwise tree (a numpy float32 copy of it gives the same bits), and
    the results are float64 math's to float32 rounding."""
    M, N = 32, 48
    y = jax.random.randint(jax.random.PRNGKey(0), (M, N), -20000, 20000,
                           dtype=jnp.int32)
    scale = jnp.array([[0.0123]], jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(1), (N,), jnp.float32)
    res = (jax.random.normal(jax.random.PRNGKey(2), (M, N), jnp.float32)
           if with_res else None)
    g = 1.0 + jax.random.normal(jax.random.PRNGKey(3), (N,))
    b = jax.random.normal(jax.random.PRNGKey(4), (N,))
    x = np.asarray(fb_epilogue(y, scale, bias, res,
                               act=kw.get("act", "none"), interpret=True))
    got, want = x, x.astype(np.float64)
    if "eps" in kw:
        got = np.asarray(jax.jit(lambda v: layer_norm_ordered(
            v, g, b, kw["eps"]))(got))
        d = want - want.mean(-1, keepdims=True)
        want = (d / np.sqrt((d * d).mean(-1, keepdims=True) + kw["eps"])
                * np.asarray(g, np.float64) + np.asarray(b, np.float64))
        np.testing.assert_array_equal(np.asarray(jax.jit(ordered_sum)(x)),
                                      _pairwise_np(x))
    if "tokens" in kw:
        t = kw["tokens"]
        seq = got.reshape(-1, t, N)
        got = np.asarray(jax.jit(token_mean)(seq))
        np.testing.assert_array_equal(
            got, _pairwise_np(seq.swapaxes(1, 2))[..., 0] / np.float32(t))
        want = want.reshape(-1, t, N).mean(axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", [1, 2, 3, 49, 197])
def test_ordered_sum_over_a_leading_axis_is_the_last_axis_sum(n):
    """``ordered_sum`` over the leading axis of a keys-major array adds the
    same pairs in the same order as over the last axis of the same
    values: bit for bit, jitted, and as the numpy float32 tree."""
    x = jnp.exp(3.0 * jax.random.normal(jax.random.PRNGKey(n), (5, 7, n)))
    last = np.asarray(jax.jit(ordered_sum)(x))[..., 0]
    lead = jax.jit(functools.partial(ordered_sum, axis=0))(
        jnp.transpose(x, (2, 0, 1)))
    assert lead.shape == (1, 5, 7)
    np.testing.assert_array_equal(np.asarray(lead)[0], last)
    np.testing.assert_array_equal(last, _pairwise_np(x)[..., 0])


# name -> (images, heads, grid, window, shift); no window: DeiT-Ti's
# (B*heads, 197, 197) scores
KEYS_MAJOR_TAILS = {"swin_shifted": (2, 3, 14, 7, 3),
                    "swin_unshifted": (2, 3, 14, 7, 0),
                    "deit": (2, 3, 0, 0, 0)}


@pytest.mark.parametrize("case", sorted(KEYS_MAJOR_TAILS))
def test_keys_major_tail_is_softmax_ordered(case):
    """The executor's scores tail (``execute._probs``: keys-major at 49
    keys, on the lanes at DeiT's 197) gives the oracle's probabilities
    bit for bit: a windowed stage's scale, relative bias and shift mask,
    then ``softmax_ordered`` over the last axis, both jitted."""
    b, heads, grid, window, shift = KEYS_MAJOR_TAILS[case]
    t = window * window if window else 197
    mounts = b * heads * ((grid // window) ** 2 if window else 1)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    scores = 4.0 * jax.random.normal(keys[0], (mounts, t, t))
    bias = (0.1 * jax.random.normal(keys[1], (heads, t, t)) if window
            else None)
    gemm = types.SimpleNamespace(window=window, shift=shift, in_hw=grid,
                                 post_scale=attn_scale(32))
    got = jax.jit(lambda s: execute._probs(
        gemm, types.SimpleNamespace(rel_bias=bias), s, b))(scores)

    def oracle(s):
        if window:
            s = window_bias((s * gemm.post_scale).reshape(b, -1, t, t),
                            bias, shift_mask(grid, window, shift)
                            ).reshape(s.shape)
        return softmax_ordered(s)

    assert got.shape == scores.shape
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(oracle)(scores)))


# ---------------------------------------------------------------------------
# program structure: dynamic-operand stages
# ---------------------------------------------------------------------------

def test_dynamic_stage_structure_and_placeholders():
    graph = _attn_graph(dim=64, heads=4)
    model = api.compile(graph, CLIP_FREE)
    program = model.program
    assert program.has_dynamic_stages
    dyn = [op for op in program.ops if op.kind == "dyn_gemm"]
    assert [op.dyn for op in dyn] == ["qk", "pv"]
    qk, pv = dyn
    # scores: contraction is the (static) head dim, softmax FB fused
    # with the 1/sqrt(hd) logit scale below a softmax row reservation
    assert qk.tile_rows == 16 and qk.post_scale == 0.25
    stages = program.stages()
    qk_posts = next(p for g, p in stages if g.name == qk.name)
    assert [o.kind for o in qk_posts] == ["softmax"]
    # context: contraction is the RUNTIME seq_len — only a row budget
    # exists at compile time, and no mount rounds can be enumerated
    assert pv.tile_rows < CLIP_FREE.array_rows
    assert pv.mount_rounds == () and qk.mount_rounds == ()
    assert pv.dyn_src == qk.src       # V mounts from the qkv buffer
    # dynamic stages pack as empty placeholders (no compile-time weights)
    for (g, _), st in zip(stages, model.packed.stages):
        assert (st.w8.size == 0) == (g.kind == "dyn_gemm")
    # the attention layer's own name is the projection stage's buffer,
    # so graph-level wiring (residuals) resolves unchanged
    assert program.logits == "attn" and program.output == "attn"


def test_seq_warmup_shape_and_buckets():
    graph = _attn_graph(dim=32, heads=2)
    model = api.compile(graph, CLIP_FREE)
    assert model.program.input_shape(2, seq_len=8) == (2, 8, 32)
    model.warmup(2, seq_len=8)
    # bucketing pads the batch axis by edge replication: bit-exact for
    # sequence inputs too (per-(batch, head) stats of duplicated rows)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 32))
    exact = api.compile(graph, CLIP_FREE, params=model.params, buckets=())
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(exact.run(x)))


def test_single_token_sequence_runs():
    """T=1 prefill (one patch / one token): seq-mean over a single row
    is well-defined and the whole pipeline stays bit-exact."""
    from repro.api.zoo import vit_tiny_graph
    graph = vit_tiny_graph(depth=1, dim=8, heads=1, input_hw=4, patch=4)
    model = api.compile(graph, CLIP_FREE, buckets=())
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(2))
    np.testing.assert_array_equal(
        np.asarray(model.run(x)),
        np.asarray(_oracle(graph)(model.params, x)))


def test_compile_rejects_seq_fbs_on_cnn_head():
    """Raw LayerSpec lists bypass the builder: the compiler still names
    the offending group instead of tripping an assert."""
    from repro.core.workload import LayerSpec
    from repro.program import compile_network
    bad = [LayerSpec("c", "conv", in_ch=3, out_ch=8, ksize=3, stride=1,
                     padding=1, in_hw=8, out_hw=8),
           LayerSpec("g", "gelu", features_out=8)]
    with pytest.raises(ValueError, match="head c is a conv"):
        compile_network(bad, cfg=CLIP_FREE.crossbar())


def test_simulate_rejects_sequence_graphs():
    model = api.compile(_attn_graph(), CLIP_FREE)
    with pytest.raises(ValueError, match="sequence workloads"):
        model.simulate()


# ---------------------------------------------------------------------------
# builder sequence-mode validation
# ---------------------------------------------------------------------------

def test_builder_sequence_validation():
    with pytest.raises(ValueError, match="input_hw.*input_seq_dim"):
        NetworkBuilder("bad")
    with pytest.raises(ValueError, match="input_hw.*input_seq_dim"):
        NetworkBuilder("bad", input_hw=8, input_ch=3, input_seq_dim=16)
    # half-specified image input is rejected, not silently 0-channel
    with pytest.raises(ValueError, match="BOTH input_hw and input_ch"):
        NetworkBuilder("bad", input_hw=32)
    with pytest.raises(ValueError, match="BOTH input_hw and input_ch"):
        NetworkBuilder("bad", input_ch=3)
    # sequence FBs cannot fuse onto a conv/fc-headed group — rejected at
    # build time with the layer named, not by a compiler assert
    nbc = NetworkBuilder("bad_conv", input_hw=8, input_ch=3)
    nbc.conv(16, name="c1")
    with pytest.raises(ValueError, match="'g1'.*conv"):
        nbc.gelu(name="g1")
    # a layer norm may follow a patchify conv (the patch norm), not an fc
    nbf = NetworkBuilder("bad_fc", input_hw=8, input_ch=3)
    nbf.fc(16, name="f1")
    with pytest.raises(ValueError, match="'ln1'.*fc"):
        nbf.layernorm(name="ln1")
    nb = NetworkBuilder("bad", input_seq_dim=16)
    with pytest.raises(ValueError, match="'ln0'.*precedes any GEMM"):
        nb.layernorm(name="ln0")
    with pytest.raises(ValueError, match="heads do not divide"):
        nb.attention(5, name="a")          # 5 does not divide 16
    nb.attention(4, name="a")
    # spatial ops reject token buffers with the layer named
    with pytest.raises(ValueError, match="p1.*spatial"):
        nb.maxpool(name="p1")
    # canonical sequence chain order: layernorm cannot precede residual
    nb2 = NetworkBuilder("bad2", input_seq_dim=16)
    nb2.attention(4, name="a")
    nb2.layernorm(name="ln")
    nb2.residual("input", name="res")
    with pytest.raises(ValueError, match="res.*canonical"):
        nb2.build()


def test_builder_spatial_residual_rasterizes_into_tokens():
    """A ViT block's first residual adds the patchify conv's spatial
    buffer to the attention's token buffer: shapes canonicalize."""
    nb = NetworkBuilder("vit_head", input_hw=8, input_ch=3)
    entry = nb.conv(16, k=4, stride=4, padding=0, name="patch")
    nb.attention(4, name="attn")
    nb.residual(entry, name="res")      # (2, 2, 16) spatial == 4 tokens
    ln = nb.layernorm(name="ln")
    g = nb.build()
    assert g.layers[-1].name == ln
    # mismatched dims still rejected, with the source shape shown
    nb2 = NetworkBuilder("vit_bad", input_hw=8, input_ch=3)
    nb2.conv(16, k=4, stride=4, padding=0, name="patch")
    proj = nb2.conv(8, k=1, padding=0, name="small", input_from="patch")
    nb2.attention(4, name="attn", input_from="patch")
    with pytest.raises(ValueError, match="shape"):
        nb2.residual(proj, name="res")
