"""Pre-norm transformer blocks on the crossbar program stack (DeiT-Ti).

``zoo.deit_graph`` at a small size (32x32 images in 8x8 patches: 16
patch tokens and the class token, width 64, 2 heads, depth 2, 10
classes) compiled clip-free: the program against the jitted functional
oracle, through every stage; its structure (the pre-norms
on the stages they feed, the class token read by the head, the embed on
the patch stage); a save -> load round trip; the builder's checks of
the new ops; and the float tails the model adds: the exact GELU, which
XLA evaluates after the epilogue kernel, and a layer norm's epsilon.
"""

import dataclasses
import importlib.util
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import HurryConfig, NetworkBuilder
from repro.api.zoo import deit_graph, vit_tiny_graph
from repro.core.crossbar import make_crossbar_matmul
from repro.kernels import ref
from repro.kernels.fb_epilogue import gelu_erf
from repro.program.compile import ProgramOp
from repro.program.execute import stage_outputs

from bench import program_trace as pt

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

CLIP_FREE = HurryConfig(array_rows=511)
SMALL = dict(depth=2, dim=64, heads=2, patch=8, input_hw=32, classes=10)


@pytest.fixture(scope="module")
def deit():
    graph = deit_graph(**SMALL)
    model = api.compile(graph, CLIP_FREE,
                        params=smoke.random_params(graph, 0))
    x = jax.random.normal(jax.random.PRNGKey(1), graph.input_shape(3))
    return model, x


def _oracle(model, x, logits=False):
    mm = make_crossbar_matmul(CLIP_FREE.crossbar())
    return jax.jit(lambda p, v: model.graph.forward(
        p, v, mm=mm, logits=logits))(model.params, x)


# ---------------------------------------------------------------------------
# the program against the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("logits", [False, True])
def test_deit_against_the_oracle(deit, logits):
    """Within ``FB_TAIL_ULP`` normwise of the oracle, and every int8
    operand the same (argmax agrees): XLA's CPU backend contracts a
    multiply and an add into one FMA where a program's fusions let it,
    so the program's and the oracle's float tails (the ordered row
    sums) may round an ulp apart here (DESIGN.md §5, §9)."""
    model, x = deit
    got = np.asarray(model.run(x, logits=logits))
    want = np.asarray(_oracle(model, x, logits))
    assert smoke.ulp_error(got, want) <= smoke.FB_TAIL_ULP
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def test_deit_every_stage_equals_the_oracle(deit):
    """Every stage buffer equals the oracle's, or the first that departs
    is a float tail within ``FB_TAIL_ULP`` (with every stage buffer
    returned, XLA on the CPU fuses the softmax's ``exp`` apart from the
    oracle's); with each stage fed the oracle's inputs, every int32 GEMM
    equals an XLA integer dot, and every float tail the XLA reference
    epilogue within ``FB_TAIL_ULP``."""
    model, x = deit
    oracle = smoke.oracle_buffers(model.graph, CLIP_FREE, model.params, x)
    div = smoke.first_divergence(model, x, oracle)
    assert div is None or div[2] <= smoke.FB_TAIL_ULP, div
    st = smoke.stage_check(model, x, oracle)
    assert st["bad_gemm"] == []
    assert st["tail"] <= st["bound"]
    assert st["oracle"] <= smoke.FB_TAIL_ULP


def test_deit_program_structure(deit):
    model, _ = deit
    stages = model.program.stages()
    assert len(stages) == 1 + 6 * SMALL["depth"] + 1
    by_dst = {(posts[-1].dst if posts else g.dst): (g, posts)
              for g, posts in stages}
    patch, posts = by_dst["embed"]
    assert patch.is_conv and [p.kind for p in posts] == ["embed"]
    # each pre-norm rides on the stage that reads the normed input
    qkv, _ = by_dst["b0_attn@qkv"]
    assert (qkv.prenorm, qkv.eps) == ("b0_ln1", 1e-6)
    fc1, posts = by_dst["b0_gelu"]
    assert fc1.prenorm == "b0_ln2" and posts[0].approx == "erf"
    out, _ = by_dst["b0_res1"]
    assert out.prenorm == "" and out.src == "b0_attn@ctx"
    # the head reads the class token of the last block's output; the
    # pool emits no op and no layer norm is a buffer
    head, _ = by_dst["softmax"]
    last = f"b{SMALL['depth'] - 1}_res2"
    assert (head.src, head.select, head.prenorm) == (last, "cls", "norm")
    names = {op.dst for op in model.program.ops}
    assert not names & {"pool", "norm", "b0_ln1", "b0_ln2"}
    assert [st.pre_g is not None for st in model.packed.stages] == [
        bool(g.prenorm) for g, _ in stages]


def test_deit_residuals_read_the_un_normed_stream(deit):
    """The oracle's residual stream is the un-normed x: the attention's
    residual source is the embed output, not its layer norm."""
    model, x = deit
    bufs = smoke.oracle_buffers(model.graph, CLIP_FREE, model.params, x)
    g = model.graph
    res1 = next(l for l in g.layers if l.name == "b0_res1")
    assert res1.residual_from == "embed"
    np.testing.assert_array_equal(
        np.asarray(bufs["b0_res1"]),
        np.asarray(bufs["b0_attn"]) + np.asarray(bufs["embed"]))


def test_deit_zoo_sizes_and_params():
    """The zoo's defaults are DeiT-Ti at 224x224: 197 tokens of 192,
    3 heads, MLP 768, 1000 classes, 5,717,416 parameters."""
    g = deit_graph()
    assert g.input_shape(2) == (2, 224, 224, 3)
    embed = next(l for l in g.layers if l.kind == "embed")
    assert embed.in_hw ** 2 + 1 == 197 and embed.features_out == 192
    attn = [l for l in g.layers if l.kind == "attention"]
    assert len(attn) == 12 and {(l.heads, l.features_in) for l in attn} == {
        (3, 192)}
    assert {l.features_out for l in g.layers if l.kind == "linear"} == {
        768, 192}
    assert {l.approx for l in g.layers if l.kind == "gelu"} == {"erf"}
    assert {l.eps for l in g.layers if l.prenorm} == {1e-6}
    shapes = jax.eval_shape(g.init_params, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 5_717_416
    assert shapes["embed"]["pos"].shape == (197, 192)


# ---------------------------------------------------------------------------
# save and load
# ---------------------------------------------------------------------------

def test_deit_save_load_bit_identical(deit, tmp_path):
    model, x = deit
    path = model.save(str(tmp_path / "deit.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
    n = SMALL["depth"]
    assert meta["version"] == 7
    assert meta["embed_stages"] == [0]
    assert len(meta["pre_stages"]) == 2 * n + 1
    loaded = api.load(path)
    assert loaded.program.ops == model.program.ops
    assert loaded.graph == model.graph
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(loaded.run(x)))


def test_version5_file_loads_with_post_norm_defaults(tmp_path):
    """A version-5 file has none of version 6's or 7's fields: its layers
    and ops take the defaults (no pre-norm, epsilon 1e-5, tanh GELU,
    mean pool, global attention, no merge, biases), which are what it
    meant, its mean pool's seqpool op becomes the head's select, and it
    runs bit-identically."""
    graph = vit_tiny_graph(depth=1, dim=32, heads=2, input_hw=8, patch=4)
    model = api.compile(graph, CLIP_FREE, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(2))
    path = model.save(str(tmp_path / "m.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    meta["version"] = 5
    del meta["pre_stages"], meta["embed_stages"], meta["bias_stages"]
    # a version-5 program kept its mean pool as a seqpool op on the stage
    # before the head, which read the pool's buffer
    ops = meta["program"]["ops"]
    head = next(i for i, op in enumerate(ops) if op["select"] == "mean")
    pool = dataclasses.asdict(ProgramOp(
        kind="seqpool", name="pool", src=ops[head]["src"], dst="pool",
        out_ch=ops[head - 1]["out_ch"], seq=True))
    ops[head]["src"] = "pool"
    ops.insert(head, pool)
    for layer in meta["graph"]["layers"]:
        for key in ("prenorm", "eps", "approx", "mode", "window", "shift",
                    "merge", "bias"):
            del layer[key]
    for op in ops:
        for key in ("prenorm", "eps", "approx", "select", "merge",
                    "shift"):
            del op[key]
    old = str(tmp_path / "v5.npz")
    with open(old, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    loaded = api.load(old)
    assert loaded.graph == model.graph
    assert loaded.program.ops == model.program.ops
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(loaded.run(x)))


# ---------------------------------------------------------------------------
# the builder's checks
# ---------------------------------------------------------------------------

def test_builder_rejects_misplaced_new_ops():
    nb = NetworkBuilder("bad", input_hw=8, input_ch=3)
    nb.layernorm(pre=True, name="ln0")
    with pytest.raises(ValueError, match="ln0.*linear, attention or fc"):
        nb.conv(8, name="c")
    nb = NetworkBuilder("bad", input_seq_dim=16)
    nb.linear(16, name="l")
    nb.layernorm(pre=True, name="ln")
    with pytest.raises(ValueError, match="ln.*not gelu"):
        nb.gelu(name="g")
    nb = NetworkBuilder("bad", input_seq_dim=16)
    nb.linear(16, name="l")
    nb.layernorm(pre=True, name="ln")
    with pytest.raises(ValueError, match="no GEMM op after it"):
        nb.build()
    with pytest.raises(ValueError, match="patchify conv"):
        nb = NetworkBuilder("bad", input_seq_dim=16)
        nb.linear(16, name="l")
        nb.embed(name="e")
    nb = NetworkBuilder("bad", input_seq_dim=16)
    nb.linear(16, name="l")
    with pytest.raises(ValueError, match="'mean' or 'cls'"):
        nb.seqpool(mode="first")
    with pytest.raises(ValueError, match="'tanh' or 'erf'"):
        nb.gelu(approx="exact")


def test_class_token_pool_must_feed_a_gemm_head():
    nb = NetworkBuilder("bad", input_hw=8, input_ch=3)
    nb.conv(16, k=4, stride=4, padding=0, name="patch")
    nb.embed(name="embed")
    nb.linear(16, name="l")
    nb.seqpool(mode="cls", name="pool")
    graph = nb.build()
    with pytest.raises(ValueError, match="class-token pool ends the net"):
        api.compile(graph, CLIP_FREE)


def test_pre_norm_fc_on_a_spatial_input_normalizes_the_flat_row():
    """An fc head's pre-norm normalizes the whole flattened input row,
    as the oracle does, bit for bit."""
    nb = NetworkBuilder("prefc", input_hw=4, input_ch=3)
    nb.conv(8, name="c")
    nb.relu(name="r")
    nb.layernorm(pre=True, name="ln")
    nb.fc(5, name="fc")
    graph = nb.build()
    fc = graph.layers[-1]
    assert (fc.prenorm, fc.features_in) == ("ln", 128)
    model = api.compile(graph, CLIP_FREE, params=smoke.random_params(
        graph, 2), buckets=())
    x = jax.random.normal(jax.random.PRNGKey(5), graph.input_shape(2))
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(_oracle(model, x)))


# ---------------------------------------------------------------------------
# the float tails: exact GELU, layer-norm epsilon
# ---------------------------------------------------------------------------

def test_gelu_erf_is_the_exact_gelu():
    """The exact GELU is the reference's expression bit for bit (XLA's
    erf on both sides), and a test can tell it from the tanh form."""
    x = jnp.linspace(-8.0, 8.0, 10_001)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(gelu_erf)(x)),
        np.asarray(jax.jit(lambda v: 0.5 * v * (1.0 + jax.scipy.special.erf(
            v * (1 / np.sqrt(2.0)))))(x)))
    tanh_form = np.asarray(ref.fb_epilogue_ref(
        jnp.asarray(x * 1000, jnp.int32).reshape(1, -1),
        jnp.full((1, 1), 1e-3), jnp.zeros((x.size,)), act="gelu"))
    # the two forms differ by up to ~1e-3
    assert np.abs(tanh_form[0] - np.asarray(gelu_erf(
        jnp.asarray(x * 1000, jnp.int32) * 1e-3))).max() > 1e-4


def test_xla_tails_follow_the_epilogue_kernel(deit):
    """The exact GELU and attention's softmax are XLA ops in their
    stage's ``epilogue`` phase, after the kernel; an erf GELU that
    does not end its FB chain is refused."""
    model, _ = deit
    text = model.compiled_text(jax.ShapeDtypeStruct(
        model.program.input_shape(2), jnp.float32))
    ops = [op for op, _ in pt.parse_hlo(text).values() if op]
    for stage in ("b0_gelu", "b1_gelu"):
        assert any(re.search(rf"/s\d+\.{stage}/epilogue/.*erf", op)
                   for op in ops), stage
    assert any(re.search(r"/s\d+\.b0_attn\.probs/epilogue/.*exp", op)
               for op in ops)
    nb = NetworkBuilder("bad", input_seq_dim=16)
    nb.linear(16, name="l")
    nb.residual("input", name="r")
    nb.gelu(approx="erf", name="g")
    nb.layernorm(name="ln")
    with pytest.raises(ValueError, match="erf GELU ends its FB chain"):
        api.compile(nb.build(), CLIP_FREE)


@pytest.mark.parametrize("eps", [1e-6, 1e-5, 1e-2])
def test_layer_norm_epsilon_reaches_the_kernel(eps):
    """A post-norm layer norm's static ``eps`` reaches the tail that
    normalizes the epilogue kernel's output (XLA's, DESIGN.md §9): the
    program equals the oracle at that epsilon, and departs from the
    default where the rows' variance is small against it."""
    def model(e):
        nb = NetworkBuilder("ln_eps", input_seq_dim=64)
        nb.linear(64, name="l")
        nb.layernorm(eps=e, name="ln")
        return api.compile(nb.build(), CLIP_FREE, seed=0)

    x = 1e-2 * jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
    m = model(eps)
    out = np.asarray(m.run(x))
    np.testing.assert_array_equal(out, np.asarray(_oracle(m, x)))
    default = np.asarray(model(1e-5).run(x))
    assert np.array_equal(out, default) == (eps == 1e-5)


# ---------------------------------------------------------------------------
# sequence buffers held as (B*T, N) rows inside the executor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["deit_ti", "vit_tiny"])
def test_depth2_logits_bit_identical_to_the_oracle(net):
    """DeiT-Ti at 224x224 (197 tokens, off the 8-row tiling) and
    vit_tiny (64 tokens), two blocks each, batch 3: with every sequence
    buffer held as (B*T, N) rows, the pre-norms and head splits on their
    per-image views and Kᵀ and the merged context moved behind their
    barriers, the program's logits and probabilities equal the jitted
    oracle's bit for bit."""
    graph = (deit_graph if net == "deit_ti" else vit_tiny_graph)(depth=2)
    model = api.compile(graph, CLIP_FREE,
                        params=smoke.random_params(graph, 0))
    x = jax.random.normal(jax.random.PRNGKey(1), graph.input_shape(3))
    for logits in (True, False):
        np.testing.assert_array_equal(
            np.asarray(model.run(x, logits=logits)),
            np.asarray(_oracle(model, x, logits)))


def test_stage_outputs_fed_the_oracle_match_it_stage_by_stage(deit):
    """``stage_outputs(feed=...)`` takes the oracle's (B, T, N) buffers
    and yields every stage's value in the oracle's shape: each stage,
    fed the oracle's inputs, lands within ``FB_TAIL_ULP`` of the
    oracle's buffer (softmax tails within their ``tail_bound``), and
    its int32 GEMM equals an XLA integer dot's."""
    model, x = deit
    oracle = smoke.oracle_buffers(model.graph, CLIP_FREE, model.params, x)
    feed = {name.replace(".", "@"): v for name, v in oracle.items()}
    got = smoke.run_stages(model, x, feed=feed)
    want = smoke.run_stages(model, x, feed=feed, kernels=smoke.Kernels(
        smoke.xla_gemm, smoke.xla_epilogue))
    stages = model.program.stages()
    assert [name for name, _, _ in got] == [
        posts[-1].dst if posts else g.dst for g, posts in stages]
    for (name, value, acc), (_, _, ref_acc), (_, posts) in zip(
            got, want, stages):
        buf = oracle[smoke.oracle_name(name)]
        assert value.shape == buf.shape, name
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(ref_acc))
        softmax = any(op.kind == "softmax" for op in posts)
        bound = smoke.tail_bound(buf) if softmax else smoke.FB_TAIL_ULP
        assert smoke.ulp_error(value, buf) <= bound, name


@pytest.mark.parametrize("batch", [3, 4])
def test_class_token_select_reads_row_b_times_t(deit, batch):
    """The head reads image b's class token, row b*T of the (B*T, D)
    rows.  Fed the oracle's last block output, its logits are the
    oracle's within ``FB_TAIL_ULP``, argmax for argmax; fed the same
    buffer with the images in reverse order, they come out reversed bit
    for bit.  A stride off by one reads other tokens and fails both."""
    model, _ = deit
    x = jax.random.normal(jax.random.PRNGKey(batch),
                          model.graph.input_shape(batch))
    oracle = smoke.oracle_buffers(model.graph, CLIP_FREE, model.params, x)
    head, _ = model.program.stages()[-1]
    assert head.select == "cls"
    res = oracle[head.src]
    assert res.shape[0] == batch and res.shape[1] % 8

    feed = {name.replace(".", "@"): v for name, v in oracle.items()}

    def logits(buf):
        fed = dict(feed, **{head.src: buf})
        return np.asarray(jax.jit(lambda pk, v, f: list(stage_outputs(
            pk, v, feed=f, return_logits=True))[-1].value)(
                model.packed, x, fed))

    got = logits(res)
    want = np.asarray(oracle[head.dst])
    assert got.shape == want.shape == (batch, SMALL["classes"])
    assert smoke.ulp_error(got, want) <= smoke.FB_TAIL_ULP
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    np.testing.assert_array_equal(logits(res[::-1]), got[::-1])
