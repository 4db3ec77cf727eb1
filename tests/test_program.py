"""Compiler/executor equivalence: the scheduled program IS the compute.

For each CNN, ``execute(compile(net))`` must reproduce the functional
crossbar forward within the exact/clip-free predicate of DESIGN.md §4:
bit-exact when every mount is clip-free, tolerance when ADC saturation
can fire (the chunk boundaries differ: FB-slice mounts vs the model's
array-row chunks).  Both sides are jitted so XLA applies the same FMA
contraction (DESIGN.md §5).

Also covers: the fused ``fb_epilogue`` kernel vs its unfused oracle,
proof that ReLU / max pool / softmax actually run through the fused
kernel, per-mount ADC saturation fidelity, program wiring validation,
and the compile-once/execute-per-batch serving entry.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import GRAPHS, HurryConfig
from repro.core.crossbar import CrossbarConfig, make_crossbar_matmul
from repro.core.workload import LayerSpec
from repro.kernels import ref
from repro.kernels.fb_epilogue import fb_epilogue
from repro.kernels.crossbar_gemm import crossbar_gemm
from repro.program import compile_network, execute_packed, pack_program

NETS = ("alexnet", "vgg16", "resnet18")
# rows=511 is clip-free (DESIGN.md §4) -> the functional model takes its
# exact path and every program mount (tile_rows <= 511) digitizes exactly
CLIP_FREE = CrossbarConfig(rows=511, adc_bits=9)
# the same numerics as a front-door config: 511-row arrays, 9-bit ADC
CLIP_FREE_API = HurryConfig(array_rows=511)


def _data(net, batch=2, seed=0):
    graph = GRAPHS[net]()
    params = graph.init_params(jax.random.PRNGKey(1))
    # random biases: the fused epilogue's bias add must be exercised
    # (model init zeros them)
    params = {k: {"w": v["w"],
                  "b": 0.1 * jax.random.normal(
                      jax.random.PRNGKey(zlib.crc32(k.encode())),
                      v["b"].shape)}
              for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, 32, 32, 3))
    return graph, params, x


def _ref_logits(graph, params, x, cfg):
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(cfg), logits=True))
    return fwd(params, x)


def _pack(net, params, cfg):
    return pack_program(compile_network(GRAPHS[net](), cfg=cfg), params)


@pytest.mark.parametrize("net", NETS)
def test_program_bit_exact_clip_free(net):
    """The serving front AND the bare executor == functional forward,
    bitwise, clip-free (both sides jitted — FMA contraction, DESIGN.md
    §5)."""
    graph, params, x = _data(net)
    ref_logits = _ref_logits(graph, params, x, CLIP_FREE)
    # the serving front: weights mounted once at compile
    model = api.compile(graph, CLIP_FREE_API, params=params)
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(ref_logits))
    # the executor entry on a program packed outside the trace
    packed = _pack(net, params, CLIP_FREE)
    logits = jax.jit(lambda pk, v: execute_packed(
        pk, v, return_logits=True))(packed, x)
    probs = jax.jit(execute_packed)(packed, x)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    np.testing.assert_allclose(
        np.asarray(probs),
        np.asarray(jax.nn.softmax(ref_logits, axis=-1)), atol=1e-7)


def test_program_tolerance_when_clipping_fires():
    """Saturating config (7-bit ADC): program mounts chunk K at FB-slice
    granularity while the model chunks at array rows, so clipped outputs
    differ — but must stay close (DESIGN.md §4 'tolerance otherwise')."""
    cfg = CrossbarConfig(adc_bits=7)     # rows=512 > 127: clipping fires
    graph, params, x = _data("alexnet")
    out = jax.jit(lambda pk, v: execute_packed(
        pk, v, return_logits=True))(_pack("alexnet", params, cfg), x)
    ref_logits = _ref_logits(graph, params, x, cfg)
    r, o = np.asarray(ref_logits), np.asarray(out)
    assert not np.array_equal(r, o)      # saturation genuinely engaged
    assert np.linalg.norm(o - r) / np.linalg.norm(r) < 0.2
    assert np.corrcoef(r.ravel(), o.ravel())[0, 1] > 0.98


def test_single_dispatch_reproduces_per_mount_adc_saturation():
    """The executor's single K-grid dispatch (rows == tile_rows) keeps
    per-mount saturation: each K block is one array read, clipped
    independently — matching the bit-sliced oracle at mount chunking."""
    xq = jnp.ones((8, 972), jnp.int8)      # 2 mounts x 486 all-ones rows
    wq = jnp.ones((972, 16), jnp.int8)
    y = crossbar_gemm(xq, wq, adc_bits=8, rows=486,
                      block_m=512, block_n=512, interpret=True)
    yr = ref.crossbar_gemm_ref(xq, wq, adc_bits=8, rows=486)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert int(y[0, 0]) == 2 * 255        # clipped per mount, not 972


# ---------------------------------------------------------------------------
# fused fb_epilogue kernel vs unfused oracle
# ---------------------------------------------------------------------------

_EPI_CASES = [
    dict(act="none"),
    dict(act="relu"),
    dict(act="relu", pool="max", window=2, img_hw=8),
    dict(act="relu", pool="avg", window=4, img_hw=8),
    dict(act="none", softmax=True),
]


@pytest.mark.parametrize("kw", _EPI_CASES)
@pytest.mark.parametrize("with_res", [False, True])
def test_fb_epilogue_matches_oracle(kw, with_res):
    if with_res and kw.get("softmax"):
        pytest.skip("residual never feeds the softmax FB")
    key = jax.random.PRNGKey(0)
    B, ih, N = 2, 8, 64
    M = B * ih * ih
    y = jax.random.randint(key, (M, N), -20000, 20000, dtype=jnp.int32)
    scale = jnp.array([[0.0123]], jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(1), (N,), jnp.float32)
    res = (jax.random.normal(jax.random.PRNGKey(2), (M, N), jnp.float32)
           if with_res else None)
    out = fb_epilogue(y, scale, bias, res, interpret=True, **kw)
    oracle = jax.jit(functools.partial(ref.fb_epilogue_ref, **kw)
                     )(y, scale, bias, res)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_fused_epilogue_used_for_all_postops(monkeypatch):
    """ReLU / max pool / residual / softmax all run through fb_epilogue —
    the crossbar output never round-trips through a separate jnp op."""
    import repro.program.execute as ex
    seen = []

    def spy(y, scale, bias, res=None, **kw):
        seen.append((kw.get("act"), kw.get("pool"), kw.get("softmax"),
                     res is not None))
        return fb_epilogue(y, scale, bias, res, **kw)

    monkeypatch.setattr(ex, "fb_epilogue", spy)
    for net in ("alexnet", "resnet18"):
        _, params, x = _data(net, batch=1)
        execute_packed(_pack(net, params, CLIP_FREE), x)
    acts = {s[0] for s in seen}
    pools = {s[1] for s in seen}
    assert "relu" in acts
    assert {"max", "avg"} <= pools
    assert any(s[2] for s in seen)        # softmax FB fused
    assert any(s[3] for s in seen)        # residual FB fused
    # every stage of both programs went through the fused kernel
    n_stages = sum(len(compile_network(GRAPHS[n](), cfg=CLIP_FREE).stages())
                   for n in ("alexnet", "resnet18"))
    assert len(seen) == n_stages


# ---------------------------------------------------------------------------
# program structure / wiring
# ---------------------------------------------------------------------------

def test_program_structure_and_mounts():
    program = compile_network(GRAPHS["alexnet"](), cfg=CLIP_FREE)
    kinds = {op.kind for op in program.ops}
    assert kinds == {"gemm", "relu", "maxpool", "softmax"}
    for op in program.ops:
        if op.kind != "gemm":
            continue
        assert 0 < op.tile_rows <= 511 and op.tile_cols > 0
        # mount rounds tile the whole weight matrix exactly
        k_cover = sorted((r.k0, r.k1) for r in op.mount_rounds)
        assert k_cover[0][0] == 0
        assert max(r.k1 for r in op.mount_rounds) > 0
        # decoded FB placement was exported onto the op
        assert op.fb_rows > 0 and op.fb_row0 >= 0
    # wiring: every src resolves to a producing op (or the input)
    names = {"input"} | {op.dst for op in program.ops}
    for op in program.ops:
        assert op.src in names
        if op.res_src:
            assert op.res_src in names


def test_compile_rejects_non_canonical_chain():
    bad = [LayerSpec("c", "conv", in_ch=3, out_ch=8, ksize=3, stride=1,
                     padding=1, in_hw=8, out_hw=8),
           LayerSpec("s", "softmax", features_out=8),
           LayerSpec("r", "relu", out_ch=8, out_hw=8)]
    with pytest.raises(ValueError, match="canonical"):
        compile_network(bad)


def test_resnet_residual_wiring_names_real_buffers():
    layers = GRAPHS["resnet18"]().layers
    by_name = {l.name: l for l in layers}
    # projection blocks route the shortcut through the proj conv
    assert by_name["s1b0_res"].residual_from == "s1b0_proj"
    # identity blocks route it from the previous block's output
    assert by_name["s0b1_res"].residual_from == "s0b0_relu2"
    assert by_name["s0b0_res"].residual_from == "relu0"


# ---------------------------------------------------------------------------
# serving entry
# ---------------------------------------------------------------------------

def test_make_server_compiles_once_and_is_deterministic():
    """``CompiledModel.run`` traces its executor once for a bucket and
    serves repeat requests identically, equal to the bare executor."""
    import repro.api.model as apimodel
    graph, params, x = _data("alexnet", batch=2)
    model = api.compile(graph, CLIP_FREE_API, params=params)
    assert model.program.n_mount_rounds > 0
    traces = []
    orig = apimodel.execute_packed

    def spy(pk, v, **kw):
        traces.append(v.shape[0])
        return orig(pk, v, **kw)

    apimodel.execute_packed = spy
    try:
        y1 = jax.block_until_ready(model.run(x, logits=True))
        y2 = jax.block_until_ready(model.run(x, logits=True))
    finally:
        apimodel.execute_packed = orig
    assert traces == [2]                  # compiled once, then served
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    fwd = jax.jit(lambda pk, v: execute_packed(pk, v, return_logits=True))
    np.testing.assert_array_equal(np.asarray(y1),
                                  np.asarray(fwd(model.packed, x)))
