"""Compile-time weight mounting (``program/pack.py``) + block activation.

Covers ISSUE 4's acceptance criteria: the packed executor consumes
pre-quantized int8 mount planes (bit-identical to traced quantization,
conv layout applied, K padded to full mounts); save -> load -> run is
bit-exact WITHOUT re-deriving weight planes (no ``quantize_symmetric``
of weights on the load-then-run path — version-1 files repack once at
load); edge-block activation (M and N that divide no block) is exact at
the kernel level and through a whole non-divisor network; and the executor's buffer-lifetime
bookkeeping never changes results.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import GRAPHS, HurryConfig, NetworkBuilder
from repro.api.zoo import vit_tiny_graph
from repro.core.conv import im2col
from repro.core.crossbar import (CrossbarConfig, crossbar_matmul,
                                 make_crossbar_matmul, quantize_symmetric)
from repro.kernels import ref
from repro.kernels.crossbar_gemm import (clip_possible, crossbar_gemm,
                                        dense_blocks, dense_layout,
                                        mount_layout, mount_rows,
                                        mounted_gemm)
from repro.kernels.fb_epilogue import fb_epilogue
from repro.program import compile_network, execute_packed, pack_program
from repro.program.execute import stage_outputs

CLIP_FREE = CrossbarConfig(rows=511, adc_bits=9)


# ---------------------------------------------------------------------------
# packing: planes match traced quantization, layout and padding applied
# ---------------------------------------------------------------------------

# a clip-free config: every static stage dense; a config whose every
# mount can clip (a 4-bit ADC digitizes counts up to 15, the smallest
# alexnet mount has 27 rows): every stage mounted
LAYOUT_CASES = {"dense": CLIP_FREE,
                "mounted": CrossbarConfig(adc_bits=4)}


@pytest.mark.parametrize("layout", LAYOUT_CASES)
def test_packed_planes_match_traced_quantization(layout):
    params = GRAPHS["alexnet"]().init_params(jax.random.PRNGKey(1))
    program = compile_network(GRAPHS["alexnet"](), cfg=LAYOUT_CASES[layout])
    packed = pack_program(program, params)
    assert packed.program.plans == ()       # executor never reads plans
    assert packed.layouts() == (layout,) * len(program.stages())
    multi_mount_unaligned = 0
    for (gemm, _), st in zip(program.stages(), packed.stages):
        w = params[gemm.param]["w"]
        if gemm.is_conv:
            kk = w.shape[0] * w.shape[1] * w.shape[2]
            # dense: (i, j, c) rows, the channels-minor im2col order;
            # mounted: (c, i, j), the oracle's im2col order
            w = (w.reshape(kk, -1) if layout == "dense"
                 else w.transpose(2, 0, 1, 3).reshape(kk, -1))
        wq = np.asarray(jax.jit(lambda v: quantize_symmetric(v, 8)[0])(w))
        assert st.w8.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(st.w_amax), np.asarray(jnp.max(jnp.abs(w))))
        rows, k = gemm.tile_rows, wq.shape[0]
        if layout == "dense":
            # K padded only at its end, to whole kernel blocks
            kp, block = dense_blocks(k)
            assert st.w8.shape[0] == kp and kp % block == 0
            assert kp - k < 128 * (kp // block)
            np.testing.assert_array_equal(np.asarray(st.w8)[:k], wq)
            assert not np.asarray(st.w8)[k:].any()
            continue
        if k <= rows:            # one mount: the whole contraction, unpadded
            np.testing.assert_array_equal(np.asarray(st.w8), wq)
            continue
        # mount layout: tile_rows real rows per mount, then zero rows up
        # to the 128-row tiling
        n, height = -(-k // rows), mount_rows(rows)
        assert st.w8.shape[0] == n * height and height % 128 == 0
        mounts = np.asarray(st.w8).reshape(n, height, -1)
        real = np.pad(wq, ((0, n * rows - k), (0, 0))).reshape(n, rows, -1)
        np.testing.assert_array_equal(mounts[:, :rows], real)
        assert not mounts[:, rows:].any()
        multi_mount_unaligned += rows % 128 != 0
    # alexnet has 485/493-row mounts
    assert multi_mount_unaligned or layout == "dense"


def test_layouts_follow_clip_possible_per_stage():
    """The benchmark's config lays every ResNet-18 stage out dense; the
    8-bit-ADC config mounts every stage whose mounts can clip and keeps
    alexnet's 27-row stem dense (27 <= 255: it cannot clip); attention's
    dynamic stages stay mounted."""
    resnet = api.compile("resnet18", HurryConfig(array_rows=511))
    assert resnet.packed.layouts() == ("dense",) * 21
    alexnet = api.compile("alexnet", HurryConfig(adc_bits=8))
    want = tuple("mounted" if clip_possible(g.tile_rows, 8) else "dense"
                 for g, _ in alexnet.program.stages())
    assert alexnet.packed.layouts() == want
    assert want == ("dense",) + ("mounted",) * 7
    vit = api.compile(vit_tiny_graph(depth=1), HurryConfig(array_rows=511))
    assert vit.packed.layouts() == tuple(
        "mounted" if g.kind == "dyn_gemm" else "dense"
        for g, _ in vit.program.stages())


def test_channels_minor_im2col_permutes_the_patch_features():
    """``im2col(channels_minor=True)`` holds the same patch entries as
    the oracle's ``(c, i, j)`` im2col, in ``(i, j, c)`` order, for the
    tap-concat and the non-overlapping reshape forms."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, 5))
    for k, stride, pad in ((3, 1, 1), (3, 2, 1), (1, 2, 0), (3, 3, 0)):
        cij = np.asarray(im2col(x, k, stride, pad))
        ijc = np.asarray(im2col(x, k, stride, pad, channels_minor=True))
        n, oh, ow, _ = cij.shape
        cij = cij.reshape(n, oh, ow, 5, k * k).swapaxes(-1, -2)
        np.testing.assert_array_equal(ijc, cij.reshape(n, oh, ow, -1))


# (name, input hw, input channels, out channels, k, stride, padding)
DENSE_CONV_CASES = (
    ("3x3_s1_p1", 8, 16, 24, 3, 1, 1),
    ("3x3_s2_p1", 8, 16, 24, 3, 2, 1),
    ("1x1_s2_p0", 8, 16, 24, 1, 2, 0),
    ("stem_c3", 8, 3, 16, 3, 1, 1),
    ("patch_16x16", 32, 3, 16, 16, 16, 0),
    ("3x3_c128_three_k_blocks", 4, 128, 16, 3, 1, 1),
)


def _conv_net(hw, cin, cout, k, stride, pad):
    nb = NetworkBuilder("one_conv", input_hw=hw, input_ch=cin)
    nb.conv(cout, k, stride, pad, name="c")
    nb.relu(name="r")
    nb.fc(10, name="fc")
    return nb.build()


def _oracle_conv_acc(graph, params, x, cfg):
    """The oracle's int32 conv GEMM: its ``(c, i, j)`` im2col matrix and
    weight matrix quantized as ``crossbar_linear`` does, one int dot."""
    l = graph.layers[0]
    w = params[l.name]["w"]
    cols = im2col(x, l.ksize, l.stride, l.padding)
    cols = cols.reshape(-1, cols.shape[-1])
    wm = w.transpose(2, 0, 1, 3).reshape(cols.shape[-1], -1)
    xq, _ = quantize_symmetric(cols, cfg.input_bits)
    wq, _ = quantize_symmetric(wm, cfg.weight_bits)
    return crossbar_matmul(xq, wq, cfg)


@pytest.mark.parametrize("case", DENSE_CONV_CASES, ids=lambda c: c[0])
def test_dense_operand_bit_exact_vs_oracle(case):
    """A dense conv stage — input quantized before im2col, patches cut
    channels-minor from the int8 tensor, K padded only to whole kernel
    blocks — gives the oracle's int32 GEMM and stage outputs bit for
    bit, and the whole net its logits."""
    _, hw, cin, cout, k, stride, pad = case
    graph = _conv_net(hw, cin, cout, k, stride, pad)
    config = HurryConfig(array_rows=511)
    model = api.compile(graph, config, seed=2)
    assert model.packed.layouts() == ("dense", "dense")
    x = jax.random.normal(jax.random.PRNGKey(3), graph.input_shape(2))
    cfg = config.crossbar()
    stages = jax.jit(lambda pk, v: {
        o.name: (o.value, o.acc) for o in stage_outputs(pk, v)})(
            model.packed, x)
    oracle = jax.jit(lambda p, v: graph.buffers(
        p, v, mm=make_crossbar_matmul(cfg)))(model.params, x)
    acc = jax.jit(_oracle_conv_acc, static_argnums=(0, 3))(
        graph, model.params, x, cfg)
    conv_acc = stages["r"][1]                # the conv stage writes "r"
    assert conv_acc.dtype == jnp.int32 and set(stages) == {"r", "fc"}
    np.testing.assert_array_equal(np.asarray(conv_acc), np.asarray(acc))
    for name, (value, _) in stages.items():
        np.testing.assert_array_equal(np.asarray(value),
                                      np.asarray(oracle[name]))


def test_dense_amax_reads_only_the_pixels_im2col_reads():
    """A 1x1/2 projection reads every other pixel: the input's largest
    |value|, put at an odd pixel, is never read, so it must not set the
    scale — the program still matches the oracle bit for bit, and a
    scale taken over the whole input would not."""
    graph = _conv_net(8, 16, 24, 1, 2, 0)
    config = HurryConfig(array_rows=511)
    model = api.compile(graph, config, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), graph.input_shape(2))
    x = x.at[1, 3, 5, 7].set(-50.0)
    cfg = config.crossbar()
    acc = jax.jit(lambda pk, v: next(stage_outputs(pk, v)).acc)(
        model.packed, x)
    want = jax.jit(_oracle_conv_acc, static_argnums=(0, 3))(
        graph, model.params, x, cfg)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(cfg), logits=True))
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(fwd(model.params, x)))
    # the planted value is the input's max and im2col never reads it
    read = np.asarray(x)[:, ::2, ::2]
    assert np.abs(read).max() < 50.0 == np.abs(np.asarray(x)).max()


def test_resnet18_cifar10_dense_program_logits_bit_exact():
    """The benchmark's network and config (``HurryConfig(array_rows=
    511)``, every stage dense) at batch 2: logits bit-exact against the
    jitted functional oracle."""
    config = HurryConfig(array_rows=511)
    model = api.compile("resnet18", config, seed=5)
    graph = model.graph
    x = jax.random.normal(jax.random.PRNGKey(4), graph.input_shape(2))
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(config.crossbar()), logits=True))
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(fwd(model.params, x)))


def test_multi_mount_stage_keeps_sliced_adc_semantics():
    """A multi-mount stage whose ``tile_rows`` is off the 128-row tiling
    (alexnet conv2: K=576 in two 486-row mounts, each laid out as 512
    rows), packed at compile time and streamed the way the executor
    streams it: the sliced kernel still clips per mount over exactly
    ``tile_rows`` real rows — bit-exact against ``ref.crossbar_gemm_ref``
    chunked at ``tile_rows``, and genuinely clipping."""
    params = GRAPHS["alexnet"]().init_params(jax.random.PRNGKey(1))
    program = compile_network(GRAPHS["alexnet"](), cfg=CrossbarConfig(adc_bits=7))
    packed = pack_program(program, params)
    (gemm, _), st = program.stages()[1], packed.stages[1]
    rows = gemm.tile_rows
    assert gemm.name == "conv2" and rows % 128 and rows < 576
    w = params["conv2"]["w"]
    w = w.transpose(2, 0, 1, 3).reshape(576, -1)
    wq = jax.jit(lambda v: quantize_symmetric(v, 8)[0])(w).astype(jnp.int8)
    x = jax.random.randint(jax.random.PRNGKey(2), (16, 576), -128, 128,
                           jnp.int32).astype(jnp.int8)
    y = mounted_gemm(x, st.w8, adc_bits=7, rows=rows, interpret=True)
    want = ref.crossbar_gemm_ref(x, wq, adc_bits=7, rows=rows)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    assert not np.array_equal(np.asarray(y),
                              np.asarray(ref.crossbar_gemm_exact_ref(x, wq)))


def test_buffer_lifetime_dropping_never_changes_results():
    """Dropping dead buffers is bookkeeping only: a run that keeps every
    intermediate alive produces the identical output."""
    import repro.program.execute as ex
    params = GRAPHS["resnet18"]().init_params(jax.random.PRNGKey(1))
    program = compile_network(GRAPHS["resnet18"](), cfg=CLIP_FREE)
    packed = pack_program(program, params)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 32, 3))
    y_drop = execute_packed(packed, x, return_logits=True)
    orig = ex._last_reads
    ex._last_reads = lambda stages: {}      # never drop anything
    try:
        y_keep = execute_packed(packed, x, return_logits=True)
    finally:
        ex._last_reads = orig
    np.testing.assert_array_equal(np.asarray(y_drop), np.asarray(y_keep))


# ---------------------------------------------------------------------------
# edge-block activation: exact at the kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("adc_bits", [9, 5])   # exact path / sliced path
def test_crossbar_gemm_pad_to_block_slice_exact(adc_bits):
    """Non-divisor M/N/K: edge blocks == the oracle, shape (M, N)."""
    k = jax.random.PRNGKey(0)
    M, K, N, rows = 37, 150, 19, 64
    x = jax.random.randint(k, (M, K), -128, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(jax.random.PRNGKey(1), (K, N), -128, 128,
                           jnp.int32).astype(jnp.int8)
    y = crossbar_gemm(x, w, adc_bits=adc_bits, rows=rows, block_m=32,
                      block_n=8, interpret=True)
    yr = ref.crossbar_gemm_ref(x, w, adc_bits=adc_bits, rows=rows)
    assert y.shape == (M, N)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_fb_epilogue_pad_to_block_slice_exact():
    """Odd M (plain chain: an edge row block) and odd N (pool chain:
    one full-width column block) are exact."""
    key = jax.random.PRNGKey(0)
    scale = jnp.array([[0.017]], jnp.float32)
    # odd M, odd N, residual + relu
    M, N = 101, 67
    y = jax.random.randint(key, (M, N), -20000, 20000, dtype=jnp.int32)
    bias = jax.random.normal(jax.random.PRNGKey(1), (N,), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(2), (M, N), jnp.float32)
    out = fb_epilogue(y, scale, bias, res, act="relu", block_m=64,
                      block_n=32, interpret=True)
    oracle = jax.jit(lambda *a: ref.fb_epilogue_ref(*a, act="relu"))(
        y, scale, bias, res)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    # pooling with an odd feature axis (M fixed by the image structure)
    B, ih, N = 2, 8, 67
    y = jax.random.randint(key, (B * ih * ih, N), -20000, 20000,
                           dtype=jnp.int32)
    bias = jax.random.normal(jax.random.PRNGKey(3), (N,), jnp.float32)
    out = fb_epilogue(y, scale, bias, None, act="relu", pool="max",
                      window=2, img_hw=ih, block_n=32, interpret=True)
    oracle = jax.jit(lambda *a: ref.fb_epilogue_ref(
        *a, act="relu", pool="max", window=2, img_hw=ih))(y, scale, bias,
                                                          None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


@pytest.mark.parametrize("n,kw", [
    (5, dict(act="relu", pool="avg", window=2, img_hw=4)),  # 2 per step
    (9, dict(act="relu", pool="avg", window=4, img_hw=4)),  # 8 per step
    (3, dict(act="relu", pool="max", window=2, img_hw=4)),  # 2 per step
], ids=["avgpool_4to2", "avgpool_4x4", "maxpool_4to2"])
def test_fb_epilogue_pads_pooled_batches(n, kw):
    """A batch that is not a multiple of the images per grid step is
    padded with whole zero images and sliced back exactly."""
    M, N = n * kw["img_hw"] ** 2, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.randint(ks[0], (M, N), -20000, 20000, dtype=jnp.int32)
    scale = jnp.array([[0.017]], jnp.float32)
    bias = jax.random.normal(ks[1], (N,), jnp.float32)
    res = jax.random.normal(ks[2], (M, N), jnp.float32)
    out = fb_epilogue(y, scale, bias, res, interpret=True, **kw)
    oracle = jax.jit(lambda *a: ref.fb_epilogue_ref(*a, **kw))(
        y, scale, bias, res)
    assert out.shape == oracle.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_non_divisor_network_end_to_end_bit_exact():
    """A net whose M/N divide nothing still matches the functional
    forward bitwise under tiny block sizes — executor-level proof that
    edge-block activation is exact."""
    nb = NetworkBuilder("odd13", input_hw=6, input_ch=3)
    nb.conv(13, name="c1")                  # N=13, M=36 vs 8x8 blocks
    nb.relu(name="r1")
    nb.fc(5, name="fc")
    nb.softmax(name="sm")
    graph = nb.build()
    config = HurryConfig(array_rows=511, block_m=8, block_n=8)
    model = api.compile(graph, config, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(1))
    logits = model.run(x, logits=True)
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(config.crossbar()), logits=True))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(fwd(model.params, x)))


# ---------------------------------------------------------------------------
# persistence: packed planes round-trip; loading never touches float weights
# ---------------------------------------------------------------------------

def _custom_model():
    nb = NetworkBuilder("tiny", input_hw=8, input_ch=4)
    nb.conv(16, name="c1")
    nb.relu(name="r1")
    nb.maxpool(name="p1")
    nb.fc(10, name="fc")
    nb.softmax(name="sm")
    graph = nb.build()
    model = api.compile(graph, HurryConfig(array_rows=511), seed=1)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(3))
    return model, x


def test_load_then_run_never_requantizes_weights(tmp_path, monkeypatch):
    """v2 saves carry the mount planes; load + run must not re-derive
    them (no weight ever passes through quantize_symmetric again)."""
    model, x = _custom_model()
    y_mem = model.run(x, logits=True)
    path = model.save(str(tmp_path / "m.npz"))

    import repro.api.serialize as sermod
    import repro.program.pack as packmod

    def poisoned(*a, **k):   # any weight quantization on this path is a bug
        raise AssertionError("weight re-quantization on the load path")

    monkeypatch.setattr(packmod, "quantize_symmetric", poisoned)
    monkeypatch.setattr(sermod, "pack_program", poisoned)
    loaded = api.load(path)
    y_loaded = loaded.run(x, logits=True)
    np.testing.assert_array_equal(np.asarray(y_mem), np.asarray(y_loaded))
    for a, b in zip(model.packed.stages, loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(a.w8), np.asarray(b.w8))


def test_version1_file_loads_via_repack_fallback(tmp_path):
    """Pre-packing (version 1) saves still load: planes re-derived once
    from the saved params, bit-identical to compile-time packing."""
    model, x = _custom_model()
    path = model.save(str(tmp_path / "m.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        arrays = {k: z[k] for k in z.files
                  if k != "__meta__" and k[0] == "p"}
    meta["version"] = 1
    for key in ("packed_stages", "buckets"):
        meta.pop(key)
    v1 = str(tmp_path / "v1.npz")
    with open(v1, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    loaded = api.load(v1)
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(loaded.run(x, logits=True)))
    for a, b in zip(model.packed.stages, loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(a.w8), np.asarray(b.w8))
    with pytest.raises(ValueError, match="version"):
        meta["version"] = 99
        bad = str(tmp_path / "bad.npz")
        with open(bad, "wb") as f:
            np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
        api.load(bad)


def _real_rows(w8: np.ndarray, op, layout: str) -> np.ndarray:
    """A stored plane's real K rows in the oracle's ``(c, i, j)`` order."""
    k, rows = max(r.k1 for r in op.mount_rounds), op.tile_rows
    if layout == "dense":
        w8 = w8[:k]
        if op.is_conv:                    # (i, j, c) -> (c, i, j)
            kk = op.ksize * op.ksize
            w8 = w8.reshape(kk, k // kk, -1).swapaxes(0, 1).reshape(k, -1)
        return w8
    if k > rows:
        n = -(-k // rows)
        w8 = w8.reshape(n, mount_rows(rows), -1)[:, :rows]
    return w8.reshape(-1, w8.shape[-1])[:k]


def _old_plane(w8: np.ndarray, op, layout: str, version: int) -> np.ndarray:
    """A plane as a version 2-4 file stored it: ``(c, i, j)`` rows, K
    zero-padded at its end to whole ``tile_rows`` mounts (versions 2-3)
    or in the mount layout (version 4)."""
    w8 = _real_rows(w8, op, layout)
    if version == 4:
        return np.asarray(mount_layout(jnp.asarray(w8), op.tile_rows, 0))
    return np.pad(w8, ((0, -w8.shape[0] % op.tile_rows), (0, 0)))


@pytest.mark.parametrize("version", [2, 3, 4])
def test_pre_mount_layout_file_loads_bit_identical(tmp_path, version):
    """Files saved before the dense layout load and run bit-identically,
    on a multi-mount stage whose ``tile_rows`` is off the 128-row tiling
    and a conv whose ``(c, i, j)`` rows are permuted at load: versions
    2-3 (K padded once, at its end, to whole mounts; the old 512x512
    block defaults stored explicitly) and version 4 (every plane in the
    mount layout, no ``layouts`` list)."""
    nb = NetworkBuilder("tiny", input_hw=8, input_ch=4)
    nb.conv(16, name="c1")
    nb.relu(name="r1")
    nb.maxpool(name="p1")
    nb.fc(10, name="fc")
    graph = nb.build()
    model = api.compile(graph, HurryConfig(array_rows=100), seed=1)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(3))
    gemms = [g for g, _ in model.program.stages()]
    layouts = model.packed.layouts()
    assert layouts == ("dense", "dense")
    # fc: K=256 in 100-row mounts
    assert any(max(r.k1 for r in g.mount_rounds) > g.tile_rows
               and g.tile_rows % 128 for g in gemms)
    path = model.save(str(tmp_path / "m.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    assert meta["version"] == 7 and meta["layouts"] == list(layouts)
    meta["version"] = version
    del meta["layouts"]
    if version < 4:
        meta["config"].update(block_m=512, block_n=512)
    for i, op in enumerate(gemms):
        arrays[f"w{i}"] = _old_plane(arrays[f"w{i}"], op, layouts[i],
                                     version)
    old = str(tmp_path / f"v{version}.npz")
    with open(old, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    loaded = api.load(old)
    assert loaded.config == model.config        # block sizes back to None
    for a, b in zip(model.packed.stages, loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(a.w8), np.asarray(b.w8))
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(loaded.run(x, logits=True)))


def test_version4_mounted_planes_load_unchanged(tmp_path):
    """Under a config whose mounts can clip, a version-4 file's planes
    are already version 5's: they load as stored."""
    nb = NetworkBuilder("tiny", input_hw=8, input_ch=16)
    nb.conv(16, name="c1")
    nb.relu(name="r1")
    nb.fc(10, name="fc")
    graph = nb.build()
    model = api.compile(graph, HurryConfig(array_rows=100, adc_bits=5),
                        seed=1)
    assert model.packed.layouts() == ("mounted", "mounted")
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(2))
    path = model.save(str(tmp_path / "m.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    meta["version"] = 4
    del meta["layouts"]
    v4 = str(tmp_path / "v4.npz")
    with open(v4, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    loaded = api.load(v4)
    for i, st in enumerate(loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(st.w8), arrays[f"w{i}"])
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(loaded.run(x, logits=True)))


def test_dense_gemm_bit_exact_over_k_blocks():
    """``mounted_gemm(layout="dense")`` at K with one, several and padded
    K blocks equals the plain integer GEMM; the dense layout refuses a
    mount that can clip."""
    for k in (150, 1152, 1100):
        kx, kw = jax.random.split(jax.random.PRNGKey(k))
        x = jax.random.randint(kx, (24, k), -128, 128).astype(jnp.int8)
        w = jax.random.randint(kw, (k, 19), -128, 128).astype(jnp.int8)
        y = mounted_gemm(x, dense_layout(w, 0), rows=485, layout="dense",
                         block_m=16, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(ref.crossbar_gemm_exact_ref(x, w)))
    assert dense_blocks(1100) == (1152, 384)      # K padded by 52 rows
    with pytest.raises(ValueError, match="exact-only"):
        mounted_gemm(x, dense_layout(w, 0), rows=512, adc_bits=9,
                     layout="dense", interpret=True)


def test_packed_program_is_a_jit_arg():
    """PackedProgram crosses the jit boundary as a pytree (arrays as
    leaves, the plan-free program as static treedef metadata)."""
    params = GRAPHS["alexnet"]().init_params(jax.random.PRNGKey(1))
    program = compile_network(GRAPHS["alexnet"](), cfg=CLIP_FREE)
    packed = pack_program(program, params)
    leaves = jax.tree_util.tree_leaves(packed)
    assert all(isinstance(l, jax.Array) for l in leaves)
    assert hash(packed.program) is not None
    traced = []
    fn = jax.jit(lambda pk, v: (traced.append(1),
                                execute_packed(pk, v,
                                               return_logits=True))[1])
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    fn(packed, x)
    fn(packed, x)                     # same packed pytree: cache hit
    assert len(traced) == 1
