"""Compile-time weight mounting (``program/pack.py``) + block activation.

Covers ISSUE 4's acceptance criteria: the packed executor consumes
pre-quantized int8 mount planes (bit-identical to traced quantization,
conv layout applied, K padded to full mounts); save -> load -> run is
bit-exact WITHOUT re-deriving weight planes (no ``quantize_symmetric``
of weights on the load-then-run path — version-1 files repack once at
load); pad-to-block activation is slice-exact at the kernel level and
through a whole non-divisor network; and the executor's buffer-lifetime
bookkeeping never changes results.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import HurryConfig, NetworkBuilder
from repro.core.crossbar import CrossbarConfig, quantize_symmetric
from repro.kernels import ref
from repro.kernels.crossbar_gemm import (crossbar_gemm, mount_rows,
                                        mounted_gemm)
from repro.kernels.fb_epilogue import fb_epilogue
from repro.models.cnn import CNN_MODELS, make_crossbar_matmul
from repro.program import compile_network, execute_packed, pack_program

CLIP_FREE = CrossbarConfig(rows=511, adc_bits=9)


# ---------------------------------------------------------------------------
# packing: planes match traced quantization, layout and padding applied
# ---------------------------------------------------------------------------

def test_packed_planes_match_traced_quantization():
    params = CNN_MODELS["alexnet"].init(jax.random.PRNGKey(1))
    program = compile_network("alexnet", cfg=CLIP_FREE)
    packed = pack_program(program, params)
    assert packed.program.plans == ()       # executor never reads plans
    multi_mount_unaligned = 0
    for (gemm, _), st in zip(program.stages(), packed.stages):
        w = params[gemm.param]["w"]
        if gemm.is_conv:
            kk = w.shape[0] * w.shape[1] * w.shape[2]
            w = w.transpose(2, 0, 1, 3).reshape(kk, -1)
        wq = np.asarray(jax.jit(lambda v: quantize_symmetric(v, 8)[0])(w))
        assert st.w8.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(st.w_amax), np.asarray(jnp.max(jnp.abs(w))))
        rows, k = gemm.tile_rows, wq.shape[0]
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
    assert multi_mount_unaligned     # alexnet has 485/493-row mounts


def test_multi_mount_stage_keeps_sliced_adc_semantics():
    """A multi-mount stage whose ``tile_rows`` is off the 128-row tiling
    (alexnet conv2: K=576 in two 486-row mounts, each laid out as 512
    rows), packed at compile time and streamed the way the executor
    streams it: the sliced kernel still clips per mount over exactly
    ``tile_rows`` real rows — bit-exact against ``ref.crossbar_gemm_ref``
    chunked at ``tile_rows``, and genuinely clipping."""
    params = CNN_MODELS["alexnet"].init(jax.random.PRNGKey(1))
    program = compile_network("alexnet", cfg=CrossbarConfig(adc_bits=7))
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
    params = CNN_MODELS["resnet18"].init(jax.random.PRNGKey(1))
    program = compile_network("resnet18", cfg=CLIP_FREE)
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
# pad-to-block activation: slice-exact at the kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("adc_bits", [9, 5])   # exact path / sliced path
def test_crossbar_gemm_pad_to_block_slice_exact(adc_bits):
    """Non-divisor M/N/K: zero-padded full tiles == the unpadded oracle."""
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
    """Odd M (plain chain) and odd N (pool chain) pad + slice exactly."""
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
    (9, dict(pool="seqmean", window=4, norm="layer")),   # 8 per step
    (9, dict(act="relu", pool="avg", window=4, img_hw=4)),
    (3, dict(act="relu", pool="max", window=2, img_hw=4)),  # 2 per step
], ids=["seqmean", "avgpool_4x4", "maxpool_4to2"])
def test_fb_epilogue_pads_pooled_batches(n, kw):
    """A batch that is not a multiple of the images (sequences) per grid
    step is padded with whole zero images and sliced back exactly."""
    rows = kw.get("img_hw", 2) ** 2 if kw["pool"] != "seqmean" \
        else kw["window"]
    M, N = n * rows, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    y = jax.random.randint(ks[0], (M, N), -20000, 20000, dtype=jnp.int32)
    scale = jnp.array([[0.017]], jnp.float32)
    bias = jax.random.normal(ks[1], (N,), jnp.float32)
    res = jax.random.normal(ks[2], (M, N), jnp.float32)
    ln = {}
    if kw.get("norm") == "layer":
        ln = dict(gamma=1 + 0.1 * jax.random.normal(ks[3], (N,)),
                  beta=0.1 * jax.random.normal(ks[4], (N,)))
    out = fb_epilogue(y, scale, bias, res, interpret=True, **kw, **ln)
    oracle = jax.jit(lambda *a: ref.fb_epilogue_ref(*a, **kw, **ln))(
        y, scale, bias, res)
    assert out.shape == oracle.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_non_divisor_network_end_to_end_bit_exact():
    """A net whose M/N divide nothing still matches the functional
    forward bitwise under tiny block sizes — executor-level proof that
    pad-to-block activation is slice-exact."""
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
    for a, b in zip(model._packed().stages, loaded.packed.stages):
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
    for a, b in zip(model._packed().stages, loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(a.w8), np.asarray(b.w8))
    with pytest.raises(ValueError, match="version"):
        meta["version"] = 99
        bad = str(tmp_path / "bad.npz")
        with open(bad, "wb") as f:
            np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
        api.load(bad)


def _v3_plane(w8: np.ndarray, op) -> np.ndarray:
    """A mount-layout plane as versions 2-3 stored it: the real K rows,
    zero-padded at the end to whole ``tile_rows`` mounts."""
    k, rows = max(r.k1 for r in op.mount_rounds), op.tile_rows
    if k > rows:
        n = -(-k // rows)
        w8 = w8.reshape(n, mount_rows(rows), -1)[:, :rows]
    w8 = w8.reshape(-1, w8.shape[-1])[:k]
    return np.pad(w8, ((0, -k % rows), (0, 0)))


@pytest.mark.parametrize("version", [2, 3])
def test_pre_mount_layout_file_loads_bit_identical(tmp_path, version):
    """Files saved before the mount layout (versions 2-3: K padded once,
    at its end, to whole mounts; the old 512x512 block defaults stored
    explicitly) load and run bit-identically, on a multi-mount stage
    whose ``tile_rows`` is off the 128-row tiling."""
    nb = NetworkBuilder("tiny", input_hw=8, input_ch=4)
    nb.conv(16, name="c1")
    nb.relu(name="r1")
    nb.maxpool(name="p1")
    nb.fc(10, name="fc")
    graph = nb.build()
    model = api.compile(graph, HurryConfig(array_rows=100), seed=1)
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(3))
    gemms = [g for g, _ in model.program.stages()]
    # fc: K=256 in 100-row mounts
    assert any(max(r.k1 for r in g.mount_rounds) > g.tile_rows
               and g.tile_rows % 128 for g in gemms)
    path = model.save(str(tmp_path / "m.npz"))
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    meta["version"] = version
    meta["config"].update(block_m=512, block_n=512)
    for i, op in enumerate(gemms):
        arrays[f"w{i}"] = _v3_plane(arrays[f"w{i}"], op)
    old = str(tmp_path / f"v{version}.npz")
    with open(old, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    loaded = api.load(old)
    assert loaded.config == model.config        # block sizes back to None
    for a, b in zip(model._packed().stages, loaded.packed.stages):
        np.testing.assert_array_equal(np.asarray(a.w8), np.asarray(b.w8))
    np.testing.assert_array_equal(np.asarray(model.run(x, logits=True)),
                                  np.asarray(loaded.run(x, logits=True)))


def test_packed_program_is_a_jit_arg():
    """PackedProgram crosses the jit boundary as a pytree (arrays as
    leaves, the plan-free program as static treedef metadata)."""
    params = CNN_MODELS["alexnet"].init(jax.random.PRNGKey(1))
    program = compile_network("alexnet", cfg=CLIP_FREE)
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
