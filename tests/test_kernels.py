"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# crossbar_gemm — exact integer semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,rows,adc", [
    (128, 256, 128, 256, 9),
    (128, 512, 256, 256, 9),
    (256, 384, 128, 128, 8),
    (128, 128, 128, 128, 7),     # 7-bit ADC: saturation kicks in
])
def test_crossbar_gemm_matches_ref(m, k, n, rows, adc):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x = jax.random.randint(kx, (m, k), -128, 128).astype(jnp.int8)
    w = jax.random.randint(kw, (k, n), -128, 128).astype(jnp.int8)
    y = ops.crossbar_gemm(x, w, adc_bits=adc, rows=rows, interpret=True)
    yr = ref.crossbar_gemm_ref(x, w, adc_bits=adc, rows=rows)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_crossbar_gemm_exact_when_adc_sufficient():
    """9-bit ADC + <=511-row chunks == exact int8 GEMM."""
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.randint(kx, (128, 768), -128, 128).astype(jnp.int8)
    w = jax.random.randint(kw, (768, 128), -128, 128).astype(jnp.int8)
    y = ops.crossbar_gemm(x, w, adc_bits=9, rows=256, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(x.astype(jnp.int32) @ w.astype(jnp.int32)))


# M and N that divide no block (DeiT-Ti's 197 tokens at batch 1 and 2, an
# fc head): the kernel runs edge blocks over the operands as they are
EDGE_GEMMS = [(197, 64, 197), (394, 192, 576), (394, 768, 192),
              (3, 512, 10)]


@pytest.mark.parametrize("path", ["exact", "dense", "sliced"])
@pytest.mark.parametrize("m,k,n", EDGE_GEMMS)
def test_mounted_gemm_edge_blocks_match_ref(m, k, n, path):
    """Every row and column, those of the edge blocks too, equals the
    reference bit for bit on both compute paths and both layouts; the
    exact path also with 128-square blocks, so that M and N both end in
    an edge block."""
    from repro.kernels.crossbar_gemm import (dense_layout, mount_layout,
                                            mounted_gemm)

    k1, k2 = jax.random.split(jax.random.PRNGKey(m * n + k))
    x = jax.random.randint(k1, (m, k), -128, 128).astype(jnp.int8)
    w = jax.random.randint(k2, (k, n), -128, 128).astype(jnp.int8)
    rows = 150                   # several mounts wherever K > 150
    if path == "sliced":         # a 7-bit ADC clips 150-row mounts
        runs = [mounted_gemm(x, mount_layout(w, rows, 0), adc_bits=7,
                             rows=rows, exact=False, interpret=True)]
        want = ref.crossbar_gemm_ref(x, w, adc_bits=7, rows=rows)
    else:
        if path == "dense":
            wl, kw = dense_layout(w, 0), dict(layout="dense")
        else:
            wl, kw = mount_layout(w, rows, 0), dict(exact=True)
        runs = [mounted_gemm(x, wl, rows=rows, block_m=bm, block_n=bm,
                             interpret=True, **kw) for bm in (None, 128)]
        want = ref.crossbar_gemm_exact_ref(x, w)
    for y in runs:
        assert y.shape == (m, n)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("n,kw,with_res", [
    (576, dict(), True),
    (768, dict(act="gelu"), True),
    (192, dict(act="relu"), True),
    (197, dict(softmax=True, post_scale=0.125), False),
], ids=["dequant_res", "gelu_res", "relu_res", "softmax"])
def test_fb_epilogue_edge_rows_match_ref(n, kw, with_res):
    """M = 394 rows in 256-row blocks: the edge block's 138 kept rows,
    like the full block's, are bit-identical to the jnp expression."""
    from repro.kernels.fb_epilogue import fb_epilogue

    m = 394
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    y = jax.random.randint(ks[0], (m, n), -20000, 20000, jnp.int32)
    scale = jnp.full((1, 1), 3e-4, jnp.float32)
    bias = jax.random.normal(ks[1], (n,), jnp.float32)
    res = jax.random.normal(ks[2], (m, n), jnp.float32) if with_res else None
    out = fb_epilogue(y, scale, bias, res, interpret=True, **kw)
    want = jax.jit(lambda *a: ref.fb_epilogue_ref(*a, **kw))(
        y, scale, bias, res)
    assert out.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# flash_attention — Eq. 1 semantics across shapes/dtypes/masks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hd", [(1, 128, 1, 64), (2, 256, 4, 64),
                                      (1, 512, 2, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, s, h, hd, causal):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    orf = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 512, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, window=128,
                            interpret=True)
    orf = ref.flash_attention_ref(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, interpret=True)
    orf = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_attention_gqa_expansion():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 256, 8, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    o = ops.attention(q, k, v, causal=True)
    ke = jnp.repeat(k, 4, axis=2)
    ve = jnp.repeat(v, 4, axis=2)
    orf = ref.flash_attention_ref(q, ke, ve, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused_gemm_epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 384),
                                   (128, 1024, 256)])
@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
def test_fused_gemm_epilogue(m, k, n, act):
    ks = jax.random.split(jax.random.PRNGKey(m + n), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * 0.05
    b = jax.random.normal(ks[2], (n,), jnp.float32)
    y = ops.fused_gemm_epilogue(x, w, b, act=act, interpret=True)
    yr = ref.fused_gemm_epilogue_ref(x, w, b, act=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


def test_fused_gemm_epilogue_residual():
    """The Conv+Res FB merge: residual add in the same kernel pass."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (128, 256), jnp.float32)
    w = jax.random.normal(ks[1], (256, 128), jnp.float32) * 0.05
    b = jnp.zeros((128,), jnp.float32)
    r = jax.random.normal(ks[2], (128, 128), jnp.float32)
    y = ops.fused_gemm_epilogue(x, w, b, r, act="relu", interpret=True)
    yr = ref.fused_gemm_epilogue_ref(x, w, b, act="relu", residual=r)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# packed_gemm — BAS block packing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [
    [256, 128, 384],            # already tile-aligned
    [200, 56, 300, 100],        # ragged (padding path)
    [128],                      # single group
    [0, 256, 0, 128],           # empty groups
])
def test_packed_gemm_matches_ref(sizes):
    G = len(sizes)
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes) + G), 2)
    w = jax.random.normal(ks[0], (G, 128, 256), jnp.float32) * 0.1
    t = max(sum(sizes), 1)
    x = jax.random.normal(ks[1], (t, 128), jnp.float32)
    y = ops.grouped_gemm(x, w, sizes)
    yr = ref.packed_gemm_ref(x, w, jnp.array(sizes))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


def test_packed_gemm_is_moe_expert_compute():
    """grouped_gemm == per-expert matmul on a sorted token buffer."""
    sizes = [96, 160]
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    w = jax.random.normal(ks[0], (2, 64, 128), jnp.float32) * 0.1
    x = jax.random.normal(ks[1], (256, 64), jnp.float32)
    y = ops.grouped_gemm(x, w, sizes)
    y0 = x[:96] @ w[0]
    y1 = x[96:] @ w[1]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate([y0, y1])),
                               rtol=1e-4, atol=1e-4)
