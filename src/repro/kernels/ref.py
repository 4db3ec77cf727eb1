"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each ``*_ref`` matches its kernel's semantics exactly (including ADC
clipping for the crossbar kernel); tests sweep shapes/dtypes and
``assert_allclose`` kernel-vs-ref in interpret mode.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# crossbar_gemm: bit-sliced int8 GEMM with per-plane ADC clipping
# ---------------------------------------------------------------------------

def crossbar_gemm_ref(x: jnp.ndarray, w: jnp.ndarray, *,
                      adc_bits: int = 9, rows: int = 512) -> jnp.ndarray:
    """(M, K) int8 x (K, N) int8 -> (M, N) int32, HURRY array semantics.

    K is processed in row-chunks of ``rows``; each (input-bit,
    weight-bit) plane's chunk count is clipped to the ADC range
    [0, 2^adc_bits - 1] before shift-and-add recombination.
    """
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    M, K = x.shape
    Kw, N = w.shape
    assert K == Kw
    adc_max = (1 << adc_bits) - 1
    xu = x.astype(jnp.int32) & 0xFF
    wu = w.astype(jnp.int32) & 0xFF
    n_chunks = -(-K // rows)
    pad = n_chunks * rows - K
    if pad:
        xu = jnp.pad(xu, ((0, 0), (0, pad)))
        wu = jnp.pad(wu, ((0, pad), (0, 0)))
    xu = xu.reshape(M, n_chunks, rows)
    wu = wu.reshape(n_chunks, rows, N)
    out = jnp.zeros((M, N), jnp.int32)
    for i in range(8):
        xb = (xu >> i) & 1
        sx = -(1 << i) if i == 7 else (1 << i)
        for j in range(8):
            wb = (wu >> j) & 1
            sw = -(1 << j) if j == 7 else (1 << j)
            counts = jnp.einsum("mcr,crn->cmn", xb, wb)
            counts = jnp.clip(counts, 0, adc_max)
            out = out + (sx * sw) * counts.sum(0)
    return out


def crossbar_gemm_exact_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Plain int8 -> int32 GEMM: what the crossbar pipeline must equal
    whenever no chunk can saturate the ADC (``rows <= 2^adc_bits - 1``)."""
    return jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32),
                   preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# packed_gemm: grouped (block-diagonal) GEMM — BAS block packing analogue
# ---------------------------------------------------------------------------

def packed_gemm_ref(x: jnp.ndarray, w: jnp.ndarray,
                    group_sizes: jnp.ndarray) -> jnp.ndarray:
    """x (T, K) tokens sorted by group; w (G, K, N); group_sizes (G,).

    Row t belongs to group g iff cum[g-1] <= t < cum[g]; output
    y[t] = x[t] @ w[group(t)].  (MegaBlocks-style grouped GEMM.)
    """
    T, K = x.shape
    G, Kw, N = w.shape
    bounds = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(bounds, jnp.arange(T), side="right")
    gid = jnp.minimum(gid, G - 1)
    return jnp.einsum("tk,tkn->tn", x, w[gid])


# ---------------------------------------------------------------------------
# fused_gemm_epilogue: GEMM + bias + activation (+ residual)
# ---------------------------------------------------------------------------

def fused_gemm_epilogue_ref(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                            *, act: str = "silu",
                            residual: jnp.ndarray | None = None) -> jnp.ndarray:
    y = jnp.dot(x, w, preferred_element_type=jnp.float32) \
        + b.astype(jnp.float32)
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "silu":
        y = jax.nn.silu(y)
    elif act == "gelu":
        y = jax.nn.gelu(y)
    elif act != "none":
        raise ValueError(act)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# fb_epilogue: fused FB chain over the int32 crossbar GEMM output
# ---------------------------------------------------------------------------

def fb_epilogue_ref(y: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                    residual: jnp.ndarray | None = None, *,
                    act: str = "none", pool: str = "none", window: int = 0,
                    img_hw: int = 0, softmax: bool = False,
                    post_scale: float = 0.0) -> jnp.ndarray:
    """The unfused jnp composition the fb_epilogue kernel must equal:
    dequant -> +bias -> +residual -> [* post_scale] -> ReLU|GELU ->
    pool window | softmax, written with the
    same ops the functional forwards use (``reduce_window`` max pool,
    window-mean avg pool, jax.nn.softmax / jax.nn.gelu).
    """
    M, N = y.shape
    out = y.astype(jnp.float32) * scale.reshape(()) + bias.astype(jnp.float32)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    if post_scale:
        out = out * post_scale
    if act == "relu":
        out = jax.nn.relu(out)
    elif act == "gelu":
        # the tanh-GELU *formula* is the shared definition (fb_epilogue
        # module docstring) — jax.nn.gelu orders the multiply/cube
        # differently, which is 1 ulp away under jit
        from repro.kernels.fb_epilogue import gelu
        out = gelu(out)
    elif act != "none":
        raise ValueError(act)
    if pool != "none":
        b = M // (img_hw * img_hw)
        x4 = out.reshape(b, img_hw, img_hw, N)
        if pool == "max":
            x4 = jax.lax.reduce_window(x4, -jnp.inf, jax.lax.max,
                                       (1, window, window, 1),
                                       (1, window, window, 1), "VALID")
        elif pool == "avg":
            oh = img_hw // window
            x4 = x4.reshape(b, oh, window, oh, window, N).mean(axis=(2, 4))
        else:
            raise ValueError(pool)
        out = x4.reshape(-1, N)
    if softmax:
        out = jax.nn.softmax(out, axis=-1)
    return out


# ---------------------------------------------------------------------------
# flash_attention: Eq. 1 online-stabilized softmax attention
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0) -> jnp.ndarray:
    """q/k/v (B, S, H, hd) -> (B, S, H, hd), fp32 accumulation."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    qpos = jnp.arange(sq)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m = jnp.maximum(jnp.max(scores, -1, keepdims=True), -1e30)
    p = jnp.exp(scores - m)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    denom = jnp.maximum(p.sum(-1), 1e-30)
    return (out / denom[..., None].transpose(0, 2, 1, 3)).astype(q.dtype)
