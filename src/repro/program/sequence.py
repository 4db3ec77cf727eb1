"""Shared sequence-workload helpers: token layout + attention constants.

Pure layout/constant helpers used by BOTH the functional oracle
(``api/graph.py::NetworkGraph.forward``) and the packed executor
(``program/execute.py``), so head splitting, token canonicalization, and
the attention softmax scale can never diverge between the two paths —
the bit-exactness contract of DESIGN.md §5/§9 needs the two sides to
trace identical expressions, and layout ops are the easiest place for a
silent transpose-order divergence to hide.

Windowed attention (Swin) adds its layout and its tables here: the
roll and window partition of the token map and their inverse, the 2x2
space-to-depth of patch merging, the relative position index and the
shift mask (numpy constants of the geometry), and the two adds of the
bias and the mask to the scaled scores, in that order.

Everything here is reshape/transpose/gather, python and numpy
constants, the position table's single add and the scores' two adds,
so sharing is free of FMA-contraction concerns.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def attn_scale(head_dim: int) -> float:
    """The scores scale `1/sqrt(head_dim)` (paper Eq. 1's logit scale)."""
    return 1.0 / math.sqrt(head_dim)


def tokens(x: jnp.ndarray) -> jnp.ndarray:
    """Canonicalize a buffer to the (B, T, D) token layout.

    Spatial NHWC buffers (e.g. a patchify conv output) map row-major:
    token ``t = row * W + col`` — the standard ViT rasterization.  Token
    buffers pass through unchanged.
    """
    if x.ndim == 4:
        return x.reshape(x.shape[0], -1, x.shape[-1])
    return x


def split_qkv_heads(qkv: jnp.ndarray, heads: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, T, 3D) fused-projection buffer -> three (B*heads, T, hd).

    The leading axis is (batch, head) row-major — one entry per mounted
    attention matrix: the executor vmaps its dynamic-operand GEMM over
    it, and the oracle vmaps its ``mm`` the same way.
    """
    B, T, three_d = qkv.shape
    D = three_d // 3
    hd = D // heads

    def sp(u):
        return (u.reshape(B, T, heads, hd).transpose(0, 2, 1, 3)
                .reshape(B * heads, T, hd))

    return sp(qkv[..., :D]), sp(qkv[..., D:2 * D]), sp(qkv[..., 2 * D:])


def merge_heads(ctx: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(B*heads, T, hd) attention context -> (B, T, heads*hd)."""
    bh, T, hd = ctx.shape
    B = bh // heads
    return (ctx.reshape(B, heads, T, hd).transpose(0, 2, 1, 3)
            .reshape(B, T, heads * hd))


def embed_tokens(x: jnp.ndarray, cls: jnp.ndarray, pos: jnp.ndarray
                 ) -> jnp.ndarray:
    """(B, T, D) patch tokens -> (B, T+1, D): the class token ``cls``
    (D,) prepended as token 0, then the position table ``pos``
    (T+1, D) added (ViT's ``[x_cls; x E] + E_pos``)."""
    b, _, d = x.shape
    head = jnp.broadcast_to(cls.astype(x.dtype).reshape(1, 1, d), (b, 1, d))
    return jnp.concatenate([head, x], axis=1) + pos


# -- windowed attention (Swin, Liu et al., arXiv:2103.14030, §3.2) --------

def rel_index(window: int) -> np.ndarray:
    """(M², M²) row of the relative position bias table for each pair of
    tokens of an ``M x M`` window: ``(dy + M-1) * (2M-1) + dx + M-1``
    with ``(dy, dx)`` the query's position less the key's (Swin's
    ``relative_position_index``)."""
    m = window
    yy, xx = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    coords = np.stack([yy.ravel(), xx.ravel()])          # (2, M²)
    rel = coords[:, :, None] - coords[:, None, :] + (m - 1)
    return (rel[0] * (2 * m - 1) + rel[1]).astype(np.int32)


def rel_bias(table: jnp.ndarray, window: int) -> jnp.ndarray:
    """A ``((2M-1)², heads)`` bias table gathered to ``(heads, M², M²)``."""
    m2 = window * window
    idx = rel_index(window).reshape(-1)
    return table[idx].reshape(m2, m2, -1).transpose(2, 0, 1)


def shift_mask(grid: int, window: int, shift: int) -> np.ndarray | None:
    """``(nW, M², M²)`` scores mask of a cyclic shift: -100 between
    tokens of different regions of the rolled map (Swin's nine regions),
    0 elsewhere; None without a shift."""
    if not shift:
        return None
    region = np.zeros((grid, grid), np.int32)
    cuts = (slice(0, -window), slice(-window, -shift), slice(-shift, None))
    n = 0
    for h in cuts:
        for w in cuts:
            region[h, w] = n
            n += 1
    g = grid // window
    win = (region.reshape(g, window, g, window).transpose(0, 2, 1, 3)
           .reshape(g * g, window * window))
    return np.where(win[:, None, :] != win[:, :, None], -100.0,
                    0.0).astype(np.float32)


def _windows(x: jnp.ndarray, grid: int, window: int, shift: int
             ) -> jnp.ndarray:
    """(B, T, N) raster tokens -> (B, nW, M², N): the map rolled by
    ``-shift`` on both axes, then cut into ``M x M`` windows, row-major."""
    b, _, n = x.shape
    g = grid // window
    x = x.reshape(b, grid, grid, n)
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    x = x.reshape(b, g, window, g, window, n).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, g * g, window * window, n)


def window_qkv_heads(qkv: jnp.ndarray, heads: int, grid: int, window: int,
                     shift: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, T, 3D) fused projection of raster tokens -> three
    (B*nW*heads, M², hd): each window of the rolled map, per head; the
    leading axis is (image, window, head) row-major, one entry per
    mounted attention matrix."""
    w = _windows(qkv, grid, window, shift)
    b, nw, m2, three_d = w.shape
    d = three_d // 3
    hd = d // heads

    def sp(u):
        return (u.reshape(b, nw, m2, heads, hd).transpose(0, 1, 3, 2, 4)
                .reshape(b * nw * heads, m2, hd))

    return sp(w[..., :d]), sp(w[..., d:2 * d]), sp(w[..., 2 * d:])


def window_merge(ctx: jnp.ndarray, heads: int, grid: int, window: int,
                 shift: int) -> jnp.ndarray:
    """(B*nW*heads, M², hd) windowed context -> (B, T, heads*hd) raster
    tokens: heads rejoined, windows put back and the roll undone."""
    _, m2, hd = ctx.shape
    g = grid // window
    x = ctx.reshape(-1, g * g, heads, m2, hd).transpose(0, 1, 3, 2, 4)
    b = x.shape[0]
    x = (x.reshape(b, g, g, window, window, heads * hd)
         .transpose(0, 1, 3, 2, 4, 5).reshape(b, grid, grid, heads * hd))
    if shift:
        x = jnp.roll(x, (shift, shift), axis=(1, 2))
    return x.reshape(b, grid * grid, heads * hd)


def window_bias(scores: jnp.ndarray, bias: jnp.ndarray,
                mask: np.ndarray | None) -> jnp.ndarray:
    """Scaled scores of each image's windows and heads, (B, nW*heads,
    M², M²), plus the relative position bias (heads, M², M²), then plus
    the shift mask (nW, M², M²): two adds, in that order (DESIGN.md §9).
    Both tables are tiled to one image's windows and heads and added
    across the images, a broadcast over a major axis only."""
    heads = bias.shape[0]
    s = scores + jnp.tile(bias, (scores.shape[1] // heads, 1, 1))
    if mask is not None:
        s = s + np.repeat(mask, heads, axis=0)
    return s


def space_to_depth(x: jnp.ndarray, grid: int) -> jnp.ndarray:
    """(B, T, C) raster tokens of a ``grid x grid`` map -> (B, T/4, 4C):
    each 2x2 neighbourhood as one token, concatenated in Swin's patch
    merging order x0 = (0::2, 0::2), x1 = (1::2, 0::2), x2 = (0::2,
    1::2), x3 = (1::2, 1::2)."""
    b, _, c = x.shape
    x = x.reshape(b, grid, grid, c)
    parts = [x[:, i::2, j::2] for i, j in ((0, 0), (1, 0), (0, 1), (1, 1))]
    return jnp.concatenate(parts, axis=-1).reshape(b, -1, 4 * c)
