"""Shared sequence-workload helpers: token layout + attention constants.

Pure layout/constant helpers used by BOTH the functional oracle
(``api/graph.py::NetworkGraph.forward``) and the packed executor
(``program/execute.py``), so head splitting, token canonicalization, and
the attention softmax scale can never diverge between the two paths —
the bit-exactness contract of DESIGN.md §5/§9 needs the two sides to
trace identical expressions, and layout ops are the easiest place for a
silent transpose-order divergence to hide.

Everything here is reshape/transpose plus one python float constant
and the position table's single add, so sharing is free of
FMA-contraction concerns.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


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
