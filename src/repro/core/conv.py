"""Convolution primitives shared by the functional oracle and the executor.

Convolutions are expressed as im2col + GEMM, so the oracle
(``repro.api.NetworkGraph.forward``) can route every GEMM through any
``mm`` (fp32 or the crossbar functional model), and the compiled
program's executor cuts its operands with the same ``im2col`` the
oracle traces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .crossbar import MatmulFn


def im2col(x: jnp.ndarray, k: int, stride: int, pad: int, *,
           channels_minor: bool = False) -> jnp.ndarray:
    """NHWC -> (N, OH, OW, C*k*k) patches, feature order (c, i, j), or
    (i, j, c) with ``channels_minor``.

    Built from ``k*k`` slices — pure data movement, so every patch entry
    is an exact copy of an input element on every backend (a patches
    *convolution* at default precision rounds its f32 inputs to bf16 on
    the TPU).  The one im2col of the repo: the compiled program's
    executor imports it, so program and oracle trace the same
    expression.  ``channels_minor`` concatenates the taps along the
    channels instead of interleaving them at stride ``k*k``, so the
    channels stay the minor, lane-dense axis; it also takes a strided
    conv's taps as contiguous slices of the padded input split into
    stride phases (``(H, W, C) -> (H/s, s, W/s, s*C)``, one reshape)
    rather than as strided slices, which the TPU compiler turns into
    gathers.
    """
    n, h, w, c = x.shape
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    if not channels_minor:
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        taps = [xp[:, i:i + stride * (oh - 1) + 1:stride,
                   j:j + stride * (ow - 1) + 1:stride, :]
                for i in range(k) for j in range(k)]
        # (N, OH, OW, C, k*k) -> (N, OH, OW, C*k*k)
        return jnp.stack(taps, axis=-1).reshape(n, oh, ow, c * k * k)
    s = stride
    if k == s and pad == 0:          # non-overlapping patches
        x = x[:, :oh * k, :ow * k].reshape(n, oh, k, ow, k, c)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, oh, ow, k * k * c)
    if s == 1:
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        taps = [xp[:, i:i + oh, j:j + ow] for i in range(k) for j in range(k)]
    else:
        # pad, round H and W up to whole stride phases (never read), split
        hp, wp = -(-(h + 2 * pad) // s) * s, -(-(w + 2 * pad) // s) * s
        xp = jnp.pad(x, ((0, 0), (pad, hp - h - pad), (pad, wp - w - pad),
                         (0, 0))).reshape(n, hp // s, s, wp // s, s * c)
        taps = [xp[:, i // s:i // s + oh, i % s, j // s:j // s + ow,
                   (j % s) * c:(j % s + 1) * c]
                for i in range(k) for j in range(k)]
    return jnp.concatenate(taps, axis=-1) if len(taps) > 1 else taps[0]


def im2col_read_mask(h: int, w: int, k: int, stride: int,
                     pad: int) -> np.ndarray | None:
    """Which pixels of an ``(h, w)`` input ``im2col(x, k, stride, pad)``
    copies into its patches: an ``(h, w, 1)`` mask, or None when it
    reads them all (any stride-1 conv).  A 1x1/2 projection reads every
    other pixel of each axis.  The ``max(|.|)`` over the read pixels is
    the patch matrix's: padding adds only zeros."""
    def axis(n):
        out = (n + 2 * pad - k) // stride + 1
        read = np.zeros(n, bool)
        for i in range(k):
            idx = i + stride * np.arange(out) - pad
            read[idx[(idx >= 0) & (idx < n)]] = True
        return read
    mask = axis(h)[:, None] & axis(w)[None, :]
    return None if mask.all() else mask[:, :, None]


def conv2d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, stride: int,
           pad: int, mm: MatmulFn) -> jnp.ndarray:
    """w: (k, k, Cin, Cout) applied via im2col GEMM."""
    k = w.shape[0]
    cols = im2col(x, k, stride, pad)                    # (N,OH,OW,Cin*k*k)
    n, oh, ow, kk = cols.shape
    wm = w.transpose(2, 0, 1, 3).reshape(kk, -1)        # (Cin*k*k, Cout)
    y = mm(cols.reshape(-1, kk), wm).reshape(n, oh, ow, -1)
    return y + b


def maxpool(x: jnp.ndarray, k: int = 2, stride: int = 2) -> jnp.ndarray:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, stride, stride, 1), "VALID")
