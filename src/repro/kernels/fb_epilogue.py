"""Fused functional-block epilogue Pallas kernel (HURRY FB post-ops).

The numeric analogue of HURRY's in-array functional blocks (paper §II-C):
after the crossbar GEMM (`crossbar_gemm.py`) produces an int32 tile, the
consumer FBs — shift-and-add requantization, bias, residual merge (Fig
4a), ReLU/max-pool tournaments (Fig 4b/c), softmax (Eq. 1) — execute in
ONE pass while the tile is still VMEM-resident, so the GEMM output never
round-trips through a separate jnp op.  This extends
`fused_gemm_epilogue.py` (which fuses fp GEMM + activation) to the
crossbar's int32 -> f32 dequant chain and to window reductions.

The sequence workload class (DESIGN.md §9) adds one FB op to the
kernel: **GELU** (a LUT activation like the softmax exp; the tanh
form).  A static ``post_scale`` factor multiplies the dequantized tile
before the activation — attention programs fold `1/sqrt(head_dim)`
into the scores stage there, keeping the float op order identical to
the functional oracle's ``softmax(scores * sm_scale)``.  The other
sequence tails are no modes of the kernel: the executor evaluates them
with XLA on the kernel's output, as fixed expression trees the oracle
and the benchmark's reference write too (DESIGN.md §9) — the exact GELU
(``gelu_erf``; Mosaic has no lowering for ``erf``), every layer norm
(``layer_norm_ordered``), attention's softmax (``softmax_ordered``),
and the mean over tokens (``token_mean``, in the operand build of the
head that reads it), their row sums in one pairwise order
(``ordered_sum``).

Op order is the canonical FB chain order (the only order the paper's /
transformer workloads produce, validated by the program compiler):

    dequant (SnA scale) -> + bias -> + residual -> [* post_scale]
        -> ReLU | GELU -> max/avg pool window  OR  softmax

The numeric bodies of the non-trivial FB ops (``gelu``,
``softmax_rows``) are module-level jnp functions so the functional
oracle (`api/graph.py::NetworkGraph.forward`) evaluates the *same
expression tree* — bit-identical under jit (DESIGN.md §5).

Pooling layout: rows of the (M, N) GEMM output are im2col vectors in
(image, row, col) order, so one grid step owns whole images' ``ih*ih``
rows and reduces ``window x window`` blocks via a leading-axis reshape
— the column-parallel window tiling of Fig 5c.  Only ``stride ==
window`` (non-overlapping) pooling is supported, which covers the
paper's workloads (2x2/2 max pool, 4x4/4 global avg pool).  A step
takes the fewest images whose output rows fill a multiple of 8
sublanes (``_groups_per_step``; the TPU tiling refuses an output block
of 1 or 4 rows), or all of them when there are fewer; a batch that is
not a multiple of that count is padded with zero images.  Softmax
needs the full feature axis in-tile, so ``block_n`` is forced to N in
that mode.

The per-column bias enters the kernel as a ``(1, N)`` row with
``(1, block_n)`` blocks: a 1-D block has no layout the TPU tiling and
XLA agree on.

Edge blocks over rows: the grid takes ``cdiv(M, block_m)`` row blocks
of the operands as they are, and the output is exactly (M, N).  Where M
does not divide the row block, the last block runs past the array: its
out-of-range rows read unspecified values and are dropped when written.
The FB chain treats every row on its own, so each kept row is computed
exactly as in a divisible grid; an M that fits one block is one block
of the whole dimension.  Columns have no edge: an N that ``block_n``
does not divide takes one full-width block (``block_n = N``, which the
TPU tiling always accepts), as softmax always does (it needs the full
feature axis in-tile).  The pooled modes fix M to ``B * img_hw^2`` and
pad the batch by whole images to their images per step, then slice the
padding off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import default_blocks

_GELU_C = 0.7978845608028654          # sqrt(2/pi)
LN_EPS = 1e-5


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """Tanh-approximated GELU — the LUT-friendly form HURRY's exp/log
    block evaluates.  Shared by the kernel and the functional oracle so
    both sides trace the identical expression (DESIGN.md §5)."""
    return 0.5 * x * (1.0 + jnp.tanh(_GELU_C * (x + 0.044715 * x * x * x)))


def gelu_erf(x: jnp.ndarray) -> jnp.ndarray:
    """Exact GELU, ``x * Phi(x)`` (ViT, DeiT).  Not a kernel mode:
    Mosaic cannot lower ``erf``, so the executor evaluates this with XLA
    on the kernel's output, the expression the oracle traces."""
    return 0.5 * x * (1.0 + jax.lax.erf(x * 0.7071067811865476))


def ordered_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum over ``axis`` (kept) in one fixed pairwise order, as
    elementwise adds of its halves, an odd last entry carried after
    them.  XLA rounds an elementwise add the same in any fusion and
    layout, while the order of its ``reduce`` follows the layout it
    picks for the operand: a row sum of an XLA tail (below) must round
    as the reference's does (DESIGN.md §9).  The axis only says where
    the summed entries lie: every axis adds the same pairs in the same
    order, so the sums are bit-identical."""
    while x.shape[axis] > 1:
        n = x.shape[axis]
        h = n // 2
        y = (jax.lax.slice_in_dim(x, 0, h, axis=axis)
             + jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
        x = jnp.concatenate([y, jax.lax.slice_in_dim(x, 2 * h, n, axis=axis)],
                            axis=axis)
    return x


def layer_norm_ordered(x: jnp.ndarray, gamma: jnp.ndarray,
                       beta: jnp.ndarray, eps: float = LN_EPS
                       ) -> jnp.ndarray:
    """Layer norm over the last axis with ``ordered_sum`` row sums, then
    scale and shift: every layer norm of a sequence network, which XLA
    evaluates in the operand build of the stage it feeds (a pre-norm) or
    on the epilogue kernel's output (a post-norm, the patch norm)."""
    n = x.shape[-1]
    m = ordered_sum(x) / n
    d = x - m
    v = ordered_sum(d * d) / n
    return d / jnp.sqrt(v + eps) * gamma + beta


def softmax_ordered(x: jnp.ndarray) -> jnp.ndarray:
    """``softmax_rows`` with an ``ordered_sum`` denominator: attention's
    softmax, which XLA evaluates on the epilogue kernel's output."""
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    return e / ordered_sum(e)


def token_mean(x: jnp.ndarray) -> jnp.ndarray:
    """(B, T, D) tokens -> (B, D): the mean over the tokens, its sum an
    ``ordered_sum`` (a mean-pooled head, which XLA evaluates in the
    operand build of the stage that reads the pool)."""
    return ordered_sum(jnp.swapaxes(x, 1, 2))[..., 0] / x.shape[1]


def softmax_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Max-subtracted per-row softmax (paper Eq. 1's stabilization).

    Structurally identical to ``jax.nn.softmax`` so either spelling
    compiles to the same HLO; the oracle's attention path uses this one
    to make the sharing explicit.
    """
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _kernel(y_ref, scale_ref, b_ref, res_ref, o_ref, *, act: str,
            pool: str, window: int, img_hw: int, softmax: bool,
            post_scale: float, has_residual: bool):
    y = (y_ref[...].astype(jnp.float32) * scale_ref[0, 0]
         + b_ref[...].astype(jnp.float32))
    if has_residual:
        y = y + res_ref[...].astype(jnp.float32)
    if post_scale:
        y = y * post_scale
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "gelu":
        y = gelu(y)
    bn = y.shape[-1]
    if pool != "none":
        oh = img_hw // window
        y = y.reshape(-1, oh, window, oh, window, bn)
        y = jnp.max(y, axis=(2, 4)) if pool == "max" else jnp.mean(y, axis=(2, 4))
        y = y.reshape(-1, bn)
    if softmax:
        y = softmax_rows(y)
    o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("act", "pool", "window",
                                             "img_hw", "softmax",
                                             "post_scale", "block_m",
                                             "block_n", "interpret"))
def fb_epilogue(y: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                residual: jnp.ndarray | None = None, *, act: str = "none",
                pool: str = "none", window: int = 0, img_hw: int = 0,
                softmax: bool = False, post_scale: float = 0.0,
                block_m: int | None = None,
                block_n: int | None = None,
                interpret: bool = False) -> jnp.ndarray:
    """y (M, N) int32 crossbar output -> fused FB chain -> f32.

    ``scale`` is the (1, 1) f32 shift-and-add requant factor (input scale
    x weight scale); ``bias`` is (N,) (passed on as a (1, N) row).
    ``act`` in {"none", "relu", "gelu"}; ``pool`` in {"none", "max",
    "avg"} — ``window == stride`` over an ``img_hw x img_hw`` spatial
    grid per image (M = B * img_hw^2, output
    (B * (img_hw//window)^2, N)).  ``post_scale`` (static) multiplies
    the dequantized tile before the activation — attention scores fold
    `1/sqrt(hd)` here.
    ``softmax=True`` (exclusive with pool) normalizes over the full
    feature axis -> (M, N).  Block sizes default to ``tiling.py``'s
    epilogue entry.
    """
    M, N = y.shape
    assert scale.shape == (1, 1) and bias.shape == (N,)
    assert act in ("none", "relu", "gelu")
    assert pool in ("none", "max", "avg")
    has_residual = residual is not None
    res = residual if has_residual else jnp.zeros((1, 1), jnp.float32)
    bias = bias.reshape(1, N)

    dm, dn = default_blocks("epilogue")
    block_m, block_n = block_m or dm, block_n or dn
    # one full-width column block where block_n does not divide N
    if softmax or N % min(block_n, N):
        block_n = N              # the row reduction needs every column
    block_n = min(block_n, N)
    pm = 0
    if pool == "none":           # edge row blocks (module docstring)
        block_m = min(block_m, M)
        grid = (pl.cdiv(M, block_m), N // block_n)
        row_spec = out_spec = pl.BlockSpec((block_m, block_n),
                                           lambda i, j: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)
    else:                        # whole images per step
        assert not softmax, "pool and softmax FBs never chain directly"
        assert window > 1 and img_hw % window == 0, (img_hw, window)
        group_rows, out_rows = img_hw * img_hw, (img_hw // window) ** 2
        assert M % group_rows == 0, (M, img_hw)
        n_groups = M // group_rows
        k = _groups_per_step(n_groups, out_rows)
        pm = -n_groups % k * group_rows
        if pm:                   # pad by whole zero images
            y = jnp.pad(y, ((0, pm), (0, 0)))
            if has_residual:
                res = jnp.pad(res, ((0, pm), (0, 0)))
        n_steps = (M + pm) // (k * group_rows)
        grid = (n_steps, N // block_n)
        row_spec = pl.BlockSpec((k * group_rows, block_n),
                                lambda i, j: (i, j))
        out_spec = pl.BlockSpec((k * out_rows, block_n), lambda i, j: (i, j))
        out_shape = jax.ShapeDtypeStruct((n_steps * k * out_rows, N),
                                         jnp.float32)

    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    col_spec = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    kernel = functools.partial(_kernel, act=act, pool=pool, window=window,
                               img_hw=img_hw, softmax=softmax,
                               post_scale=post_scale,
                               has_residual=has_residual)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row_spec,
            one,
            col_spec,
            row_spec if has_residual else one,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        # names the custom call (``%fb_epilogue.N``) in the compiled
        # HLO, which trace readers match on
        name="fb_epilogue",
    )(y, scale, bias, res)
    return out[:n_groups * out_rows] if pm else out


def _groups_per_step(n_groups: int, out_rows: int) -> int:
    """Images per pooled grid step.

    The fewest whose ``out_rows``-row outputs fill a multiple of 8
    sublanes, or all ``n_groups`` when there are fewer (a block equal to
    the whole array is always accepted).  The caller pads the batch to
    a multiple of the result.
    """
    return min(8 // math.gcd(8, out_rows), n_groups)
