"""Bit-sliced crossbar GEMM Pallas kernel — the paper-faithful compute.

Implements HURRY's in-array int8 GEMM semantics on the TPU: two's-
complement bit planes of the weights x bit-serial input phases, each
plane-pair's partial count clipped to the ADC range before shift-and-add.
The hardware adaptation (DESIGN.md §3): analog bitline integration
becomes an int32 MXU accumulation over {0,1} planes; the row-chunking
that ReRAM does across stacked arrays becomes the K-grid dimension, and
ADC saturation applies per chunk exactly as per array.

Two statically-dispatched compute paths (DESIGN.md §"Exact fast path"):

* **Plane-packed sliced path** (the faithful route): the 8 input bit
  planes are stacked along the M axis and the 8 weight bit planes along
  the N axis, so each tile performs ONE ``(8*bm, rows) x (rows, 8*bn)``
  int32 ``dot_general`` instead of 64 separate plane-pair dots.  The
  resulting ``(8*bm, 8*bn)`` counts block is clipped to the ADC range in
  one vectorized op, then recombined with a single weighted contraction
  against the ``s_i * s_j`` shift-and-add scale table.  Bit-slice
  recombination is linear digital post-processing (ISAAC lineage /
  FPSA), so batching the plane loop this way is semantics-preserving:
  every bitline count is still digitized independently before SnA.

* **Exact fast path** (``exact=True`` or auto-detected): when
  ``rows <= 2^adc_bits - 1`` each plane-pair chunk count — a sum of at
  most ``rows`` products of {0,1} bits — is already within ADC range,
  so the clip is a provable no-op and the whole pipeline collapses to a
  plain int8 -> int32 GEMM accumulated over K chunks.  This is
  bit-identical to the sliced path (HURRY's own 512-row / 9-bit pairing
  is clip-free except for ``rows == 512 == 2^9``; see
  ``clip_possible``).  When clipping *can* fire the fast path is
  refused and the sliced path runs.

Grid: (M/bm, N/bn, n_k) — each K block is one mount (one "array"
read), or one block of the dense layout below; both paths do a single
MXU dispatch per tile.

**Mount layout.** A stage's ``tile_rows`` is the array height minus the
rows its functional blocks reserve, so it is rarely a multiple of the
TPU's 128-lane tiling (485, 493, ...), and Mosaic refuses a K block of
that height.  Operands therefore carry K in the *mount layout*
(``mount_layout``): each mount's ``rows`` real rows followed by zero
rows up to ``mount_rows(rows)``, the next multiple of 128.  Zero rows
add nothing to any bitline count (``clip(0) == 0``), so a K block of
``mount_rows(rows)`` keeps per-mount ADC semantics over exactly ``rows``
real rows.  A contraction that fits one mount (``K <= rows``) keeps its
full length as the block, which Mosaic accepts at any size.
``pack.plane_pack`` lays weights out this way once at compile time;
``mounted_gemm`` takes such weights and lays out the streamed
activation itself, and ``crossbar_gemm`` also lays out the weights for
callers holding plain ``(K, N)`` operands.

**Dense layout.** Where clipping cannot fire (``clip_possible`` is
False), the int32 sums are the same for any K order and any K
blocking, so mount boundaries mean nothing and their padding is pure
overhead (+78 % K for a 576-row contraction in 485-row mounts).  Such
operands carry K in the *dense layout* (``dense_layout``): the whole
contraction as one block when it is at most ``DENSE_BLOCK_K`` rows,
else K zero-padded at its end to whole blocks of a multiple of 128 rows
(``dense_blocks``).  ``mounted_gemm(..., layout="dense")`` takes an
activation in that order (only its end is padded, never re-mounted) and
runs the exact kernel, one K block per grid step.

Edge blocks: the grid is ``cdiv(M, block_m) x cdiv(N, block_n)`` over
the operands as they are, and the output is exactly (M, N).  Where M or
N does not divide its block, the last block runs past the array: on the
TPU its out-of-range part reads unspecified values and is dropped when
written (interpret mode pads it likewise).  Every kept int32 element
depends only on its own row of ``x``, its own column of ``w`` and the
whole K axis, so it is computed exactly as in a divisible grid on both
compute paths; K itself is never ragged (both layouts pad it with
zeros).  The exact path takes a block of the whole dimension where it
fits one block, which has no edge at all; the sliced path's plane
reshapes need tiles on the (8, 128) tiling, so its block is the
dimension rounded up to it.  One pad is kept, the lane pad: an N under
one 128-lane tile (a 64-channel conv, a 10-class head, attention's
P·V) has its weight columns padded to 128 and the result sliced back.
Such an output fills whole lane tiles in HBM either way, so the slice
is a bitcast of the same tiled bytes, and the kernel writes whole tiles.

``block_m``/``block_n`` default per compute path (``tiling.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import default_blocks

def _plane_weights(shape, dim):
    """Two's-complement plane weights 2^i (MSB negative) along ``dim``.

    Built from iota arithmetic because Pallas kernels cannot capture
    array constants, and 1D iota fails on TPU.
    """
    i = jax.lax.broadcasted_iota(jnp.int32, shape, dim)
    return jnp.where(i == 7, jnp.int32(-128), jnp.left_shift(jnp.int32(1), i))


def clip_possible(rows: int, adc_bits: int) -> bool:
    """True iff an ADC clip can ever fire for ``rows``-row chunks.

    A bitline count is ``sum_row x_bit * w_bit`` over at most ``rows``
    1-bit products, hence ``count <= rows``; the ADC digitizes
    ``[0, 2^adc_bits - 1]`` exactly.  Clipping is therefore impossible —
    and the bit-sliced pipeline exactly equals a plain int GEMM — iff
    ``rows <= 2^adc_bits - 1``.
    """
    return rows > (1 << adc_bits) - 1


def _kernel_sliced(x_ref, w_ref, o_ref, acc_ref, *, adc_max: int, n_k: int):
    """Plane-packed faithful path: 1 MXU dot per tile for all 64 planes."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xu = x_ref[...].astype(jnp.int32) & 0xFF            # (bm, R)
    wu = w_ref[...].astype(jnp.int32) & 0xFF            # (R, bn)
    bm, rows = xu.shape
    bn = wu.shape[1]
    # (1D iota fails on TPU — broadcast the bit index to the full rank)
    xbits = jax.lax.broadcasted_iota(jnp.int32, (8, 1, 1), 0)
    wbits = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    # input planes stacked along M: (8, bm, R) -> (8*bm, R)
    xb = ((xu[None, :, :] >> xbits) & 1).reshape(8 * bm, rows)
    # weight planes stacked along N: (R, 8, bn) -> (R, 8*bn)
    wb = ((wu[:, None, :] >> wbits) & 1).reshape(rows, 8 * bn)
    # All 64 analog bitline count blocks in ONE MXU pass.  f32 is exact
    # here — {0,1} products, counts <= rows << 2^24 — and hits the fast
    # matmul path on every backend (int32 dot has none on CPU).
    counts = jax.lax.dot_general(
        xb.astype(jnp.float32), wb.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    counts = jnp.clip(counts, 0, adc_max)               # ADC digitization
    # SnA partial sums can exceed 2^24, so recombine in int32.
    counts = counts.astype(jnp.int32).reshape(8, bm, 8, bn)
    # SnA recombination table s_i * s_j, one weighted contraction over planes
    scale = (_plane_weights((8, 1, 1, 1), 0)
             * _plane_weights((1, 1, 8, 1), 2))
    acc_ref[...] += (counts * scale).sum(axis=(0, 2))

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _kernel_exact(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, f32_dot: bool):
    """Clip-free fast path: plain int8 -> int32 GEMM, no bit slicing.

    When the per-block partial sum provably fits f32's integer range
    (``block_k * 128 * 128 <= 2^24``, i.e. K blocks of at most 1024
    rows, which both layouts keep to on the exact path) the block dot
    runs in f32 — bit-exact, and it hits the fast matmul path on every
    backend (int32 dot has none on CPU) — with cross-block accumulation
    still in int32.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if f32_dot:
        y = jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
    else:
        y = jax.lax.dot_general(
            x_ref[...].astype(jnp.int32), w_ref[...].astype(jnp.int32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    acc_ref[...] += y

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


LANE = 128          # TPU lane width: K blocks are multiples of it


def mount_rows(rows: int) -> int:
    """Height of one mount in the mount layout: ``rows`` rounded up to
    the 128-lane tiling (module docstring)."""
    return -(-rows // LANE) * LANE


def mount_layout(a: jnp.ndarray, rows: int, axis: int) -> jnp.ndarray:
    """Lay ``a``'s contraction ``axis`` out as full mounts.

    K is cut into ``ceil(K / rows)`` mounts of ``rows`` real rows (the
    last zero-filled), and each mount is zero-padded to
    ``mount_rows(rows)``.  A contraction that fits one mount is
    returned unchanged: its block is the whole axis.
    """
    k = a.shape[axis]
    if k <= rows:
        return a
    n, height = -(-k // rows), mount_rows(rows)
    widths = [(0, 0)] * (a.ndim + 1)
    widths[axis] = (0, n * rows - k)
    a = jnp.pad(a, widths[:-1])
    a = a.reshape(a.shape[:axis] + (n, rows) + a.shape[axis + 1:])
    if height != rows:
        widths[axis] = (0, 0)
        widths[axis + 1] = (0, height - rows)
        a = jnp.pad(a, widths)
    return a.reshape(a.shape[:axis] + (n * height,) + a.shape[axis + 2:])


# the tallest K block whose f32 chunk dot stays exact: 1024 * 128 * 128
# = 2^24 (``_kernel_exact``)
DENSE_BLOCK_K = 1024


def dense_blocks(k: int) -> tuple[int, int]:
    """(padded K, K block) of the dense layout of a ``k``-row contraction.

    Up to ``DENSE_BLOCK_K`` rows K is one block, unpadded.  Beyond it, K
    is cut into blocks of a multiple of 128 rows, at most
    ``DENSE_BLOCK_K``: among the fewest such blocks up to twice as many,
    the count that pads K least (4608 rows: six blocks of 768, no
    padding).
    """
    if k <= DENSE_BLOCK_K:
        return k, k
    tiles = -(-k // LANE)
    fewest = -(-tiles // (DENSE_BLOCK_K // LANE))
    n = min(range(fewest, 2 * fewest + 1),
            key=lambda n: (-(-tiles // n) * n, n))
    block = -(-tiles // n) * LANE
    return n * block, block


def dense_layout(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Zero-pad ``a``'s contraction ``axis`` to its dense-layout length
    (``dense_blocks``); rows keep their order."""
    k = a.shape[axis]
    kp, _ = dense_blocks(k)
    if kp == k:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, kp - k)
    return jnp.pad(a, widths)


def crossbar_gemm(x: jnp.ndarray, w: jnp.ndarray, *, adc_bits: int = 9,
                  rows: int = 512, block_m: int | None = None,
                  block_n: int | None = None, interpret: bool = False,
                  exact: bool | None = None) -> jnp.ndarray:
    """(M, K) int8 x (K, N) int8 -> (M, N) int32 with HURRY semantics.

    ``mounted_gemm`` on the weights put into the mount layout here (its
    docstring has the dispatch rules).
    """
    return mounted_gemm(x, mount_layout(w, rows, 0), adc_bits=adc_bits,
                        rows=rows, block_m=block_m, block_n=block_n,
                        interpret=interpret, exact=exact)


@functools.partial(jax.jit, static_argnames=("adc_bits", "rows", "block_m",
                                             "block_n", "interpret", "exact",
                                             "layout"))
def mounted_gemm(x: jnp.ndarray, w: jnp.ndarray, *, adc_bits: int = 9,
                 rows: int = 512, block_m: int | None = None,
                 block_n: int | None = None, interpret: bool = False,
                 exact: bool | None = None,
                 layout: str = "mounted") -> jnp.ndarray:
    """(M, K) int8 activation x laid-out int8 weights -> (M, N) int32.

    ``layout="mounted"`` (default): ``w`` is in the mount layout of
    ``rows``-row mounts (``mount_layout``, as ``pack.plane_pack`` stores
    it); ``x`` is laid out the same way here.  ``rows`` is the real row
    count of each mount (the ADC chunk).  ``exact=None`` auto-dispatches:
    the clip-free single-GEMM fast path when ``rows <= 2^adc_bits - 1``
    (bit-identical, see ``clip_possible``), else the plane-packed sliced
    path.  ``exact=False`` forces the faithful sliced path;
    ``exact=True`` asserts clip-freeness and raises if ADC saturation
    could fire.

    ``layout="dense"``: ``w`` is in the dense layout (``dense_layout``)
    and ``x`` holds the same K rows in the same order, unpadded; only
    its end is zero-padded here.  Dense operands are exact by contract:
    the caller's mounts of ``rows`` rows are clip-free (raises
    otherwise, and for ``exact=False``), and K blocks follow
    ``dense_blocks``, not mounts.

    Block sizes default per path (``tiling.py``).  M and N need not
    divide them: edge blocks run past the array, and the output is
    exactly (M, N); only an N under 128 lanes is padded to them and
    sliced back (see module docstring).

    Its ops sit in two named scopes: ``mount`` (the activation's mount
    layout or its dense K pad, the lane pad and its slice back) and
    ``gemm`` (the kernel).
    """
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    M, K = x.shape
    pk = 0
    if layout == "dense":
        if exact is False or clip_possible(rows, adc_bits):
            raise ValueError(
                f"the dense layout is exact-only: rows={rows} with a "
                f"{adc_bits}-bit ADC can clip, or exact=False was asked")
        exact = True
        kp, block_k = dense_blocks(K)
        pk = kp - K
    elif layout == "mounted":
        rows = min(rows, K)
        with jax.named_scope("mount"):
            x = mount_layout(x, rows, 1)
        K = x.shape[1]
        block_k = min(K, mount_rows(rows))
        if exact is None:
            exact = not clip_possible(rows, adc_bits)
        elif exact and clip_possible(rows, adc_bits):
            raise ValueError(
                f"exact=True but ADC clipping can fire: rows={rows} > "
                f"2^{adc_bits} - 1 = {(1 << adc_bits) - 1}; use the sliced "
                "path")
    else:
        raise ValueError(f"layout {layout!r} not in ('mounted', 'dense')")
    Kw, N = w.shape
    if K + pk != Kw:
        raise ValueError(f"weights have {Kw} rows; the activation laid out "
                         f"{layout} has {K + pk} (see {layout}_layout)")
    Np = max(N, LANE)            # the lane pad (module docstring)
    with jax.named_scope("mount"):
        if pk:
            x = jnp.pad(x, ((0, 0), (0, pk)))
        if Np > N:
            w = jnp.pad(w, ((0, 0), (0, Np - N)))
    bm, bn = default_blocks("exact" if exact else "sliced")
    # a dimension that fits one block is that block; the sliced path's
    # plane reshapes need it rounded up to the (8, 128) tiling
    tm, tn = (1, 1) if exact else (8, LANE)
    block_m = min(block_m or bm, -(-M // tm) * tm)
    block_n = min(block_n or bn, -(-Np // tn) * tn)
    n_k = Kw // block_k
    if exact:
        # f32 block dots are exact iff |partial| <= block_k * 128^2 <= 2^24
        kernel = functools.partial(_kernel_exact, n_k=n_k,
                                   f32_dot=block_k * 128 * 128 <= 1 << 24)
    else:
        kernel = functools.partial(_kernel_sliced,
                                   adc_max=(1 << adc_bits) - 1, n_k=n_k)
    with jax.named_scope("gemm"):
        y = pl.pallas_call(
            kernel,
            grid=(pl.cdiv(M, block_m), pl.cdiv(Np, block_n), n_k),
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, Np), jnp.int32),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
            interpret=interpret,
            # names the custom call (``%mounted_gemm.N``) in the
            # compiled HLO, which trace readers match on
            name="mounted_gemm",
        )(x, w)
    with jax.named_scope("mount"):
        return y[:, :N] if Np > N else y
