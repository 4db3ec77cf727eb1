"""Functional model of a ReRAM crossbar performing bit-sliced analog GEMM.

This is the faithful compute model of HURRY's in-situ array (paper §II):

* 1-bit cells (paper §II-B gives three reasons; we model exactly that).
* Weights (signed int, default 8-bit) are decomposed into two's-complement
  bit planes; each plane occupies its own column group.
* Inputs (signed int, default 8-bit) are streamed bit-serially through
  1-bit DACs (paper: "1-bit DACs").
* Per (input-bit, weight-bit) combination the bitline integrates the count
  ``sum_row x_bit[row] * w_bit[row, col]`` — a non-negative integer that a
  9-bit ADC digitizes.  With a 512-row array and 1-bit cells the count is
  at most 512, which is why the paper pairs the 512x512 array with a 9-bit
  ADC: digitization is exact except for the measure-zero all-ones column
  (clipped by 1 LSB at 512 > 2^9 - 1 = 511).
* Shift-and-add (SnA) recombines planes: y = sum_ij s_i s_j 2^(i+j) ADC(.)
  where the MSB plane carries negative weight (two's complement).

Everything is vectorized jnp and jit-friendly.  An optional Gaussian
read-noise model (thermal + shot + RTN, paper §IV-A1) perturbs the analog
count before ADC rounding; this drives the accuracy-drop experiment.

Compute paths (statically dispatched per config, see DESIGN.md):

* **Exact fast path** — when every row chunk has at most ``2^adc_bits - 1``
  rows and read noise is off, no bitline count can exceed the ADC range,
  clipping is a provable no-op, and the whole bit-sliced pipeline is
  bit-identical to one plain int32 GEMM (after two's-complement wrapping
  to the configured bit widths).  ``CrossbarConfig.clip_free`` is the
  predicate; noise presence is checked per call.
* **Plane-packed sliced path** — the faithful route whenever clipping or
  noise can occur.  Input bit planes are stacked along M and weight
  planes along N so the per-chunk counts come from one batched
  ``(C, Bi*M, R) x (C, R, Bw*N)`` matmul instead of a 5-D
  ``(Bi, Bw, C, M, N)`` einsum; ADC noise+clip apply elementwise to the
  packed counts (each bitline is still digitized independently), and
  shift-and-add is a single weighted contraction.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CrossbarConfig:
    """Physical configuration of one unit ReRAM array."""

    rows: int = 512
    cols: int = 512
    cell_bits: int = 1          # HURRY uses single-bit cells (paper §II-B)
    adc_bits: int = 9           # 9-bit ADC for 512 rows (paper §II-A)
    dac_bits: int = 1           # bit-serial input streaming
    weight_bits: int = 8        # int8 quantized weights (paper §IV-A2)
    input_bits: int = 8         # int8 quantized activations
    # Read-noise model (std of the analog count before ADC rounding).
    noise_sigma_thermal: float = 0.0
    noise_sigma_shot: float = 0.0   # scaled by sqrt(count)

    @property
    def adc_max(self) -> int:
        return (1 << self.adc_bits) - 1

    @property
    def weight_planes(self) -> int:
        # ceil(weight_bits / cell_bits) planes, one column group per plane.
        return -(-self.weight_bits // self.cell_bits)

    @property
    def input_phases(self) -> int:
        # bit-serial phases per input value.
        return -(-self.input_bits // self.dac_bits)

    @property
    def clip_free(self) -> bool:
        """True iff ADC clipping can never fire (count <= rows <= adc_max).

        With 1-bit cells a bitline count is a sum of at most ``rows``
        {0,1} products, so ``rows <= 2^adc_bits - 1`` makes digitization
        exact and the bit-sliced pipeline equal to a plain int GEMM.
        ``crossbar_matmul`` refines this per call: a chunk also holds at
        most K rows, so ``K <= adc_max`` is equally clip-free.
        """
        return self.rows <= self.adc_max

    def has_noise(self, noise_key) -> bool:
        """True iff the read-noise model perturbs counts for this call."""
        return noise_key is not None and (self.noise_sigma_thermal > 0
                                          or self.noise_sigma_shot > 0)


def _twos_complement_planes(v: jnp.ndarray, bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decompose signed ints into (planes, plane_weights).

    planes: (bits, *v.shape) of {0,1}; plane_weights: (bits,) with the MSB
    negative (two's complement recombination is exact for signed ints).
    """
    u = v.astype(jnp.int32) & ((1 << bits) - 1)
    planes = jnp.stack([(u >> i) & 1 for i in range(bits)]).astype(jnp.int32)
    w = jnp.array([1 << i for i in range(bits - 1)] + [-(1 << (bits - 1))],
                  dtype=jnp.int32)
    return planes, w


def _wrap_signed(v: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Two's-complement wrap to ``bits`` — what plane decomposition +
    MSB-negative recombination computes for any int input."""
    half = 1 << (bits - 1)
    return ((v.astype(jnp.int32) + half) & ((1 << bits) - 1)) - half


def _adc(count: jnp.ndarray, cfg: CrossbarConfig,
         noise_key: Optional[jax.Array]) -> jnp.ndarray:
    """Digitize an analog bitline count with optional read noise."""
    if cfg.has_noise(noise_key):
        sigma = cfg.noise_sigma_thermal + cfg.noise_sigma_shot * jnp.sqrt(
            jnp.maximum(count.astype(jnp.float32), 0.0))
        noisy = count.astype(jnp.float32) + sigma * jax.random.normal(
            noise_key, count.shape, dtype=jnp.float32)
        count = jnp.round(noisy).astype(jnp.int32)
    return jnp.clip(count, 0, cfg.adc_max)


@partial(jax.jit, static_argnames=("cfg",))
def crossbar_matmul(x: jnp.ndarray, w: jnp.ndarray, cfg: CrossbarConfig = CrossbarConfig(),
                    noise_key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Bit-sliced crossbar GEMM: (..., K) x (K, N) -> (..., N) in int32.

    K is split into row-chunks of ``cfg.rows``; partial sums are combined
    digitally by the shift-and-add units (SnA), exactly as HURRY/ISAAC do
    across stacked arrays.

    Statically dispatches the clip-free exact fast path (one int32 GEMM)
    when no chunk can saturate the ADC and read noise is off; otherwise
    runs the faithful plane-packed sliced path (see module docstring).
    Both are bit-identical wherever they overlap.
    """
    assert x.ndim >= 1 and w.ndim == 2
    K, N = w.shape
    lead = x.shape[:-1]
    x2 = x.reshape((-1, K)).astype(jnp.int32)
    M = x2.shape[0]

    # Exact fast path: counts <= min(rows, K) <= adc_max means the ADC
    # digitizes every bitline exactly, so bit slicing + SnA collapses to a
    # plain int GEMM over the two's-complement-wrapped operands.
    if (cfg.clip_free or K <= cfg.adc_max) and not cfg.has_noise(noise_key):
        y = jax.lax.dot_general(
            _wrap_signed(x2, cfg.input_bits), _wrap_signed(w, cfg.weight_bits),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        return y.reshape(*lead, N)

    xp, xs = _twos_complement_planes(x2, cfg.input_bits)     # (Bi, M, K)
    wp, ws = _twos_complement_planes(w, cfg.weight_bits)     # (Bw, K, N)
    Bi, Bw = cfg.input_bits, cfg.weight_bits

    n_chunks = -(-K // cfg.rows)
    pad = n_chunks * cfg.rows - K
    if pad:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, pad)))
        wp = jnp.pad(wp, ((0, 0), (0, pad), (0, 0)))
    # plane-packed operands: input planes stacked along M, weight planes
    # along N — (C, Bi*M, R) x (C, R, Bw*N), one batched matmul over chunks
    xp = (xp.reshape(Bi, M, n_chunks, cfg.rows)
          .transpose(2, 0, 1, 3).reshape(n_chunks, Bi * M, cfg.rows))
    wp = (wp.reshape(Bw, n_chunks, cfg.rows, N)
          .transpose(1, 2, 0, 3).reshape(n_chunks, cfg.rows, Bw * N))

    # Analog count per (chunk, input-bit x row-vec, weight-bit x col): each
    # (i, j, c) block is one array read; values are non-negative <= rows.
    # f32 matmul is exact for {0,1} products with counts <= rows << 2^24
    # and hits the fast matmul path (int32 contractions have none on CPU).
    counts = jnp.einsum("cmr,crn->cmn", xp.astype(jnp.float32),
                        wp.astype(jnp.float32))
    counts = _adc(counts, cfg, noise_key).astype(jnp.int32)
    # SnA recombination (digital, exact): weighted contraction over planes
    # and chunks in int32 (partial sums can exceed 2^24); the reshape only
    # splits the packed axes back out.
    scale = (xs[:, None] * ws[None, :]).astype(jnp.int32)    # (Bi, Bw)
    y = jnp.einsum("cimwn,iw->mn",
                   counts.reshape(n_chunks, Bi, M, Bw, N), scale)
    return y.reshape(*lead, N)


def quantize_scale(amax: jnp.ndarray, bits: int = 8) -> jnp.ndarray:
    """Symmetric quantization scale from a per-tensor ``max(|x|)``.

    Split out so callers holding a precomputed ``amax`` (e.g. packed
    weight stages, ``program/pack.py``) derive the scale through the
    SAME in-graph expression as ``quantize_symmetric`` — XLA's
    algebraic simplifier rewrites products of divisions, so feeding a
    pre-divided scale in as a constant lands 1 ulp away from the
    traced ``(amax/qmax) * (amax'/qmax)`` form.
    """
    qmax = (1 << (bits - 1)) - 1
    return jnp.maximum(amax, 1e-8) / qmax


def quantize_symmetric(x: jnp.ndarray, bits: int = 8,
                       amax: jnp.ndarray | None = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor quantization -> (int values, scale).

    ``amax`` is the ``max(|.|)`` the scale derives from, ``x``'s own by
    default; a caller quantizing a tensor before it expands it (a conv
    input before im2col) passes the max over the elements the expansion
    reads, which is the expanded matrix's own.
    """
    qmax = (1 << (bits - 1)) - 1
    if amax is None:
        amax = jnp.max(jnp.abs(x))
    scale = quantize_scale(amax, bits)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int32)
    return q, scale


def crossbar_linear(x_fp: jnp.ndarray, w_fp: jnp.ndarray,
                    cfg: CrossbarConfig = CrossbarConfig(),
                    noise_key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Quantize fp inputs/weights to int8, run the crossbar, dequantize."""
    xq, xscale = quantize_symmetric(x_fp, cfg.input_bits)
    wq, wscale = quantize_symmetric(w_fp, cfg.weight_bits)
    y = crossbar_matmul(xq, wq, cfg, noise_key)
    return y.astype(jnp.float32) * (xscale * wscale)


MatmulFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def fp_matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return x @ w


def make_crossbar_matmul(cfg: Optional[CrossbarConfig] = None,
                         noise_key: Optional[jax.Array] = None) -> MatmulFn:
    """Route model GEMMs through the crossbar functional model.

    ``crossbar_matmul`` statically dispatches per config (DESIGN.md §4):
    clip-free + no-noise runs as one exact int GEMM; noisy or saturating
    configs take the faithful plane-packed sliced path.
    """
    cfg = cfg or CrossbarConfig()

    def mm(x, w):
        return crossbar_linear(x, w, cfg, noise_key)
    return mm
