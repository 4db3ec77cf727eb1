"""Weight packing: move weight residency from every forward to compile.

In HURRY (and the ISAAC/FPSA lineage) weights are programmed into
crossbar conductances **once**; only inputs stream through at inference.
``pack_program`` is the numeric analogue of that conductance
programming: given a compiled ``CrossbarProgram`` and its float
parameter pytree, it pre-computes — once, at pack time — everything
about the weights that ``execute_program`` used to re-derive on every
call:

* per-stage symmetric int8 quantization of the full weight matrix
  (``quantize_symmetric`` at ``cfg.weight_bits``) -> the int8 **mount
  planes** plus the f32 weight ``amax`` statistic (the O(params)
  reduction; the executor re-derives the scalar scale in-graph via
  ``quantize_scale`` so the dequant product keeps the exact HLO shape
  of the functional reference — see that helper's docstring);
* the conv im2col layout (``w.transpose(2, 0, 1, 3).reshape(kk, -1)``);
* the mount layout (``kernels.crossbar_gemm.mount_layout``): K cut into
  ``tile_rows``-row mounts, each zero-padded to the next multiple of 128
  rows, so every mount round is one ADC chunk of exactly ``tile_rows``
  real rows in a K block the TPU tiling accepts, and the executor
  activates ALL mounts of a stage in one ``mounted_gemm`` K-grid
  dispatch (block activation).

The quantize+pad core is the standalone ``plane_pack`` helper — the
SAME function the executor invokes **in-graph, per batch** on the
dynamic operands of attention stages (quantized K/V head matrices,
DESIGN.md §9): compile-time weight mounting and run-time activation
mounting are one code path, so the exactness argument transfers
verbatim.

The result is a ``PackedProgram`` — a jax pytree whose leaves are the
per-stage ``(w8, w_amax, bias[, ln_g, ln_b])`` arrays and whose static
treedef carries the (plan-free) program — that ``execute_packed``
consumes directly.  Layer-norm FBs fused onto a stage carry their
gamma/beta here too, so the packed executor never reads the float
param pytree.  Dynamic-operand stages own no weights: they pack as
empty placeholders (their mounts materialize per batch in the
executor).  The hot loop then only quantizes *activations* (the
data-dependent quantities) and dispatches kernels; no weight touches
float math again.  Packing eagerly and quantizing under jit produce
bit-identical planes: ``quantize_symmetric`` is abs/max/divide/round —
none of it subject to FMA contraction (DESIGN.md §5).

``repro.api`` persists the packed planes in its save format (version 4),
so ``api.load(...).run(...)`` never re-derives them (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.crossbar import quantize_symmetric
from repro.kernels.crossbar_gemm import mount_layout

from .compile import CrossbarProgram


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedStage:
    """One GEMM stage's chip-resident weights.

    ``w8`` is the int8 mount-plane matrix ``(K_mounted, N)`` — im2col
    layout applied, K in the mount layout (``mount_layout``) so the
    kernel's K grid is exactly the stage's mount rounds; ``w_amax`` is the f32
    per-tensor ``max(|w|)`` from which the executor derives the
    symmetric quantization scale in-graph (``quantize_scale``);
    ``bias`` the f32 per-column bias.  ``ln_g``/``ln_b`` are the fused
    layer-norm FB's gamma/beta when the stage's post chain has one
    (``None`` otherwise).  Dynamic-operand stages are empty placeholders
    (0-sized ``w8``): their operands mount per batch in the executor.
    """

    w8: jnp.ndarray
    w_amax: jnp.ndarray
    bias: jnp.ndarray
    ln_g: jnp.ndarray | None = None
    ln_b: jnp.ndarray | None = None


def dyn_placeholder() -> PackedStage:
    """The empty PackedStage of a dynamic-operand (attention) stage."""
    return PackedStage(w8=jnp.zeros((0, 0), jnp.int8),
                       w_amax=jnp.zeros((), jnp.float32),
                       bias=jnp.zeros((0,), jnp.float32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedProgram:
    """A ``CrossbarProgram`` with weights mounted at pack time.

    ``program`` is static metadata (hashable — packing strips the
    compile-time array plans, which the executor never reads, exactly
    as the save format does); ``stages`` holds one ``PackedStage`` per
    GEMM stage, in ``program.stages()`` order.
    """

    stages: tuple[PackedStage, ...]
    program: CrossbarProgram = dataclasses.field(
        metadata=dict(static=True))

    @property
    def cfg(self):
        return self.program.cfg


def plane_pack(w: jnp.ndarray, *, tile_rows: int,
               weight_bits: int = 8) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mount a (K, N) float matrix: -> (int8 planes (K_mounted, N), f32 amax).

    Symmetric per-tensor int8 quantization at ``weight_bits``, K laid
    out as full ``tile_rows``-row mounts, each zero-padded to a multiple
    of 128 rows (``mount_layout``; zero rows add nothing to any bitline
    count).
    Invoked once per weight at pack time — and **in-graph, per batch**
    on the quantized K/V head matrices of dynamic attention stages, the
    run-time analogue of programming conductances (DESIGN.md §9).  The
    ``amax`` statistic (not the scale) is returned so every consumer
    derives the scale through ``quantize_scale``'s traced expression.
    """
    wq, _ = quantize_symmetric(w, weight_bits)
    return (mount_layout(wq.astype(jnp.int8), tile_rows, 0),
            jnp.max(jnp.abs(w)).astype(jnp.float32))


def pack_weight(w: jnp.ndarray, *, is_conv: bool, tile_rows: int,
                weight_bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Float weight -> (int8 mount planes (K_mounted, N), f32 amax)."""
    if is_conv:                 # (k, k, in_ch, out_ch) -> (in_ch*k*k, N)
        kk = w.shape[0] * w.shape[1] * w.shape[2]
        w = w.transpose(2, 0, 1, 3).reshape(kk, -1)
    return plane_pack(w, tile_rows=tile_rows, weight_bits=weight_bits)


@functools.partial(jax.jit, static_argnums=(0,))
def pack_program(program: CrossbarProgram, params: dict) -> PackedProgram:
    """Mount ``params`` into ``program``: the compile-time analogue of
    programming the chip's conductances.  Meant to run ONCE outside the
    per-call hot path (``ProgramServer`` packs at construction,
    ``api.compile`` at compile time).

    Jitted (program static) so the weight quantization compiles exactly
    like the jitted functional reference and the in-trace packing of
    ``execute_program``: eager op-by-op dispatch rounds ``x / scale``
    one ulp differently on a measure-zero set of boundary values, which
    would flip the occasional int8 plane entry (DESIGN.md §5/§7).
    """
    cfg = program.cfg
    stages = []
    for gemm, posts in program.stages():
        if gemm.kind == "dyn_gemm":
            stages.append(dyn_placeholder())
            continue
        p = params[gemm.param]
        w8, amax = pack_weight(p[gemm.w_key], is_conv=gemm.is_conv,
                               tile_rows=gemm.tile_rows,
                               weight_bits=cfg.weight_bits)
        ln = next((o for o in posts if o.kind == "layernorm"), None)
        lp = params[ln.param] if ln is not None else None
        stages.append(PackedStage(
            w8=w8, w_amax=amax,
            bias=p[gemm.b_key].astype(jnp.float32),
            ln_g=None if lp is None else lp["g"].astype(jnp.float32),
            ln_b=None if lp is None else lp["b"].astype(jnp.float32)))
    return PackedProgram(stages=tuple(stages),
                         program=dataclasses.replace(program, plans=()))
