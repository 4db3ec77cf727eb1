"""Weight packing: move weight residency from every forward to compile.

In HURRY (and the ISAAC/FPSA lineage) weights are programmed into
crossbar conductances **once**; only inputs stream through at inference.
``pack_program`` is the numeric analogue of that conductance
programming: given a compiled ``CrossbarProgram`` and its float
parameter pytree, it pre-computes — once, at pack time — everything
about the weights, so no forward re-derives it:

* per-stage symmetric int8 quantization of the full weight matrix
  (``quantize_symmetric`` at ``cfg.weight_bits``) -> the int8 **mount
  planes** plus the f32 weight ``amax`` statistic (the O(params)
  reduction; the executor re-derives the scalar scale in-graph via
  ``quantize_scale`` so the dequant product keeps the exact HLO shape
  of the functional reference — see that helper's docstring);
* the K order and layout, chosen per stage by ``stage_layout``:

  - **dense** — every weight-mounted stage whose ``tile_rows``-row
    mounts cannot clip (``clip_possible`` is False: the exact path).
    Its int32 sums are the same for any K order and any K blocking, so
    mount boundaries carry no semantics: a conv's K is taken in
    ``(i, j, c)`` order (``w.reshape(k*k*C, N)``, the order of the
    executor's channels-minor im2col) and zero-padded only to whole
    kernel blocks (``kernels.crossbar_gemm.dense_layout``);
  - **mounted** — stages where an ADC clip can fire, and dynamic
    attention stages.  K order and mount membership decide which
    products share an ADC chunk, so a conv keeps the ``(c, i, j)``
    im2col order (``w.transpose(2, 0, 1, 3).reshape(kk, -1)``) and the
    mount layout (``kernels.crossbar_gemm.mount_layout``): K cut into
    ``tile_rows``-row mounts, each zero-padded to the next multiple of
    128 rows, so every mount round is one ADC chunk of exactly
    ``tile_rows`` real rows in a K block the TPU tiling accepts, and the
    executor activates ALL mounts of a stage in one ``mounted_gemm``
    K-grid dispatch (block activation).

The quantize+pad core is the standalone ``plane_pack`` helper — the
SAME function the executor invokes **in-graph, per batch** on the
dynamic operands of attention stages (quantized K/V head matrices,
DESIGN.md §9): compile-time weight mounting and run-time activation
mounting are one code path, so the exactness argument transfers
verbatim.

The result is a ``PackedProgram`` — a jax pytree whose leaves are the
per-stage ``(w8, w_amax, bias[, ln_g, ln_b, pre_g, pre_b, emb_cls,
emb_pos, rel_bias])`` arrays and whose static treedef carries the (plan-free)
program — that ``execute_packed`` consumes directly.
``PackedProgram.layouts()`` says which layout each stage took.
Layer-norm FBs fused onto a stage carry their gamma/beta here too, as
do a stage's pre-norm (the layer norm of its input) and a patchify
stage's class token and position table, so the packed executor never
reads the float param pytree.  Dynamic-operand stages own no
weights: they pack as empty placeholders (their mounts materialize per
batch in the executor), except that a windowed attention's Q·Kᵀ stage
holds its relative position bias, gathered once from the
``(2M-1)^2 x heads`` table to ``(heads, M², M²)``
(``sequence.rel_bias``).  A bias-free GEMM packs a zero bias.  The hot loop then only quantizes *activations* (the
data-dependent quantities) and dispatches kernels; no weight touches
float math again.  Packing eagerly and quantizing under jit produce
bit-identical planes: ``quantize_symmetric`` is abs/max/divide/round —
none of it subject to FMA contraction (DESIGN.md §5).

``repro.api`` persists the packed planes in its save format (version 5),
so ``api.load(...).run(...)`` never re-derives them (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.crossbar import quantize_symmetric
from repro.kernels.crossbar_gemm import (clip_possible, dense_layout,
                                        mount_layout)

from .compile import CrossbarProgram, ProgramOp
from .sequence import rel_bias


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedStage:
    """One GEMM stage's chip-resident weights.

    ``w8`` is the int8 plane matrix ``(K_laid_out, N)`` — im2col order
    and K layout as ``stage_layout`` chose (module docstring), so the
    kernel's K grid is the stage's mount rounds (mounted) or its dense
    K blocks (dense); ``w_amax`` is the f32
    per-tensor ``max(|w|)`` from which the executor derives the
    symmetric quantization scale in-graph (``quantize_scale``);
    ``bias`` the f32 per-column bias.  ``ln_g``/``ln_b`` are the fused
    layer-norm FB's gamma/beta when the stage's post chain has one,
    ``pre_g``/``pre_b`` those of its input's layer norm (``prenorm``),
    and ``emb_cls``/``emb_pos`` the class token and position table of
    an ``embed`` FB (each ``None`` where the stage has no such op).
    Dynamic-operand stages are empty placeholders (0-sized ``w8``):
    their operands mount per batch in the executor; ``rel_bias`` is a
    windowed Q·Kᵀ stage's gathered ``(heads, M², M²)`` position bias.
    """

    w8: jnp.ndarray
    w_amax: jnp.ndarray
    bias: jnp.ndarray
    ln_g: jnp.ndarray | None = None
    ln_b: jnp.ndarray | None = None
    pre_g: jnp.ndarray | None = None
    pre_b: jnp.ndarray | None = None
    emb_cls: jnp.ndarray | None = None
    emb_pos: jnp.ndarray | None = None
    rel_bias: jnp.ndarray | None = None


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

    def layouts(self) -> tuple[str, ...]:
        """``"dense"`` or ``"mounted"`` per stage, in stage order
        (``stage_layout``)."""
        return tuple(stage_layout(gemm, self.cfg)
                     for gemm, _ in self.program.stages())


def stage_layout(gemm: ProgramOp, cfg) -> str:
    """The K layout of a stage's operands: ``"dense"`` for a
    weight-mounted stage whose ``tile_rows``-row mounts cannot clip at
    ``cfg.adc_bits`` (the exact path, where K order and blocking change
    no sum), else ``"mounted"`` (module docstring)."""
    if gemm.kind == "gemm" and not clip_possible(gemm.tile_rows,
                                                 cfg.adc_bits):
        return "dense"
    return "mounted"


def plane_pack(w: jnp.ndarray, *, tile_rows: int, weight_bits: int = 8,
               layout: str = "mounted") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mount a (K, N) float matrix: -> (int8 planes (K_laid_out, N), f32 amax).

    Symmetric per-tensor int8 quantization at ``weight_bits``, K laid
    out as full ``tile_rows``-row mounts, each zero-padded to a multiple
    of 128 rows (``mount_layout``; zero rows add nothing to any bitline
    count), or in the dense layout (``dense_layout``: K padded only at
    its end, to whole kernel blocks).
    Invoked once per weight at pack time — and **in-graph, per batch**
    on the quantized K/V head matrices of dynamic attention stages, the
    run-time analogue of programming conductances (DESIGN.md §9).  The
    ``amax`` statistic (not the scale) is returned so every consumer
    derives the scale through ``quantize_scale``'s traced expression.
    """
    wq, _ = quantize_symmetric(w, weight_bits)
    wq = wq.astype(jnp.int8)
    planes = (dense_layout(wq, 0) if layout == "dense"
              else mount_layout(wq, tile_rows, 0))
    return planes, jnp.max(jnp.abs(w)).astype(jnp.float32)


def pack_weight(w: jnp.ndarray, *, is_conv: bool, tile_rows: int,
                weight_bits: int, layout: str = "mounted"
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Float weight -> (int8 planes (K_laid_out, N), f32 amax).

    A conv weight ``(k, k, in_ch, out_ch)`` is taken in the im2col order
    of ``layout``: ``(i, j, c)`` dense, ``(c, i, j)`` mounted.
    """
    if is_conv:
        n = w.shape[-1]
        if layout == "mounted":
            w = w.transpose(2, 0, 1, 3)
        w = w.reshape(-1, n)
    return plane_pack(w, tile_rows=tile_rows, weight_bits=weight_bits,
                      layout=layout)


def _op_params(params: dict, key: str) -> dict:
    """An FB's float32 parameters (``params[key]``; none for ``""``)."""
    if not key:
        return {}
    return {k: v.astype(jnp.float32) for k, v in params[key].items()}


@functools.partial(jax.jit, static_argnums=(0,))
def pack_program(program: CrossbarProgram, params: dict) -> PackedProgram:
    """Mount ``params`` into ``program``: the compile-time analogue of
    programming the chip's conductances.  Meant to run ONCE outside the
    per-call hot path (``api.compile`` packs at compile time).

    Jitted (program static) so the weight quantization compiles exactly
    like the jitted functional reference: eager op-by-op dispatch
    rounds ``x / scale`` one ulp differently on a measure-zero set of
    boundary values, which would flip the occasional int8 plane entry
    (DESIGN.md §5/§7).
    """
    cfg = program.cfg
    stages = []
    for gemm, posts in program.stages():
        if gemm.kind == "dyn_gemm":
            st = dyn_placeholder()
            if gemm.param:              # a windowed Q·Kᵀ stage
                st = dataclasses.replace(st, rel_bias=rel_bias(
                    params[gemm.param]["relb"].astype(jnp.float32),
                    gemm.window))
            stages.append(st)
            continue
        p = params[gemm.param]
        w8, amax = pack_weight(p[gemm.w_key], is_conv=gemm.is_conv,
                               tile_rows=gemm.tile_rows,
                               weight_bits=cfg.weight_bits,
                               layout=stage_layout(gemm, cfg))
        ln = _op_params(params, next(
            (o.param for o in posts if o.kind == "layernorm"), ""))
        pre = _op_params(params, gemm.prenorm)
        emb = _op_params(params, next(
            (o.param for o in posts if o.kind == "embed"), ""))
        stages.append(PackedStage(
            w8=w8, w_amax=amax,
            bias=(p[gemm.b_key].astype(jnp.float32) if gemm.b_key
                  else jnp.zeros((gemm.out_ch,), jnp.float32)),
            ln_g=ln.get("g"), ln_b=ln.get("b"),
            pre_g=pre.get("g"), pre_b=pre.get("b"),
            emb_cls=emb.get("cls"), emb_pos=emb.get("pos")))
    return PackedProgram(stages=tuple(stages),
                         program=dataclasses.replace(program, plans=()))
