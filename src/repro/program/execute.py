"""Executor: run a ``CrossbarProgram`` numerically, batched.

A pure dataflow interpreter over the program's static op list — the
scheduled program is *the* thing that computes:

* weights are chip-resident: ``pack.pack_program`` pre-quantizes, lays
  out, and K-pads every stage's weight matrix ONCE (the numeric
  analogue of programming conductances), so the hot loop only
  quantizes *activations* — the data-dependent quantities;
* every GEMM is ONE ``mounted_gemm`` Pallas dispatch: the kernel's K
  grid activates all row mounts of the stage in a single call, partial
  sums chained in int32 inside the kernel's accumulator (SnA across
  stacked arrays, bit-identical to the former per-mount ``lax.scan``
  because int32 addition is associative).  How the stage's operand is
  built depends on its layout (``pack.stage_layout``):

  - **dense** — every weight-mounted stage on the exact path (its
    ``tile_rows``-row mounts cannot clip; all stages of the clip-free
    configs).  There the int32 sums are the same for any K order and
    any K blocking, so mount boundaries may be ignored, and the int8
    operand is built once, in the layout the kernel reads
    (``_dense_operand``): the stage input is quantized first — its
    ``amax`` over exactly the elements im2col reads, so values and
    scale are bit-identical to quantizing the patch matrix — then the
    ``k*k`` taps are cut from the int8 tensor and concatenated along
    the channels (``(i, j, c)`` order, channels minor and lane-dense),
    and the kernel pads K only to whole blocks of at most 1024 rows;
  - **mounted** — stages whose mounts can clip: the f32 im2col in
    ``(c, i, j)`` order, quantized, then laid out by the kernel as
    mounts like the packed weights (``rows=tile_rows``), each K block
    one physical array read with per-mount ADC chunk semantics — there
    K order and mount membership are semantics;
* every post-op chain (shift-and-add requant -> bias -> residual ->
  ReLU/tanh GELU -> max/avg pool window | softmax) runs in ONE pass of
  the fused ``fb_epilogue`` Pallas kernel over the GEMM output tile, so
  the crossbar output never round-trips through a separate jnp op —
  the numeric analogue of HURRY hiding FB post-ops inside the array;
* sequence stages add steps around that (``compile.py``): a stage
  that reads a token pool takes row 0 of each sequence of its source
  (``select="cls"``: rows ``0, T, 2T, ...``) or the mean of its tokens
  (``select="mean"``, ``fb_epilogue.token_mean``); a pre-norm stage
  layer-normalizes its
  input while it builds the operand, before ``amax`` and the int8
  convert, so LN(x) is never a stage buffer; and a patchify stage's
  ``embed`` prepends the class token to its output tokens and adds the
  position table (``sequence.embed_tokens``);
* the sequence tails run as XLA ops on the epilogue kernel's output,
  in its ``epilogue`` phase, in chain order: the exact GELU (Mosaic
  cannot lower ``erf``; it ends its stage's chain), a post-norm or
  patch layer norm, and attention's softmax (after a windowed
  attention's relative position bias and shift mask); and the mean
  over tokens in the operand build of the head that reads it.  The
  next stage re-quantizes them, so a result a few ulps apart from an
  XLA evaluation of the same expression (Mosaic sums a row in another
  order) flips int8 roundings there, which a deep network amplifies:
  on a TPU v5e the 12-block DeiT-Ti read ``prob_err`` 0.087 against
  the oracle with its softmax in Mosaic (PERF.md §6).  In XLA, with their sums in one fixed pairwise
  order (``fb_epilogue.ordered_sum``), the program's float tails round
  as any XLA evaluation of the reference does.

**Dynamic-operand stages** (``kind="dyn_gemm"``, DESIGN.md §9) extend
the same machinery to attention's activation-side GEMMs: per (batch,
head), the Q·Kᵀ / P·V right-hand operand is quantized and mounted
IN-GRAPH with the same ``plane_pack`` helper that mounts weights at
compile time, then dispatched through the same ``mounted_gemm`` kernel
with the K grid sized to the *runtime* contraction length (head dim for
scores, seq_len for context — the paper's block-activation scheme on
dynamically sized mounts).  The per-mount loop is a ``jax.vmap`` over
the (batch*heads) axis — mirroring the functional oracle's vmapped
``mm`` exactly, so per-slice quantization statistics line up and the
clip-free bit-exactness argument of §5 carries over unchanged.  A
windowed attention (Swin) mounts per (image, window, head) instead: its
Q·Kᵀ and P·V operand builds roll the token map by ``-shift`` and cut it
into windows (``sequence.window_qkv_heads``), its scores get the
relative position bias and then the shift mask before the softmax
(``sequence.window_bias``), and its P·V epilogue puts the windows back
and undoes the roll where the context rejoins the rows
(``sequence.window_merge``).  A patch-merging stage builds its operand
from the 2x2 space-to-depth of its input's token map
(``sequence.space_to_depth``) before its pre-norm.

**Sequence buffers are token rows.**  Inside the executor a sequence
stage's buffer is the ``(B*T, N)`` row matrix its epilogue kernel
writes, and each stage that reads it takes it as rows; the graph's
``(B, T, N)`` shape is a view the executor takes only where a step
works per image (DESIGN.md §9).  At DeiT-Ti's T = 197, off the TPU's
8-row tiling, every change between the two shapes is a copy of the
whole buffer, so XLA is left to place the ones the work needs and no
others: the patch stage's ``embed`` builds the sequence per image and
writes it as rows once; a class-token read takes rows ``0, T, 2T,
...``; a pre-norm takes the per-image view, which XLA lays out with
the normed axis major so that its pairwise row sums slice whole tiles
(on the rows they slice lanes, which cost a v5e more than the
relayout saves; PERF.md §6); attention splits q, k and v from the
per-image view of the fused projection and writes its merged context
back as rows.  Two ``optimization_barrier``s fix where the head moves
happen: K is transposed into Kᵀ in f32, in one pass, before it is
quantized (else XLA moves Kᵀ as int8, whose packed tiles a v5e
relayouts slowly), and the merged context is built per image in f32
before its rows are quantized.  Each step moves data only: the
values, the scale sets and the order of every float sum are those of
the oracle.  At the boundary the graph's shapes hold:
``stage_outputs(feed=...)`` reads the oracle's ``(B, T, N)`` buffers
as rows, and yields every ``StageOutput.value`` in the graph's shape
(under a jit only the program's output is live, so those reshapes
compile away).

**Attention's scores tail runs keys-major** (``_probs``) where the keys
fill under one 128-lane tile.  The epilogue kernel writes a Q·Kᵀ
stage's scores as ``(mounts, q, k)``, the keys on the lanes: at Swin's
49 keys every op after it, and each level of the ordered row sum (24,
12, 6, ... keys wide), would write whole 128-lane tiles, most of them
padding.  So the scores move once, in f32, to ``(k, q, mounts)``, the
mounts dense on the lanes, where the row max, ``exp`` and the
``ordered_sum`` tree over the leading axis run; ``exp`` and its row
sums move back, and the divide runs in the kernel's layout.  There XLA
folds it into the P·V stage's quantize, ``e / (sum * scale)``, as it
does in the oracle: a quotient moved on its own would round apart from
it (PERF.md §6).  Layout constraints hold both layouts (left free, XLA
lays each transpose out as a bitcast of the kernel's output, the keys
on the lanes again).  A windowed stage adds its scale, bias and mask
before the move, in the kernel's layout, on the per-image view ``(B,
nW*heads, q, k)`` behind an ``optimization_barrier``: each table is
then added as one image's tile across the images (without the barrier
XLA moves the view's reshape past the adds and writes both tables out
for every mount).  Keys that fill a lane tile (DeiT-Ti's 197) stay on
the lanes, where the two moves cost more than the levels save (PERF.md
§6).  Each op, its values and the order of every float sum are the
oracle's; only where the data lies changes.

Intermediate buffers are dropped as soon as no later stage reads them
(``src``, ``dyn_src`` or ``res_src``), so an eager forward holds the
live frontier of the dataflow graph, not every activation.

Quantization mirrors ``core/crossbar.crossbar_linear`` exactly
(per-tensor symmetric int8 of the full im2col/token matrix — for a
dense stage, of the input elements that matrix copies — and weight
matrix; per-(batch, head) tensors for dynamic stages), so under a
clip-free config the program forward is bit-identical to the
functional-model forward when both are jitted (identical FMA
contraction; DESIGN.md §5).  Read noise is a functional-model-only
experiment: the program path models a clean chip.

**Named scopes.**  Every op a stage issues carries one stage scope,
``s<NN>.<buffer>`` (the stage's index and the buffer it writes, an
attention buffer ``<layer>@qkv`` spelt ``<layer>.qkv``: JAX ends a
scope's name at ``@``), and one phase scope inside it, so a compiled
program's ``op_name`` metadata says which stage and which phase each
instruction serves:

* ``im2col`` — im2col (a dense stage's tap concat on the int8
  tensor), token-row and flatten reshapes, a class-token read, head
  splits and Kᵀ, and inside it ``window``, a windowed attention's roll
  and window partition, and ``merge``, a merge stage's space-to-depth;
* ``quantize`` — the activation's amax reduction and int8 convert,
  and inside it ``prenorm``, a pre-norm stage's layer norm of its
  input;
* ``mount`` — ``mounted_gemm``'s mount layout or dense K pad, its
  lane pad of an N under 128 and the slice back, and ``plane_pack`` of
  a dynamic stage's right-hand operand;
* ``gemm`` — the ``mounted_gemm`` kernel;
* ``epilogue`` — the scale product, the ``fb_epilogue`` kernel, the
  XLA tails, a conv's NHWC output reshape and a context's head merge,
  and inside it ``embed``, a patchify stage's class token and position
  table, ``relbias``, a windowed Q·Kᵀ stage's bias and mask, and
  ``unwindow``, a windowed P·V stage's window reverse and un-roll.

Scopes are metadata only: they change no op and cost nothing at run
time.

``execute_packed`` is the one entry: trace-pure, it takes the packed
program as a pytree argument, so ``jax.jit`` compiles it once per batch
shape (``api.CompiledModel.run`` does, per bucket).
"""

from __future__ import annotations

from typing import Callable, Iterator, Mapping, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.conv import im2col, im2col_read_mask
from repro.core.crossbar import quantize_scale, quantize_symmetric
from repro.kernels.crossbar_gemm import LANE, mounted_gemm
from repro.kernels.fb_epilogue import (fb_epilogue, gelu_erf,
                                       layer_norm_ordered, ordered_sum,
                                       softmax_ordered, token_mean)
from repro.kernels.ops import interpret_default

from .compile import ProgramOp
from .pack import PackedProgram, PackedStage, plane_pack, stage_layout
from .sequence import (embed_tokens, merge_heads, shift_mask,
                       space_to_depth, split_qkv_heads, window_bias,
                       window_merge, window_qkv_heads)


class Kernels(NamedTuple):
    """The two kernels every stage dispatches.  A reference pair with
    the same signatures can stand in to check them stage by stage
    (``stage_outputs``)."""

    gemm: Callable
    epilogue: Callable


def _last_reads(stages) -> dict[str, int]:
    """Buffer name -> index of the last stage that reads it."""
    last: dict[str, int] = {}
    for si, (gemm, posts) in enumerate(stages):
        last[gemm.src] = si
        if gemm.dyn_src:
            last[gemm.dyn_src] = si
        for op in posts:
            if op.kind == "residual":
                last[op.res_src] = si
    return last


def _heads(gemm: ProgramOp, qkv: jnp.ndarray, batch: int
           ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q, k and v of every mount from the fused projection's rows: per
    (image, head), or per (image, window, head) of the rolled map."""
    x = _tokens(qkv, batch)
    if not gemm.window:
        return split_qkv_heads(x, gemm.heads)
    with jax.named_scope("window"):
        return window_qkv_heads(x, gemm.heads, gemm.in_hw, gemm.window,
                                gemm.shift)


def _dyn_stage(gemm: ProgramOp, posts: list[ProgramOp], st: PackedStage,
               bufs: Mapping, cfg, batch: int, *, block_m: int | None,
               block_n: int | None, interpret: bool, kernels: Kernels
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One dynamic-operand GEMM stage (attention Q·Kᵀ or P·V) ->
    (output, int32 GEMM result per mount).

    Mounts the right-hand activation per (batch, head) — per (batch,
    window, head) in a windowed attention — with ``plane_pack``, the
    same helper that mounts weights at compile time, and dispatches the
    same ``mounted_gemm`` kernel, its K grid sized to the runtime
    contraction length (module docstring).
    """
    with jax.named_scope("im2col"):
        if gemm.dyn == "qk":
            q, k, _ = _heads(gemm, bufs[gemm.src], batch)
            # Kᵀ moved in f32, in one pass, before it is quantized
            # (module docstring)
            a = q                                # (BH, T, hd)
            w = jax.lax.optimization_barrier(jnp.swapaxes(k, 1, 2))
        elif gemm.dyn == "pv":
            a = bufs[gemm.src]                   # (BH, T, T) probabilities
            _, _, w = _heads(gemm, bufs[gemm.dyn_src], batch)
        else:  # pragma: no cover - compile_network emits only qk/pv
            raise ValueError(gemm.dyn)
    softmax = any(p.kind == "softmax" for p in posts)
    rows = min(gemm.tile_rows, a.shape[-1])      # dynamic mount height
    # each phase vmaps over (batch, head) inside its own scope: a scope
    # opened inside a vmapped function is named "vmap(<phase>)"
    with jax.named_scope("quantize"):
        aq, ascale = jax.vmap(
            lambda a2: quantize_symmetric(a2, cfg.input_bits))(a)
        aq = aq.astype(jnp.int8)
    with jax.named_scope("mount"):
        w8, wamax = jax.vmap(lambda w2: plane_pack(
            w2, tile_rows=rows, weight_bits=cfg.weight_bits))(w)
    acc = jax.vmap(lambda a2, w2: kernels.gemm(
        a2, w2, adc_bits=cfg.adc_bits, rows=rows, block_m=block_m,
        block_n=block_n, interpret=interpret))(aq, w8)
    with jax.named_scope("epilogue"):
        zeros = jnp.zeros((w.shape[2],), jnp.float32)

        # a windowed stage scales its scores where it adds the bias
        # (``window_bias``), as the oracle's one expression does
        post_scale = 0.0 if gemm.window else gemm.post_scale

        def epilogue(y, s, wm):
            ws = quantize_scale(wm, cfg.weight_bits)
            scale = (s * ws).astype(jnp.float32).reshape(1, 1)
            return kernels.epilogue(
                y, scale, zeros, None, post_scale=post_scale,
                block_m=block_m, block_n=block_n, interpret=interpret)

        out = jax.vmap(epilogue)(acc, ascale, wamax)
        if softmax:                    # XLA's softmax (module docstring)
            out = _probs(gemm, st, out, batch)
        if gemm.dyn == "pv":           # heads rejoin the model dim, as rows
            if gemm.window:
                with jax.named_scope("unwindow"):
                    out = window_merge(out, gemm.heads, gemm.in_hw,
                                       gemm.window, gemm.shift)
            else:
                out = merge_heads(out, gemm.heads)
            out = jax.lax.optimization_barrier(out)
            out = out.reshape(-1, out.shape[-1])
    return out, acc


def _row_major(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` laid out row-major: its last axis on the lanes."""
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def _probs(gemm: ProgramOp, st: PackedStage, scores: jnp.ndarray,
           batch: int) -> jnp.ndarray:
    """A Q·Kᵀ stage's (mounts, q, k) scores -> its probabilities, in the
    same form: a windowed stage's scale, bias and mask, then
    ``softmax_ordered`` over the keys, its max, ``exp`` and row sum
    keys-major where the keys fill under one lane tile (module
    docstring)."""
    m, q, k = scores.shape
    if gemm.window:
        with jax.named_scope("relbias"):
            s = jax.lax.optimization_barrier(scores.reshape(batch, -1, q, k))
            s = window_bias(s * gemm.post_scale, st.rel_bias,
                            shift_mask(gemm.in_hw, gemm.window, gemm.shift))
            scores = _row_major(s).reshape(m, q, k)
    if k >= LANE:
        return softmax_ordered(scores)
    x = _row_major(jnp.transpose(scores, (2, 1, 0)))        # (k, q, mounts)
    e = jnp.exp(x - jnp.max(x, axis=0, keepdims=True))

    def back(v):
        return jnp.transpose(_row_major(v), (2, 1, 0))

    return back(e) / back(ordered_sum(e, 0))


def _tokens(x: jnp.ndarray, batch: int) -> jnp.ndarray:
    """A sequence buffer's per-image view ``(B, T, N)``: of its rows, of
    an NHWC map (tokens row-major, ``sequence.tokens``) or as it is."""
    return x.reshape(batch, -1, x.shape[-1])


def _gemm_rows(x: jnp.ndarray, seq: bool) -> jnp.ndarray:
    """A linear stage's input as GEMM rows: tokens ``(B, T, D)`` ->
    ``(B*T, D)``, an NHWC map flattened per image, a matrix as is."""
    if x.ndim == 2:
        return x
    return x.reshape(-1, x.shape[-1]) if seq else x.reshape(x.shape[0], -1)


def _dense_operand(gemm: ProgramOp, src: jnp.ndarray, bits: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A dense stage's int8 GEMM operand ``(M, K)`` and its scale, built
    in one pass in the kernel's K order (module docstring).

    The stage input is quantized first, its ``amax`` taken over exactly
    the pixels im2col reads (``im2col_read_mask``), so the int8 values and
    the scale are bit-identical to quantizing the patch matrix: the same
    scalar and the same elementwise expression on the same values.  The
    patches are then cut from the int8 tensor channels-minor, in the
    ``(i, j, c)`` order ``pack_weight`` gives a dense conv's weights.
    """
    with jax.named_scope("quantize"):
        mag = jnp.abs(src)
        if gemm.is_conv:
            read = im2col_read_mask(src.shape[1], src.shape[2], gemm.ksize,
                                    gemm.stride, gemm.padding)
            if read is not None:         # unread pixels count as 0
                mag = jnp.where(read, mag, 0.0)
        xq, xs = quantize_symmetric(src, bits, amax=jnp.max(mag))
        xq = xq.astype(jnp.int8)
    with jax.named_scope("im2col"):
        if gemm.is_conv:
            xq = im2col(xq, gemm.ksize, gemm.stride, gemm.padding,
                        channels_minor=True)
            xq = xq.reshape(-1, xq.shape[-1])
        else:
            xq = _gemm_rows(xq, gemm.seq)
    return xq, xs


def _static_stage(gemm: ProgramOp, posts: list[ProgramOp],
                  st: PackedStage, bufs: Mapping, cfg, batch: int, *,
                  block_m: int | None, block_n: int | None, interpret: bool,
                  drop_softmax: bool, kernels: Kernels
                  ) -> tuple[str, jnp.ndarray, jnp.ndarray, bool]:
    """One weight-mounted GEMM stage + fused epilogue -> (dst, buffer,
    int32 GEMM result, whether the buffer is sequence rows)."""
    src = bufs[gemm.src]
    if gemm.seq or gemm.select:
        with jax.named_scope("im2col"):
            src = _gemm_rows(src, True)      # (B*T, D) token rows
            if gemm.select == "cls":         # the class token: row b*T
                src = src[::src.shape[0] // batch]
            elif gemm.select == "mean":      # the mean of each sequence
                src = token_mean(_tokens(src, batch))
            if gemm.merge:                   # (B*T/4, 4D) merged rows
                with jax.named_scope("merge"):
                    src = space_to_depth(_tokens(src, batch), gemm.in_hw)
                    src = src.reshape(-1, src.shape[-1])
    if gemm.prenorm:
        if not gemm.seq:
            with jax.named_scope("im2col"):
                src = _gemm_rows(src, False)
        with jax.named_scope("quantize"), jax.named_scope("prenorm"):
            # tokens per image, where XLA lays the normed axis out
            # major (module docstring)
            x = _tokens(src, batch) if gemm.seq else src
            src = layer_norm_ordered(x, st.pre_g, st.pre_b,
                                     gemm.eps).reshape(src.shape)
    layout = stage_layout(gemm, cfg)
    if layout == "dense":
        xq, xs = _dense_operand(gemm, src, cfg.input_bits)
    else:
        with jax.named_scope("im2col"):
            if gemm.is_conv:
                cols = im2col(src, gemm.ksize, gemm.stride, gemm.padding)
                xin = cols.reshape(-1, cols.shape[-1])
            else:
                xin = _gemm_rows(src, gemm.seq)
        with jax.named_scope("quantize"):
            xq, xs = quantize_symmetric(xin, cfg.input_bits)
            xq = xq.astype(jnp.int8)
    y_int = kernels.gemm(xq, st.w8, adc_bits=cfg.adc_bits,
                         rows=gemm.tile_rows, block_m=block_m,
                         block_n=block_n, interpret=interpret, layout=layout)
    act, pool, window, img_hw = "none", "none", 0, 0
    softmax, res, embed, erf_gelu = False, None, False, False
    ln_eps = None                      # XLA tail (module docstring)
    out_hw = gemm.out_hw
    dst = posts[-1].dst if posts else gemm.dst
    for op in posts:
        if op.kind == "relu":
            act = "relu"
        elif op.kind == "gelu" and op.approx == "erf":
            erf_gelu = True            # XLA, after the kernel
        elif op.kind == "gelu":
            act = "gelu"
        elif op.kind == "layernorm":
            ln_eps = op.eps
        elif op.kind == "embed":
            embed = True
        elif op.kind == "residual":
            res = bufs[op.res_src]
        elif op.kind in ("maxpool", "avgpool"):
            pool = "max" if op.kind == "maxpool" else "avg"
            window, img_hw, out_hw = op.window, op.in_hw, op.out_hw
        elif op.kind == "softmax":
            softmax = True
        else:  # pragma: no cover - compile_network validates kinds
            raise ValueError(op.kind)
    if softmax and drop_softmax:
        softmax = False
        dst = gemm.dst
    with jax.named_scope("epilogue"):
        # the weight scale divides out of the stored amax IN-GRAPH so the
        # dequant product keeps the functional reference's HLO shape
        # (quantize_scale docstring; DESIGN.md §5)
        ws = quantize_scale(st.w_amax, cfg.weight_bits)
        scale = (xs * ws).astype(jnp.float32).reshape(1, 1)
        if res is not None:
            res = res.reshape(-1, res.shape[-1])
        out = kernels.epilogue(y_int, scale, st.bias, res, act=act,
                               pool=pool, window=window, img_hw=img_hw,
                               softmax=softmax, block_m=block_m,
                               block_n=block_n, interpret=interpret)
        # a sequence stage's (B*T, N) output is its buffer as it is
        # a patchify conv with an embed or a patch norm writes tokens
        tokens_out = embed or ln_eps is not None
        rows = tokens_out or gemm.seq
        if gemm.is_conv and not tokens_out:
            out = out.reshape(batch, out_hw, out_hw, -1)
        if erf_gelu:
            out = gelu_erf(out)
        if ln_eps is not None:         # per image, as a pre-norm
            d = out.shape[-1]
            out = layer_norm_ordered(_tokens(out, batch), st.ln_g, st.ln_b,
                                     ln_eps).reshape(-1, d)
        if embed:          # the one place a sequence is built per image
            with jax.named_scope("embed"):
                d = out.shape[-1]
                out = embed_tokens(_tokens(out, batch), st.emb_cls,
                                   st.emb_pos).reshape(-1, d)
    return dst, out, y_int, rows


class StageOutput(NamedTuple):
    """What one stage wrote: its buffer name and value, and the int32
    crossbar GEMM result its epilogue consumed (per batch*head for
    dynamic attention stages)."""

    name: str
    value: jnp.ndarray
    acc: jnp.ndarray


def stage_outputs(packed: PackedProgram, x: jnp.ndarray, *,
                  block_m: int | None = None, block_n: int | None = None,
                  interpret: bool | None = None,
                  return_logits: bool = False,
                  feed: Mapping[str, jnp.ndarray] | None = None,
                  kernels: Kernels | None = None
                  ) -> Iterator[StageOutput]:
    """Run a packed program stage by stage, yielding a ``StageOutput``
    per stage, in program order.

    Buffer names are the graph's layer names (plus ``@``-suffixed ones
    inside attention).  With ``feed`` every stage reads its inputs from
    ``feed`` (e.g. the functional oracle's buffers) instead of from the
    stages before it, so each stage can be held to a reference on
    identical inputs; ``kernels`` swaps in reference kernels for such
    checks (default: ``mounted_gemm`` and ``fb_epilogue``).  Other
    arguments as ``execute_packed``.
    """
    if interpret is None:
        interpret = interpret_default()
    if kernels is None:
        kernels = Kernels(mounted_gemm, fb_epilogue)
    program = packed.program
    cfg = program.cfg
    batch = x.shape[0]
    bufs: dict[str, jnp.ndarray] = {program.input: x}
    src = bufs if feed is None else feed
    stages = program.stages()
    last = _last_reads(stages)
    for si, ((gemm, posts), st) in enumerate(zip(stages, packed.stages)):
        dst = posts[-1].dst if posts else gemm.dst
        with jax.named_scope(f"s{si:02d}.{dst.replace('@', '.')}"):
            if gemm.kind == "dyn_gemm":
                out, acc = _dyn_stage(gemm, posts, st, src, cfg, batch,
                                      block_m=block_m, block_n=block_n,
                                      interpret=interpret, kernels=kernels)
                rows = gemm.dyn == "pv"
            else:
                dst, out, acc, rows = _static_stage(
                    gemm, posts, st, src, cfg, batch, block_m=block_m,
                    block_n=block_n, interpret=interpret,
                    drop_softmax=return_logits and si == len(stages) - 1,
                    kernels=kernels)
        bufs[dst] = out
        # the graph's (B, T, N) buffer; under a jit only the program's
        # output is live, so the other reshapes compile away
        yield StageOutput(dst, _tokens(out, batch) if rows else out, acc)
        # drop buffers no later stage reads: eager forwards hold only
        # the live dataflow frontier
        for name in [n for n, li in last.items() if li <= si]:
            bufs.pop(name, None)
            del last[name]


def execute_packed(packed: PackedProgram, x: jnp.ndarray,
                   *, block_m: int | None = None, block_n: int | None = None,
                   interpret: bool | None = None,
                   return_logits: bool = False) -> jnp.ndarray:
    """Run a packed program on a batch ``x`` (B, H, W, C) float32 — or
    (B, T, D) tokens for sequence-input programs.

    The steady-state hot path: weights are already chip-resident int8
    mount planes (see ``pack.py``), so each stage quantizes its input,
    makes one ``mounted_gemm`` dispatch activating every mount (one
    per batch*head for dynamic attention stages), and one fused
    ``fb_epilogue`` dispatch.  Returns the program output buffer —
    softmax probabilities, or the pre-softmax logits with
    ``return_logits=True`` (the final stage is re-fused without its
    softmax FB, mirroring the functional forward).  ``block_m`` /
    ``block_n`` override the kernels' per-path tile defaults
    (``kernels/tiling.py``); ``None`` keeps them.
    """
    program = packed.program
    ret = program.logits if return_logits else program.output
    out = None
    for stage in stage_outputs(packed, x, block_m=block_m, block_n=block_n,
                               interpret=interpret,
                               return_logits=return_logits):
        if stage.name == ret:
            out = stage.value
    return out

