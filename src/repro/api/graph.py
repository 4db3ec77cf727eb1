"""Network IR: ``NetworkBuilder`` -> ``NetworkGraph`` with shape inference.

The builder is the front-door authoring surface — users describe a
network op by op (``nb.conv(...)``, ``nb.relu()``, ``nb.maxpool()``,
``nb.residual(from_=...)``, ``nb.fc(...)``, ``nb.softmax()``) and every
call infers the output shape from the running input shape, validating as
it goes: GEMM-headed groups (a non-GEMM layer before any GEMM head is an
error naming the layer), known wiring sources, shape-matched residuals,
window == stride pooling (the only pooling the FB column tiling maps),
and the canonical FB chain order ``residual -> relu -> pool -> softmax``
(paper Fig 4a / §II-C2).  Errors surface at *build* time with the
offending layer's name, not deep inside the compiler.

**Sequence mode** (DESIGN.md §9): the same builder authors transformer
graphs over ``(T, D)`` token shapes — ``nb.linear(features)``,
``nb.layernorm(eps=)``, ``nb.gelu(approx=)``, ``nb.attention(heads)``,
``nb.seqpool(mode=)``, ``nb.embed()``.  A spatial buffer entering a
sequence op is rasterized into ``T = hw^2`` tokens (the ViT patchify
transition); ``nb.embed()`` after the patchify conv does the same with
a learned class token prepended and a learned position table added
(``T = hw^2 + 1``).  A network may also start directly in token space
via ``NetworkBuilder(input_seq_dim=D)``, in which case the sequence
length is a run-time property of the batch (``T`` is tracked as 0
during inference of shapes).  The sequence FB chain order is
``residual -> gelu -> layernorm -> seqpool`` (post-norm transformer
blocks).  Pre-norm blocks (``x + f(LN(x))``) put
``nb.layernorm(pre=True)`` before the GEMM op: the next ``linear``,
``attention`` or ``fc`` normalizes its input, and residuals that read
that input still read it un-normed.

Hierarchical, windowed transformers (Swin) work on a square grid of
raster tokens, whose side the builder tracks through every sequence
shape (a class token leaves a sequence without one): a
``nb.layernorm()`` may follow a patchify conv (the patch norm), and its
tokens keep the map's grid; ``nb.attention(heads, window=M, shift=s)``
attends inside each ``M x M`` window of the map rolled by ``-s``, with
a learned relative position bias per head and the shift's region mask;
``nb.linear(features, merge=True, bias=False)`` is patch merging: its
operand is the 2x2 space-to-depth of the map (half the grid side, four
times the width), which a ``layernorm(pre=True)`` before it normalizes.

The resulting ``NetworkGraph`` is the one source of truth for layer
shapes: the scheduler consumes its ``LayerSpec`` list, ``init_params``
derives the parameter pytree from it, and ``forward`` is a generic
functional interpreter (the im2col primitives of ``core/conv.py``, GEMMs
routed through any ``mm`` — fp32 or the crossbar functional model) used
as the numeric reference for compiled programs.  Attention routes all
four of its GEMMs (fused qkv projection, per-head Q·Kᵀ, per-head P·V,
output projection) through the same ``mm``, so the oracle evaluates the
crossbar-quantized attention the compiled program executes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.conv import conv2d, maxpool
from repro.core.crossbar import fp_matmul
from repro.core.workload import (GEMM_KINDS, LayerSpec, POST_RANK,
                                 input_spec, layer_groups)
from repro.kernels.fb_epilogue import (gelu, gelu_erf, layer_norm_ordered,
                                       softmax_ordered, token_mean)
from repro.program.sequence import (attn_scale, embed_tokens, merge_heads,
                                    rel_bias, shift_mask, space_to_depth,
                                    split_qkv_heads, tokens, window_bias,
                                    window_merge, window_qkv_heads)

# shapes are ("spatial", hw, ch) until an fc flattens to ("flat", features)
# or a sequence op rasterizes to ("seq", tokens, dim); T == 0 marks a
# run-time sequence length (sequence-input nets)
_SPATIAL, _FLAT, _SEQ = "spatial", "flat", "seq"
_AUTO_PREFIX = {"conv": "conv", "fc": "fc", "relu": "relu",
                "maxpool": "pool", "avgpool": "avgpool",
                "residual": "res", "softmax": "softmax",
                "linear": "lin", "layernorm": "ln", "gelu": "gelu",
                "attention": "attn", "seqpool": "seqpool",
                "embed": "embed"}


def _as_tokens(shape: tuple) -> tuple:
    """Shape-level analogue of ``sequence.tokens``: spatial -> seq."""
    if shape[0] == _SPATIAL:
        return (_SEQ, shape[1] * shape[1], shape[2])
    return shape


@dataclasses.dataclass(frozen=True)
class NetworkGraph:
    """A validated, shape-inferred network: the builder's output."""

    name: str
    in_hw: int
    in_ch: int
    layers: tuple[LayerSpec, ...]
    in_features: int = 0          # set instead of hw/ch for fc-first nets
    in_seq: int = 0               # model dim for sequence-input nets

    def input_shape(self, batch: int = 1, seq_len: int = 16
                    ) -> tuple[int, ...]:
        if self.in_seq:
            return (batch, seq_len, self.in_seq)
        if self.in_features:
            return (batch, self.in_features)
        return (batch, self.in_hw, self.in_hw, self.in_ch)

    def init_params(self, key: jax.Array) -> dict:
        """He-init parameter pytree whose shapes come from the graph.

        One source of truth: ``api.compile`` inits through here, so
        layer shapes exist in exactly one place.
        """
        params: dict = {}
        for i, l in enumerate(self.layers):
            k = jax.random.fold_in(key, i)
            if l.prenorm:
                params[l.prenorm] = {"g": jnp.ones((l.features_in,)),
                                     "b": jnp.zeros((l.features_in,))}
            if l.kind == "conv":
                fan_in = l.ksize * l.ksize * l.in_ch
                w = jax.random.normal(
                    k, (l.ksize, l.ksize, l.in_ch, l.out_ch)
                ) * jnp.sqrt(2.0 / fan_in)
                params[l.name] = {"w": w, "b": jnp.zeros((l.out_ch,))}
            elif l.kind in ("fc", "linear"):
                w = jax.random.normal(
                    k, (l.features_in, l.features_out)
                ) * jnp.sqrt(2.0 / l.features_in)
                params[l.name] = {"w": w}
                if l.bias:
                    params[l.name]["b"] = jnp.zeros((l.features_out,))
            elif l.kind == "attention":
                d = l.features_in
                k1, k2 = jax.random.split(k)
                params[l.name] = {
                    "wqkv": jax.random.normal(k1, (d, 3 * d))
                    * jnp.sqrt(2.0 / d),
                    "bqkv": jnp.zeros((3 * d,)),
                    "wo": jax.random.normal(k2, (d, d)) * jnp.sqrt(2.0 / d),
                    "bo": jnp.zeros((d,)),
                }
                if l.window:     # Swin's init: std 0.02
                    params[l.name]["relb"] = 0.02 * jax.random.normal(
                        jax.random.fold_in(k, 2),
                        ((2 * l.window - 1) ** 2, l.heads))
            elif l.kind == "layernorm":
                params[l.name] = {"g": jnp.ones((l.features_out,)),
                                  "b": jnp.zeros((l.features_out,))}
            elif l.kind == "embed":
                # ViT's init: both drawn at std 0.02
                k1, k2 = jax.random.split(k)
                d = l.features_out
                params[l.name] = {
                    "cls": 0.02 * jax.random.normal(k1, (d,)),
                    "pos": 0.02 * jax.random.normal(
                        k2, (l.in_hw * l.in_hw + 1, d))}
        return params

    def forward(self, params: dict, x: jnp.ndarray, *,
                mm: Callable = fp_matmul, logits: bool = False
                ) -> jnp.ndarray:
        """Generic functional forward over the graph (the numeric oracle).

        Interprets the layer list with the im2col primitives of
        ``core/conv.py``, routing every GEMM through ``mm``
        (``make_crossbar_matmul(cfg)`` for the crossbar model) —
        including the two *dynamic-operand* GEMMs inside attention,
        which vmap ``mm`` over the (batch, head) axis exactly as the
        packed executor vmaps its crossbar dispatch (DESIGN.md §9).
        Under a clip-free config this matches the compiled-program path
        bitwise when both are jitted (DESIGN.md §5).  ``logits=True``
        returns the last GEMM output (pre-softmax).
        """
        bufs = self.buffers(params, x, mm=mm)
        gemms = [l.name for l in self.layers if l.kind in GEMM_KINDS]
        return bufs[gemms[-1] if logits else self.layers[-1].name]

    def buffers(self, params: dict, x: jnp.ndarray, *,
                mm: Callable = fp_matmul) -> dict[str, jnp.ndarray]:
        """Every layer's output, keyed by layer name (``"input"`` too).

        The oracle's dataflow laid bare, for setting it beside another
        implementation stage by stage.  An attention layer also records
        its inner results as ``<layer>.qkv`` (fused projection,
        ``(B, T, 3D)``), ``<layer>.probs`` (per batch*head softmax; per
        image*window*head for a windowed one) and ``<layer>.ctx``
        (merged context, raster tokens).  A GEMM head's pre-norm is
        part of its layer (no buffer of its own).  Arguments as
        ``forward``.
        """
        bufs: dict[str, jnp.ndarray] = {"input": x}
        cur = "input"

        def gemm_input(l):
            """A linear/attention/fc layer's input: tokens (their 2x2
            space-to-depth for a merge), or a flat row per image for fc;
            layer-normed under ``prenorm``."""
            src = bufs[l.input_from or cur]
            if l.kind != "fc":
                src = tokens(src)
                if l.merge:
                    src = space_to_depth(src, l.in_hw)
            elif src.ndim == 4:
                src = src.reshape(src.shape[0], -1)
            if l.prenorm:
                p = params[l.prenorm]
                src = layer_norm_ordered(src, p["g"], p["b"], l.eps)
            return src

        for l in self.layers:
            if l.kind == "conv":
                src = bufs[l.input_from or cur]
                p = params[l.name]
                y = conv2d(src, p["w"], p["b"], l.stride, l.padding, mm)
            elif l.kind == "fc":
                src = gemm_input(l)
                p = params[l.name]
                y = mm(src, p["w"]) + p["b"]
            elif l.kind == "linear":
                src = gemm_input(l)
                b, t, d = src.shape
                p = params[l.name]
                y = mm(src.reshape(b * t, d), p["w"])
                if l.bias:
                    y = y + p["b"]
                y = y.reshape(b, t, -1)
            elif l.kind == "attention":
                src = gemm_input(l)
                b, t, d = src.shape
                p = params[l.name]
                qkv = mm(src.reshape(b * t, d), p["wqkv"]) + p["bqkv"]
                if l.window:
                    win = (l.heads, l.in_hw, l.window, l.shift)
                    q, kk, v = window_qkv_heads(qkv.reshape(b, t, 3 * d),
                                                *win)
                else:
                    q, kk, v = split_qkv_heads(qkv.reshape(b, t, 3 * d),
                                               l.heads)
                scores = jax.vmap(lambda a, w: mm(a, w.T))(q, kk)
                scores = scores * attn_scale(d // l.heads)
                if l.window:
                    scores = window_bias(
                        scores.reshape(b, -1, *scores.shape[1:]),
                        rel_bias(p["relb"], l.window),
                        shift_mask(l.in_hw, l.window, l.shift)
                    ).reshape(scores.shape)
                probs = softmax_ordered(scores)
                ctx = jax.vmap(mm)(probs, v)
                ctx = (window_merge(ctx, *win) if l.window
                       else merge_heads(ctx, l.heads))
                y = (mm(ctx.reshape(b * t, d), p["wo"])
                     + p["bo"]).reshape(b, t, d)
                bufs[f"{l.name}.qkv"] = qkv.reshape(b, t, 3 * d)
                bufs[f"{l.name}.probs"] = probs
                bufs[f"{l.name}.ctx"] = ctx
            elif l.kind == "relu":
                y = jax.nn.relu(bufs[cur])
            elif l.kind == "gelu":
                y = (gelu_erf if l.approx == "erf" else gelu)(bufs[cur])
            elif l.kind == "layernorm":
                p = params[l.name]
                y = layer_norm_ordered(tokens(bufs[cur]), p["g"], p["b"],
                                       l.eps)
            elif l.kind == "embed":
                p = params[l.name]
                y = embed_tokens(tokens(bufs[cur]), p["cls"], p["pos"])
            elif l.kind == "maxpool":
                y = maxpool(bufs[cur], l.ksize, l.stride)
            elif l.kind == "avgpool":
                v = bufs[cur]
                b, h, w_, c = v.shape
                y = v.reshape(b, h // l.ksize, l.ksize,
                              w_ // l.ksize, l.ksize, c).mean(axis=(2, 4))
            elif l.kind == "seqpool":
                v = tokens(bufs[cur])
                y = v[:, 0] if l.mode == "cls" else token_mean(v)
            elif l.kind == "residual":
                a = bufs[cur]
                r = bufs[l.residual_from]
                y = a + (tokens(r) if a.ndim == 3 else r)
            elif l.kind == "softmax":
                y = jax.nn.softmax(bufs[cur], axis=-1)
            else:
                raise ValueError(f"{l.name}: unknown layer kind {l.kind!r}")
            bufs[l.name] = y
            cur = l.name
        return bufs

    @classmethod
    def from_layers(cls, layers, name: str = "custom") -> "NetworkGraph":
        """Wrap a raw ``LayerSpec`` list (compat path for old call sites).

        Validates GEMM-headed grouping; the input spec is read off the
        first layer.
        """
        layers = tuple(layers)
        if not layers:
            raise ValueError("empty network")
        for _ in layer_groups(list(layers)):   # raises on headless groups
            pass
        ihw, ich, ifeat, iseq = input_spec(list(layers))
        return cls(name=name, in_hw=ihw, in_ch=ich, in_features=ifeat,
                   in_seq=iseq, layers=layers)


class NetworkBuilder:
    """Incremental network authoring with per-op shape inference.

    Every method appends one layer, infers its output shape, validates,
    and returns the layer's name (usable as ``input_from=`` /
    ``from_=`` wiring for branches).  ``build()`` returns the immutable
    ``NetworkGraph``.  Pass ``input_hw``/``input_ch`` for image-input
    nets or ``input_seq_dim`` for token-input nets ((B, T, D) batches
    with T chosen at run time).
    """

    def __init__(self, name: str = "custom", *, input_hw: int = 0,
                 input_ch: int = 0, input_seq_dim: int = 0):
        has_img = bool(input_hw or input_ch)
        if bool(input_seq_dim) == has_img:
            raise ValueError(
                f"{name}: pass either input_hw+input_ch (image input) or "
                "input_seq_dim (token input)")
        if has_img and not (input_hw and input_ch):
            raise ValueError(
                f"{name}: image input needs BOTH input_hw and input_ch "
                f"(got hw={input_hw}, ch={input_ch})")
        self.name = name
        self._in = (input_hw, input_ch, input_seq_dim)
        self._layers: list[LayerSpec] = []
        self._shapes: dict[str, tuple] = {
            "input": ((_SEQ, 0, input_seq_dim) if input_seq_dim
                      else (_SPATIAL, input_hw, input_ch))}
        self._cur = "input"
        self._grids: dict[str, int] = {}   # token buffer -> grid side
        self._finals = {"input"}      # materialized group-final buffers
        self._counts: dict[str, int] = {}
        self._has_gemm = False
        self._head_kind = ""          # kind of the current group's head
        self._prenorm: tuple[str, float] | None = None   # (name, eps)

    # -- internals ---------------------------------------------------------

    def _name(self, kind: str, name: str | None) -> str:
        if name is None:
            n = self._counts.get(kind, 0) + 1
            self._counts[kind] = n
            name = f"{_AUTO_PREFIX[kind]}{n}"
        if name in self._shapes:
            raise ValueError(f"duplicate layer name {name!r}")
        return name

    def _src_shape(self, name: str, src: str, want: str) -> tuple:
        if src not in self._shapes:
            raise ValueError(f"{name}: unknown input layer {src!r}")
        shape = self._shapes[src]
        if want == _SEQ:
            shape = _as_tokens(shape)      # spatial rasterizes into tokens
        if shape[0] != want:
            raise ValueError(
                f"{name}: needs a {want} input, but {src!r} produces "
                f"{shape[0]} output {shape[1:]}")
        return shape

    def _grid(self, name: str) -> int:
        """Side of the square grid of ``name``'s raster tokens: a map's
        own side, a token buffer's as tracked, 0 without one (a class
        token, flat features, a run-time sequence)."""
        shape = self._shapes[name]
        if shape[0] == _SPATIAL:
            return shape[1]
        return self._grids.get(name, 0) if shape[0] == _SEQ else 0

    def _require_gemm(self, name: str, kind: str) -> None:
        if not self._has_gemm:
            raise ValueError(
                f"layer {name!r} ({kind}) precedes any GEMM layer; every "
                "post-op must follow a GEMM group head — conv/fc, or "
                "linear/attention for sequence chains (HURRY schedules "
                "GEMM-headed FB groups)")

    def _require_seq_head(self, name: str, kind: str) -> None:
        """Sequence FBs only fuse onto linear/attention-headed groups.

        A conv/fc group cannot host them (the compiler's CNN lowering
        has no such FB requests), so reject at build time with the
        layer named rather than deep inside ``compile_network``.
        """
        self._require_gemm(name, kind)
        if self._head_kind not in ("linear", "attention"):
            raise ValueError(
                f"layer {name!r} ({kind}) is a sequence FB but its group "
                f"head is a {self._head_kind}; gelu/layernorm/seqpool "
                "fuse onto linear or attention group heads only")

    def _open_group(self, name: str, input_from: str, kind: str) -> str:
        """A new GEMM closes the previous group: its output materializes.

        Returns the resolved source name; validates explicit wiring only
        targets materialized group-final buffers.
        """
        self._finals = self._finals | {self._cur}
        src = input_from or self._cur
        if input_from and input_from not in self._finals:
            raise ValueError(
                f"{name}: input_from={input_from!r} is not a materialized "
                "group output (only group-final buffers are wired)")
        self._has_gemm = True
        self._head_kind = kind
        return src

    def _take_prenorm(self, name: str, kind: str) -> dict:
        """The pending pre-norm as a GEMM head's fields (and clear it)."""
        if self._prenorm is None:
            return {}
        if kind not in ("fc", "linear", "attention"):
            raise ValueError(
                f"{name}: a pre-norm ({self._prenorm[0]!r}) must be "
                f"followed by a linear, attention or fc layer, not {kind}")
        norm, eps = self._prenorm
        self._prenorm = None
        return {"prenorm": norm, "eps": eps}

    def _add(self, spec: LayerSpec, shape: tuple,
             grid: int | None = None) -> str:
        """Append ``spec``; its tokens' ``grid`` side defaults to the
        current buffer's (post-ops keep the map)."""
        if self._prenorm is not None:       # a GEMM head took it already
            self._take_prenorm(spec.name, spec.kind)
        self._grids[spec.name] = self._grid(self._cur) if grid is None \
            else grid
        self._layers.append(spec)
        self._shapes[spec.name] = shape
        self._cur = spec.name
        return spec.name

    # -- ops ---------------------------------------------------------------

    def conv(self, out_ch: int, k: int = 3, stride: int = 1,
             padding: int = 1, *, name: str | None = None,
             input_from: str = "") -> str:
        name = self._name("conv", name)
        src = self._open_group(name, input_from, "conv")
        _, hw, ch = self._src_shape(name, src, _SPATIAL)
        out_hw = (hw + 2 * padding - k) // stride + 1
        if out_hw <= 0:
            raise ValueError(f"{name}: {k}x{k}/s{stride}/p{padding} conv "
                             f"over {hw}x{hw} input has no output")
        return self._add(
            LayerSpec(name, "conv", in_ch=ch, out_ch=out_ch, ksize=k,
                      stride=stride, padding=padding, in_hw=hw,
                      out_hw=out_hw, input_from=input_from),
            (_SPATIAL, out_hw, out_ch))

    def fc(self, features_out: int, *, name: str | None = None,
           input_from: str = "") -> str:
        name = self._name("fc", name)
        src = self._open_group(name, input_from, "fc")
        shape = self._shapes.get(src)
        if shape is None:
            raise ValueError(f"{name}: unknown input layer {src!r}")
        fin = shape[1] * shape[1] * shape[2] if shape[0] == _SPATIAL \
            else shape[1]
        return self._add(
            LayerSpec(name, "fc", features_in=fin,
                      features_out=features_out, input_from=input_from,
                      **self._take_prenorm(name, "fc")),
            (_FLAT, features_out))

    def linear(self, features_out: int, *, merge: bool = False,
               bias: bool = True, name: str | None = None,
               input_from: str = "") -> str:
        """Sequence GEMM: (T, D) -> (T, features_out), tokens in M.

        ``merge=True`` is patch merging: the operand is the 2x2
        space-to-depth of the ``g x g`` token grid, (T/4, 4D), and the
        output grid is ``g/2``.  ``bias=False`` drops the bias.
        """
        name = self._name("linear", name)
        src = self._open_group(name, input_from, "linear")
        _, t, d = self._src_shape(name, src, _SEQ)
        grid, in_hw = self._grid(src), 0
        if merge:
            if not grid or grid % 2:
                raise ValueError(
                    f"{name}: patch merging needs an even square token "
                    f"grid; {src!r} has "
                    + (f"a {grid}x{grid} one" if grid else "none"))
            in_hw, t, d, grid = grid, t // 4, 4 * d, grid // 2
        return self._add(
            LayerSpec(name, "linear", features_in=d,
                      features_out=features_out, input_from=input_from,
                      in_hw=in_hw, merge=merge, bias=bias,
                      **self._take_prenorm(name, "linear")),
            (_SEQ, t, features_out), grid)

    def attention(self, heads: int, *, window: int = 0, shift: int = 0,
                  name: str | None = None, input_from: str = "") -> str:
        """Multi-head self-attention over the token buffer, (T, D)->(T, D).

        One builder op; the program compiler expands it into the fused
        qkv projection, the two dynamic-operand GEMM stages (Q·Kᵀ with a
        fused softmax FB, P·V), and the output projection (DESIGN.md §9).

        ``window=M`` attends inside each ``M x M`` window of the square
        token grid instead (Swin's W-MSA), with a learned relative
        position bias of ``(2M-1)^2 x heads``; ``shift=s`` rolls the
        map by ``-s`` first and masks pairs of tokens from different
        regions (SW-MSA).  The window must tile a grid without a class
        token, and ``0 <= shift < window``; a window as large as the
        grid is one window, which takes no shift (as published).
        """
        name = self._name("attention", name)
        src = self._open_group(name, input_from, "attention")
        _, t, d = self._src_shape(name, src, _SEQ)
        if heads < 1 or d % heads:
            raise ValueError(
                f"{name}: {heads} heads do not divide model dim {d}")
        grid = self._grid(src)
        if window:
            if not grid:
                raise ValueError(
                    f"{name}: windowed attention needs a square token grid "
                    f"without a class token; {src!r} has none")
            if window < 1 or grid % window:
                raise ValueError(f"{name}: a {window}x{window} window does "
                                 f"not tile the {grid}x{grid} token grid")
            if not 0 <= shift < window:
                raise ValueError(f"{name}: shift {shift} is not in "
                                 f"[0, {window})")
            if window == grid:
                shift = 0
        elif shift:
            raise ValueError(f"{name}: a shift needs a window")
        return self._add(
            LayerSpec(name, "attention", features_in=d, features_out=d,
                      heads=heads, input_from=input_from,
                      in_hw=grid if window else 0, window=window,
                      shift=shift, **self._take_prenorm(name, "attention")),
            (_SEQ, t, d), grid)

    def relu(self, *, name: str | None = None) -> str:
        name = self._name("relu", name)
        self._require_gemm(name, "relu")
        shape = self._shapes[self._cur]
        if shape[0] == _SPATIAL:
            spec = LayerSpec(name, "relu", out_ch=shape[2], out_hw=shape[1])
        else:
            spec = LayerSpec(name, "relu", features_out=shape[-1])
        return self._add(spec, shape)

    def gelu(self, *, approx: str = "tanh", name: str | None = None) -> str:
        """GELU FB (sequence chains; the LUT analogue of the relu FB):
        the tanh approximation or the exact ``approx="erf"`` form."""
        name = self._name("gelu", name)
        if approx not in ("tanh", "erf"):
            raise ValueError(f"{name}: gelu approx {approx!r} is not "
                             "'tanh' or 'erf'")
        self._require_seq_head(name, "gelu")
        shape = self._src_shape(name, self._cur, _SEQ)
        return self._add(
            LayerSpec(name, "gelu", features_out=shape[2], approx=approx),
            shape)

    def layernorm(self, *, pre: bool = False, eps: float = 1e-5,
                  name: str | None = None) -> str:
        """Layer norm over the feature axis, with epsilon ``eps``.

        By default an FB post-op of the current group's token buffer
        (post-norm), or of a patchify conv's map, whose tokens it
        writes (the patch norm).  ``pre=True`` normalizes the input of
        the next
        ``linear``, ``attention`` or ``fc`` op instead (pre-norm): it
        adds no layer and no buffer, the GEMM head carries it, and a
        residual that reads the same input reads it un-normed.
        """
        name = self._name("layernorm", name)
        if pre:
            if self._prenorm is not None:
                raise ValueError(f"{name}: pre-norm {self._prenorm[0]!r} "
                                 "has no GEMM op yet")
            self._shapes[name] = self._shapes[self._cur]   # name taken
            self._prenorm = (name, eps)
            return name
        self._require_gemm(name, "layernorm")
        if self._head_kind != "conv":
            self._require_seq_head(name, "layernorm")
        shape = self._src_shape(name, self._cur, _SEQ)
        return self._add(
            LayerSpec(name, "layernorm", features_out=shape[2], eps=eps),
            shape)

    def seqpool(self, *, mode: str = "mean", name: str | None = None) -> str:
        """Pool the token axis: (T, D) -> flat (D,) (ViT-style head) —
        the mean of the tokens, or with ``mode="cls"`` the class token
        (row 0; ``embed`` put it there)."""
        name = self._name("seqpool", name)
        if mode not in ("mean", "cls"):
            raise ValueError(f"{name}: seqpool mode {mode!r} is not "
                             "'mean' or 'cls'")
        self._require_seq_head(name, "seqpool")
        shape = self._src_shape(name, self._cur, _SEQ)
        return self._add(
            LayerSpec(name, "seqpool", features_out=shape[2], mode=mode),
            (_FLAT, shape[2]))

    def embed(self, *, name: str | None = None) -> str:
        """Token embedding FB of a patchify conv: its (hw, hw, D) map as
        hw^2 row-major tokens, a learned class token prepended (token 0)
        and a learned (hw^2 + 1, D) position table added."""
        name = self._name("embed", name)
        self._require_gemm(name, "embed")
        if self._head_kind != "conv":
            raise ValueError(
                f"layer {name!r} (embed) fuses onto a patchify conv group "
                f"head, not a {self._head_kind}")
        _, hw, ch = self._src_shape(name, self._cur, _SPATIAL)
        return self._add(
            LayerSpec(name, "embed", in_hw=hw, out_ch=ch, features_out=ch),
            (_SEQ, hw * hw + 1, ch), 0)

    def _pool(self, kind: str, k: int, stride: int,
              name: str | None) -> str:
        name = self._name(kind, name)
        self._require_gemm(name, kind)
        if k != stride:
            raise ValueError(
                f"{name}: only window == stride pooling maps onto the FB "
                f"column tiling (got window {k}, stride {stride})")
        _, hw, ch = self._src_shape(name, self._cur, _SPATIAL)
        if hw % k:
            raise ValueError(f"{name}: {k}x{k} window does not tile the "
                             f"{hw}x{hw} input")
        return self._add(
            LayerSpec(name, kind, out_ch=ch, ksize=k, stride=stride,
                      in_hw=hw, out_hw=hw // stride),
            (_SPATIAL, hw // stride, ch))

    def maxpool(self, k: int = 2, stride: int = 2, *,
                name: str | None = None) -> str:
        return self._pool("maxpool", k, stride, name)

    def avgpool(self, k: int = 2, stride: int = 2, *,
                name: str | None = None) -> str:
        return self._pool("avgpool", k, stride, name)

    def residual(self, from_: str, *, name: str | None = None) -> str:
        name = self._name("residual", name)
        self._require_gemm(name, "residual")
        if from_ not in self._finals:
            raise ValueError(
                f"{name}: residual source {from_!r} is not a materialized "
                "group output (it must be a previous group's final buffer)")
        shape = self._shapes[self._cur]
        src_shape = self._shapes[from_]
        if shape[0] == _SEQ:           # spatial addends rasterize to tokens
            src_shape = _as_tokens(src_shape)
        if src_shape != shape:
            raise ValueError(
                f"{name}: residual source {from_!r} shape "
                f"{src_shape[1:]} != current {shape[1:]}")
        if shape[0] == _SEQ:
            spec = LayerSpec(name, "residual", features_out=shape[2],
                             residual_from=from_)
        else:
            _, hw, ch = self._src_shape(name, self._cur, _SPATIAL)
            spec = LayerSpec(name, "residual", out_ch=ch, out_hw=hw,
                             residual_from=from_)
        return self._add(spec, shape)

    def softmax(self, *, name: str | None = None) -> str:
        name = self._name("softmax", name)
        self._require_gemm(name, "softmax")
        shape = self._src_shape(name, self._cur, _FLAT)
        return self._add(
            LayerSpec(name, "softmax", features_out=shape[1]), shape)

    # -- finalize ----------------------------------------------------------

    def build(self) -> NetworkGraph:
        if not self._layers:
            raise ValueError(f"{self.name}: empty network")
        if self._prenorm is not None:
            raise ValueError(f"{self._prenorm[0]}: pre-norm with no GEMM "
                             "op after it")
        # grouping + canonical chain order validation (same POST_RANK
        # table as the compiler, so errors surface at build time with
        # layer names and the two checks can never diverge)
        for group in layer_groups(list(self._layers)):
            rank = -1
            for l in group[1:]:
                if POST_RANK[l.kind] <= rank:
                    raise ValueError(
                        f"{l.name}: {l.kind} out of canonical FB chain "
                        "order (residual -> relu|gelu -> pool -> "
                        "layernorm -> embed|seqpool -> softmax) in "
                        f"group {group[0].name!r}")
                rank = POST_RANK[l.kind]
        hw, ch, seq = self._in
        return NetworkGraph(name=self.name, in_hw=hw, in_ch=ch,
                            in_seq=seq, layers=tuple(self._layers))
