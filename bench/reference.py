"""Plain reference of the crossbar semantics, in straightforward jax.numpy.

The benchmark's own copy: it imports nothing of the program under test,
so a change to the program cannot move the yardstick.  A stage is what
the crossbar program computes for one GEMM and its functional blocks:

    per-tensor symmetric int8 quantization of the input and the weight
    -> exact integer GEMM (the clip-free crossbar: every bitline count
       fits the ADC, so bit slicing and shift-and-add equal one int GEMM)
    -> dequantize (input scale x weight scale) -> + bias -> + residual
    -> [x post scale] -> ReLU | GELU -> layer norm
    -> max / avg pool window | mean over tokens | softmax

Every array between stages is float32.  ``Precision`` lowers that to
bfloat16, or the quantization to fewer bits: the benchmark's control,
which has to come out as not correct.

Model families (``models/``) write their forward passes with these
functions.  Each GEMM stage reports its shape to an optional recorder,
so the work counts (``work.py``) follow the reference and not the
program's own stage list.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
_GELU_C = math.sqrt(2.0 / math.pi)


@dataclasses.dataclass(frozen=True)
class Precision:
    """What the reference computes in.  The configuration states int8
    crossbar operands and float32 everywhere else; a control lowers one
    of them."""

    bits: int = 8                    # crossbar operand bits
    act: str = "float32"             # dtype of every inter-stage buffer


EXACT = Precision()
CONTROLS = {"bf16_activations": Precision(act="bfloat16"),
            "int4_operands": Precision(bits=4)}


@dataclasses.dataclass(frozen=True)
class GemmShape:
    """One crossbar GEMM stage: ``count`` products of (M, K) x (K, N).

    ``count`` is the number of independent operand pairs (batch x heads
    for attention's dynamic stages, 1 otherwise).  ``residual`` says the
    epilogue reads an (M, N) addend; ``out_rows`` is the rows it writes
    per product (fewer than M where it pools).
    """

    name: str
    m: int
    k: int
    n: int
    count: int = 1
    residual: bool = False
    out_rows: int = 0


Recorder = Callable[[GemmShape], None]


class Ref:
    """Reference primitives at one precision, with an optional recorder."""

    def __init__(self, prec: Precision = EXACT, record: Recorder | None = None):
        self.prec = prec
        self.record = record

    # -- numerics ----------------------------------------------------------

    def store(self, x: jnp.ndarray) -> jnp.ndarray:
        """A buffer as it is kept between stages."""
        return x.astype(self.prec.act).astype(jnp.float32)

    def quantize(self, x: jnp.ndarray, axes=None):
        """Symmetric quantization over ``axes`` (all of them: per tensor)
        -> (int8 values, float32 scale)."""
        qmax = (1 << (self.prec.bits - 1)) - 1
        amax = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None)
        scale = jnp.maximum(amax, 1e-8) / qmax
        q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
        return q.astype(jnp.int8), scale

    def gemm(self, x: jnp.ndarray, w: jnp.ndarray, name: str, *,
             residual: bool = False, out_rows: int = 0) -> jnp.ndarray:
        """(M, K) x (K, N) crossbar GEMM, dequantized (no bias)."""
        self._note(GemmShape(name, x.shape[0], x.shape[1], w.shape[1],
                             residual=residual, out_rows=out_rows))
        xq, xs = self.quantize(x)
        wq, ws = self.quantize(w)
        y = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * (xs * ws)

    def batched_gemm(self, a: jnp.ndarray, b: jnp.ndarray,
                     name: str) -> jnp.ndarray:
        """(S, M, K) x (S, K, N) with operands quantized per slice: the
        dynamic attention stages, one mount per (image, head)."""
        s, m, k = a.shape
        self._note(GemmShape(name, m, k, b.shape[2], count=s))
        aq, as_ = self.quantize(a, axes=(1, 2))
        bq, bs = self.quantize(b, axes=(1, 2))
        y = jax.lax.dot_general(aq, bq, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * (as_ * bs)

    def _note(self, shape: GemmShape) -> None:
        if self.record is not None:
            self.record(shape)

    # -- layers ------------------------------------------------------------

    @staticmethod
    def im2col(x: jnp.ndarray, k: int, stride: int, pad: int) -> jnp.ndarray:
        """NHWC -> (N*OH*OW, C*k*k) patches, feature order (c, i, j)."""
        n, h, w, c = x.shape
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        oh = (h + 2 * pad - k) // stride + 1
        ow = (w + 2 * pad - k) // stride + 1
        taps = [xp[:, i:i + stride * (oh - 1) + 1:stride,
                   j:j + stride * (ow - 1) + 1:stride, :]
                for i in range(k) for j in range(k)]
        cols = jnp.stack(taps, axis=-1)              # (N, OH, OW, C, k*k)
        return cols.reshape(n * oh * ow, c * k * k), oh

    def conv(self, x, p, name: str, *, k: int, stride: int, pad: int,
             residual=None, relu: bool = False, pool: tuple = ()):
        """Conv stage: im2col GEMM + bias [+ residual] [ReLU] [pool]."""
        n = x.shape[0]
        cols, oh = self.im2col(x, k, stride, pad)
        w = p["w"]                                   # (k, k, Cin, Cout)
        wm = w.transpose(2, 0, 1, 3).reshape(-1, w.shape[-1])
        out_rows = 0
        if pool:
            out_rows = (oh // pool[1]) ** 2 * n
        y = self.gemm(cols, wm, name, residual=residual is not None,
                      out_rows=out_rows) + p["b"]
        y = y.reshape(n, oh, oh, -1)
        if residual is not None:
            y = y + residual
        if relu:
            y = jnp.maximum(y, 0.0)
        if pool:
            kind, win = pool
            y = y.reshape(n, oh // win, win, oh // win, win, y.shape[-1])
            y = y.max(axis=(2, 4)) if kind == "max" else y.mean(axis=(2, 4))
        return self.store(y)

    def dense(self, x, p, name: str, *, residual=None, act: str = "none",
              norm=None, seqmean: bool = False, softmax: bool = False):
        """Token or flat GEMM stage: (..., K) rows -> (..., N)."""
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        out_rows = lead[0] if seqmean else 0
        y = self.gemm(x2, p["w"], name, residual=residual is not None,
                      out_rows=out_rows) + p["b"]
        y = y.reshape(*lead, -1)
        if residual is not None:
            y = y + residual
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        elif act == "gelu":
            y = gelu(y)
        if norm is not None:
            y = layer_norm(y, norm["g"], norm["b"])
        if seqmean:
            y = y.mean(axis=1)
        if softmax:
            y = softmax_rows(y)
        return self.store(y)

    def attention_core(self, qkv: jnp.ndarray, heads: int,
                       name: str) -> jnp.ndarray:
        """Scores (with the 1/sqrt(hd) scale and softmax) and context of
        every (image, head) from the fused (B, T, 3D) projection."""
        b, t, three_d = qkv.shape
        d = three_d // 3
        hd = d // heads

        def split(u):
            return (u.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
                    .reshape(b * heads, t, hd))

        q, k, v = (split(qkv[..., i * d:(i + 1) * d]) for i in range(3))
        scores = self.batched_gemm(q, jnp.swapaxes(k, 1, 2), f"{name}.qk")
        probs = self.store(softmax_rows(scores * (1.0 / math.sqrt(hd))))
        ctx = self.batched_gemm(probs, v, f"{name}.pv")
        ctx = ctx.reshape(b, heads, t, hd).transpose(0, 2, 1, 3)
        return self.store(ctx.reshape(b, t, d))


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """Tanh-approximated GELU."""
    return 0.5 * x * (1.0 + jnp.tanh(_GELU_C * (x + 0.044715 * x * x * x)))


def layer_norm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    m = jnp.mean(x, axis=-1, keepdims=True)
    d = x - m
    v = jnp.mean(d * d, axis=-1, keepdims=True)
    return d / jnp.sqrt(v + LN_EPS) * g + b


def softmax_rows(x: jnp.ndarray) -> jnp.ndarray:
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)
