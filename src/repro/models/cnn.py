"""Functional JAX CNNs (AlexNet / VGG-16 / ResNet-18, CIFAR-10 variants).

Convolutions are expressed as im2col + GEMM so the *same* forward pass can
route every GEMM through either jnp (fp32 reference) or the HURRY crossbar
functional model (`repro.core.crossbar_linear`, int8 bit-sliced with
optional read noise) — that is how the simulator's accuracy claims are
computed rather than assumed.  Param init shapes derive from the
``repro.api.zoo`` builder graphs (the one source of truth for layer
shapes — the same graphs the scheduler lowers), and
``make_program_forward`` runs the same nets through the compiled
``CrossbarProgram`` path (``repro.program``): the scheduler's mount
rounds + FB ops executed on the Pallas crossbar kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crossbar import CrossbarConfig, crossbar_linear

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


def make_program_forward(net: str, cfg: Optional[CrossbarConfig] = None,
                         return_logits: bool = True,
                         **compile_kw) -> Callable[[dict, jnp.ndarray],
                                                   jnp.ndarray]:
    """Compile-then-execute forward: the scheduled program computes.

    Lowers ``net`` once through the scheduler (Algorithms 1 & 2 +
    sequence-pair decoding, ``repro.program.compile``) and returns a
    ``forward(params, x)`` that executes the resulting
    ``CrossbarProgram`` — every GEMM through the ``crossbar_gemm``
    Pallas kernel, every post-op through the fused ``fb_epilogue``
    kernel.  Under a clip-free config this is bit-identical to
    ``forward(params, x, mm=make_crossbar_matmul(cfg))`` when both are
    jitted (DESIGN.md §5).  ``return_logits=True`` mirrors the
    functional forward's output; ``False`` returns the softmax FB's
    probabilities.
    """
    from repro.program import compile_network, execute_program
    program = compile_network(net, cfg=cfg, **compile_kw)

    def forward(params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return execute_program(program, params, x,
                               return_logits=return_logits)
    return forward


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

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


def _graph_init(net: str) -> Callable[[jax.Array], dict]:
    """Param init whose shapes derive from the builder graph.

    ``repro.api.zoo`` graphs are the one source of truth for layer
    shapes; the pytree keys are the graph's GEMM layer names, which the
    handwritten forwards below index by.
    """
    def init(key: jax.Array) -> dict:
        from repro.api.zoo import GRAPHS    # lazy: api builds on models
        return GRAPHS[net]().init_params(key)
    return init


# ---------------------------------------------------------------------------
# AlexNet (CIFAR)
# ---------------------------------------------------------------------------

def alexnet_forward(params: dict, x: jnp.ndarray,
                    mm: MatmulFn = fp_matmul) -> jnp.ndarray:
    pools_after = {1, 2, 5}
    for i in range(1, 6):
        p = params[f"conv{i}"]
        x = jax.nn.relu(conv2d(x, p["w"], p["b"], 1, 1, mm))
        if i in pools_after:
            x = maxpool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(mm(x, params["fc6"]["w"]) + params["fc6"]["b"])
    x = jax.nn.relu(mm(x, params["fc7"]["w"]) + params["fc7"]["b"])
    return mm(x, params["fc8"]["w"]) + params["fc8"]["b"]


# ---------------------------------------------------------------------------
# VGG-16 (CIFAR)
# ---------------------------------------------------------------------------

_VGG_CFG = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
            512, 512, 512, "M", 512, 512, 512, "M"]


def vgg16_forward(params: dict, x: jnp.ndarray,
                  mm: MatmulFn = fp_matmul) -> jnp.ndarray:
    i = 1
    for v in _VGG_CFG:
        if v == "M":
            x = maxpool(x)
        else:
            p = params[f"conv{i}"]
            x = jax.nn.relu(conv2d(x, p["w"], p["b"], 1, 1, mm))
            i += 1
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(mm(x, params["fc1"]["w"]) + params["fc1"]["b"])
    return mm(x, params["fc2"]["w"]) + params["fc2"]["b"]


# ---------------------------------------------------------------------------
# ResNet-18 (CIFAR)
# ---------------------------------------------------------------------------

_RESNET_STAGES = [(64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2)]


def resnet18_forward(params: dict, x: jnp.ndarray,
                     mm: MatmulFn = fp_matmul) -> jnp.ndarray:
    p = params["conv0"]
    x = jax.nn.relu(conv2d(x, p["w"], p["b"], 1, 1, mm))
    for s, (ch, blocks, stage_stride) in enumerate(_RESNET_STAGES):
        for b in range(blocks):
            pre = f"s{s}b{b}"
            stride = stage_stride if b == 0 else 1
            res = x
            p1 = params[f"{pre}_conv1"]
            h = jax.nn.relu(conv2d(x, p1["w"], p1["b"], stride, 1, mm))
            p2 = params[f"{pre}_conv2"]
            h = conv2d(h, p2["w"], p2["b"], 1, 1, mm)
            if f"{pre}_proj" in params:
                pp = params[f"{pre}_proj"]
                res = conv2d(x, pp["w"], pp["b"], stride, 0, mm)
            x = jax.nn.relu(h + res)
    x = x.mean(axis=(1, 2))
    return mm(x, params["fc"]["w"]) + params["fc"]["b"]


@dataclasses.dataclass(frozen=True)
class CNNModel:
    init: Callable[[jax.Array], dict]
    forward: Callable[..., jnp.ndarray]


CNN_MODELS = {
    "alexnet": CNNModel(_graph_init("alexnet"), alexnet_forward),
    "vgg16": CNNModel(_graph_init("vgg16"), vgg16_forward),
    "resnet18": CNNModel(_graph_init("resnet18"), resnet18_forward),
}
