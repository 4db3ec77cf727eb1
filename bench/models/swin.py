"""Swin-T as published, as the program's sequence IR expresses it.

Sizes from the configuration file: ``input_hw``, ``input_ch``, ``patch``,
``embed_dim``, ``depths``, ``heads``, ``window``, ``mlp_ratio``,
``classes``, ``ln_eps``, ``gelu`` (``"erf"``) and ``mask_value``.  The
forward is the Swin Transformer's (Liu et al., arXiv:2103.14030,
§3.1-3.3), written as timm's ``swin_tiny_patch4_window7_224`` runs it::

    z  = LN(patches(x) E)                        # a map of (hw/patch)^2 tokens
    stage s > 0: z = Linear(LN(concat(x0, x1, x2, x3)))   # patch merging,
                 x0 = z[0::2, 0::2], x1 = z[1::2, 0::2],  # no bias
                 x2 = z[0::2, 1::2], x3 = z[1::2, 1::2]
    block i:  z = z + unshift(W-MSA(shift(LN1(z))))       # shift on odd i
              z = z + MLP(LN2(z))
    p  = softmax(head(mean_tokens(LN(z))))

W-MSA cuts the (rolled) map into ``window x window`` windows and attends
inside each with scores ``q.k^T / sqrt(hd) + B + mask``: ``B`` from the
relative position bias table, the mask ``mask_value`` between tokens
that the roll brought together from different regions.  No shift where
the window covers the map.

Every GEMM is a crossbar stage (``Ref.gemm``; Q.K^T and P.V per (image,
window, head) with ``Ref.batched_gemm``), and every buffer between
stages goes through ``Ref.store``, so the benchmark's controls apply.  A
layer norm that feeds a GEMM is no buffer of its own, as in the program.
The window partition, roll, bias gather, mask, layer norm, softmax and
exact GELU are this file's own; a row sum of a layer norm, of the
softmax and of the token mean is a pairwise sum of the row's halves,
elementwise adds that XLA rounds the same in any layout.

``graph`` builds the program's network with the public ``repro.api``
builder; ``init`` and ``reference`` are the benchmark's own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import erf

from bench.reference import Ref


def graph(sizes):
    from repro.api import NetworkBuilder

    dim, eps, win = sizes["embed_dim"], sizes["ln_eps"], sizes["window"]
    nb = NetworkBuilder(sizes["name"], input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    nb.conv(dim, k=sizes["patch"], stride=sizes["patch"], padding=0,
            name="patch")
    entry = nb.layernorm(eps=eps, name="patch_norm")
    for s, (depth, heads) in enumerate(zip(sizes["depths"], sizes["heads"])):
        if s:
            nb.layernorm(pre=True, eps=eps, name=f"s{s}_merge_norm")
            dim *= 2
            entry = nb.linear(dim, merge=True, bias=False,
                              name=f"s{s}_merge")
        for i in range(depth):
            n = f"s{s}b{i}"
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln1")
            nb.attention(heads, window=win, shift=win // 2 if i % 2 else 0,
                         name=f"{n}_attn")
            r1 = nb.residual(entry, name=f"{n}_res1")
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln2")
            nb.linear(dim * sizes["mlp_ratio"], name=f"{n}_fc1")
            nb.gelu(approx=sizes["gelu"], name=f"{n}_gelu")
            nb.linear(dim, name=f"{n}_fc2")
            entry = nb.residual(r1, name=f"{n}_res2")
    nb.layernorm(eps=eps, name="norm")
    nb.seqpool(mode="mean", name="pool")
    nb.fc(sizes["classes"], name="head")
    nb.softmax(name="softmax")
    return nb.build()


def init(key, sizes) -> dict:
    """He-normal weights; small random biases, and layer-norm gains and
    shifts near 1 and 0, so every functional-block operand is exercised;
    relative position bias tables at 0.1 x normal, so that a bias
    dropped or gathered wrongly shows in the comparison."""
    dim, p, m = sizes["embed_dim"], sizes["patch"], sizes["window"]
    keys = iter(jax.random.split(key, 16 * sum(sizes["depths"]) + 32))

    def lin(shape, bias=True):
        fan_in = math.prod(shape[:-1])
        out = {"w": jax.random.normal(next(keys), shape)
               * jnp.sqrt(2.0 / fan_in)}
        if bias:
            out["b"] = 0.1 * jax.random.normal(next(keys), (shape[-1],))
        return out

    def norm(d):
        return {"g": 1.0 + 0.1 * jax.random.normal(next(keys), (d,)),
                "b": 0.1 * jax.random.normal(next(keys), (d,))}

    params = {"patch": lin((p, p, sizes["input_ch"], dim)),
              "patch_norm": norm(dim)}
    for s, (depth, heads) in enumerate(zip(sizes["depths"], sizes["heads"])):
        if s:
            params[f"s{s}_merge_norm"] = norm(4 * dim)
            params[f"s{s}_merge"] = lin((4 * dim, 2 * dim), bias=False)
            dim *= 2
        hidden = dim * sizes["mlp_ratio"]
        for i in range(depth):
            n = f"s{s}b{i}"
            qkv, out = lin((dim, 3 * dim)), lin((dim, dim))
            params[f"{n}_ln1"] = norm(dim)
            params[f"{n}_attn"] = {
                "wqkv": qkv["w"], "bqkv": qkv["b"], "wo": out["w"],
                "bo": out["b"],
                "relb": 0.1 * jax.random.normal(
                    next(keys), ((2 * m - 1) ** 2, heads))}
            params[f"{n}_ln2"] = norm(dim)
            params[f"{n}_fc1"] = lin((dim, hidden))
            params[f"{n}_fc2"] = lin((hidden, dim))
    params["norm"] = norm(dim)
    params["head"] = lin((dim, sizes["classes"]))
    return params


def row_sum(x):
    """Sum over the last axis, kept: halves added pairwise until one
    column is left (an odd column carried to the next round)."""
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = jnp.concatenate([x[..., :h] + x[..., h:2 * h], x[..., 2 * h:]],
                            axis=-1)
    return x


def layer_norm(x, p, eps):
    n = x.shape[-1]
    m = row_sum(x) / n
    d = x - m
    v = row_sum(d * d) / n
    return d / jnp.sqrt(v + eps) * p["g"] + p["b"]


def softmax(x):
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    return e / row_sum(e)


def gelu(x):
    """Exact GELU: x * Phi(x)."""
    return 0.5 * x * (1.0 + erf(x * (1.0 / math.sqrt(2.0))))


def relative_index(m: int) -> np.ndarray:
    """(m², m²) index into the (2m-1)² bias table: the query's (row,
    col) less the key's, shifted to start at 0, row-major."""
    coords = np.stack(np.meshgrid(np.arange(m), np.arange(m),
                                  indexing="ij")).reshape(2, -1)
    rel = (coords[:, :, None] - coords[:, None, :]).transpose(1, 2, 0)
    rel = rel + (m - 1)
    return rel[:, :, 0] * (2 * m - 1) + rel[:, :, 1]


def partition(x, m: int):
    """(B, H, W, C) -> (B * H/m * W/m, m*m, C): windows, row-major."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // m, m, w // m, m, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, m * m, c)


def reverse(x, m: int, h: int, w: int):
    """``partition``'s inverse: (B * nW, m*m, C) -> (B, H, W, C)."""
    c = x.shape[-1]
    x = x.reshape(-1, h // m, w // m, m, m, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, h, w, c)


def shift_mask(hw: int, m: int, shift: int, value: float) -> np.ndarray:
    """(nW, m², m²): ``value`` between tokens of a window that come from
    different regions of the rolled map, 0 elsewhere."""
    img = np.zeros((1, hw, hw, 1), np.float32)
    cnt = 0
    bands = (slice(0, -m), slice(-m, -shift), slice(-shift, None))
    for hs in bands:
        for ws in bands:
            img[:, hs, ws, :] = cnt
            cnt += 1
    wins = partition(img, m)[..., 0]                 # (nW, m²)
    diff = wins[:, None, :] - wins[:, :, None]
    return np.where(diff != 0, value, 0.0).astype(np.float32)


def block(ref: Ref, z, params, name: str, heads: int, m: int, shift: int,
          sizes):
    """One Swin block on the (B, H, W, C) map."""
    b, hw, _, c = z.shape
    hd = c // heads
    eps = sizes["ln_eps"]
    a = params[f"{name}_attn"]
    x = layer_norm(z, params[f"{name}_ln1"], eps)
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    win = partition(x, m)                               # (B*nW, m², C)
    bw, n, _ = win.shape
    qkv = ref.store(ref.gemm(win.reshape(-1, c), a["wqkv"],
                             f"{name}_attn.qkv") + a["bqkv"])
    qkv = qkv.reshape(bw, n, 3, heads, hd).transpose(2, 0, 3, 1, 4)
    q, k, v = (u.reshape(bw * heads, n, hd) for u in qkv)
    scores = ref.batched_gemm(q, jnp.swapaxes(k, 1, 2), f"{name}_attn.qk")
    scores = scores.reshape(bw, heads, n, n) * (1.0 / math.sqrt(hd))
    table = a["relb"][relative_index(m).reshape(-1)]
    scores = scores + table.reshape(n, n, heads).transpose(2, 0, 1)
    if shift:
        mask = shift_mask(hw, m, shift, sizes["mask_value"])
        nw = mask.shape[0]
        scores = (scores.reshape(b, nw, heads, n, n)
                  + mask[None, :, None]).reshape(bw, heads, n, n)
    probs = ref.store(softmax(scores))
    ctx = ref.batched_gemm(probs.reshape(bw * heads, n, n), v,
                           f"{name}_attn.pv")
    ctx = ref.store(ctx.reshape(bw, heads, n, hd).transpose(0, 2, 1, 3)
                    .reshape(bw, n, c))
    y = ref.gemm(ctx.reshape(-1, c), a["wo"], f"{name}_attn",
                 residual=True) + a["bo"]
    y = reverse(y.reshape(bw, n, c), m, hw, hw)
    if shift:
        y = jnp.roll(y, (shift, shift), axis=(1, 2))
    z = ref.store(z + y)
    f1, f2 = params[f"{name}_fc1"], params[f"{name}_fc2"]
    x = layer_norm(z, params[f"{name}_ln2"], eps).reshape(-1, c)
    h = ref.store(gelu(ref.gemm(x, f1["w"], f"{name}_fc1") + f1["b"]))
    y = ref.gemm(h, f2["w"], f"{name}_fc2", residual=True) + f2["b"]
    return ref.store(z + y.reshape(z.shape))


def merge(ref: Ref, z, norm, w, name: str, eps: float):
    """Patch merging: (B, H, W, C) -> (B, H/2, W/2, 2C)."""
    x = jnp.concatenate([z[:, 0::2, 0::2], z[:, 1::2, 0::2],
                         z[:, 0::2, 1::2], z[:, 1::2, 1::2]], axis=-1)
    lead = x.shape[:-1]
    x = layer_norm(x, norm, eps).reshape(-1, x.shape[-1])
    return ref.store(ref.gemm(x, w["w"], name).reshape(*lead, -1))


def reference(params, x, sizes, ref: Ref):
    """Probabilities (B, classes) of images (B, H, W, C)."""
    eps, m, p = sizes["ln_eps"], sizes["window"], sizes["patch"]
    z = ref.conv(x, params["patch"], "patch", k=p, stride=p, pad=0)
    z = ref.store(layer_norm(z, params["patch_norm"], eps))
    for s, (depth, heads) in enumerate(zip(sizes["depths"], sizes["heads"])):
        if s:
            z = merge(ref, z, params[f"s{s}_merge_norm"],
                      params[f"s{s}_merge"], f"s{s}_merge", eps)
        for i in range(depth):
            shift = m // 2 if i % 2 and z.shape[1] > m else 0
            z = block(ref, z, params, f"s{s}b{i}", heads, m, shift, sizes)
    z = layer_norm(z, params["norm"], eps)
    b, c = z.shape[0], z.shape[-1]
    tokens = z.reshape(b, -1, c)
    pooled = ref.store(row_sum(jnp.swapaxes(tokens, 1, 2))[..., 0]
                       / tokens.shape[1])
    h = params["head"]
    logits = ref.gemm(pooled, h["w"], "head") + h["b"]
    return ref.store(softmax(logits))
