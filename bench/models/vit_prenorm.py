"""DeiT-Ti as published, as the program's sequence IR expresses it.

Sizes from the configuration file: ``input_hw``, ``input_ch``, ``patch``,
``dim``, ``depth``, ``heads``, ``mlp_ratio``, ``classes``, ``ln_eps`` and
``gelu`` (``"erf"``, the exact form).  The forward is ViT's
(Dosovitskiy et al., arXiv:2010.11929, Eqs. 1-4), which DeiT keeps
(Touvron et al., arXiv:2012.12877)::

    z0  = [x_cls ; patches(x) E] + E_pos       # (input_hw/patch)^2 + 1 tokens
    z'l = z(l-1) + MSA(LN1(z(l-1)))            # heads of dim/heads
    zl  = z'l   + MLP(LN2(z'l))                # dim -> mlp_ratio*dim -> dim
    p   = softmax(head(LN(zL[0])))             # the class token

Every GEMM is a crossbar stage (``Ref.gemm``, attention's Q.K^T and P.V
per (image, head) with ``Ref.batched_gemm``), and every buffer between
stages goes through ``Ref.store``, so the benchmark's controls apply.  A
layer norm feeds a GEMM directly and is no buffer of its own, as in the
program.  The GELU, the layer norm and attention are this file's own:
the exact GELU through ``jax.scipy.special.erf``, the layer norm with
the configuration's epsilon, and the softmax of the scores.  A row sum
of the layer norm and of the softmax is a pairwise sum, written as
elementwise adds of the row's halves: XLA rounds those the same in any
layout, where the order of a ``reduce`` follows the layout XLA picks
for its operand, so the sums come out as the program's on any chip.

``graph`` builds the program's network with the public ``repro.api``
builder; ``init`` and ``reference`` are the benchmark's own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.scipy.special import erf

from bench.reference import Ref


def graph(sizes):
    from repro.api import NetworkBuilder

    dim, eps = sizes["dim"], sizes["ln_eps"]
    nb = NetworkBuilder(sizes["name"], input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    nb.conv(dim, k=sizes["patch"], stride=sizes["patch"], padding=0,
            name="patch")
    entry = nb.embed(name="embed")
    for i in range(sizes["depth"]):
        nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln1")
        nb.attention(sizes["heads"], name=f"b{i}_attn")
        r1 = nb.residual(entry, name=f"b{i}_res1")
        nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln2")
        nb.linear(dim * sizes["mlp_ratio"], name=f"b{i}_fc1")
        nb.gelu(approx=sizes["gelu"], name=f"b{i}_gelu")
        nb.linear(dim, name=f"b{i}_fc2")
        entry = nb.residual(r1, name=f"b{i}_res2")
    nb.seqpool(mode="cls", name="pool")
    nb.layernorm(pre=True, eps=eps, name="norm")
    nb.fc(sizes["classes"], name="head")
    nb.softmax(name="softmax")
    return nb.build()


def init(key, sizes) -> dict:
    """He-normal weights; small random biases, and layer-norm gains and
    shifts near 1 and 0, so every functional-block operand is exercised;
    the class token and the position table standard-normal, so that an
    embedding dropped or put in the wrong place shows in the
    comparison."""
    dim, p = sizes["dim"], sizes["patch"]
    hidden = dim * sizes["mlp_ratio"]
    tokens = (sizes["input_hw"] // p) ** 2 + 1
    keys = iter(jax.random.split(key, 14 * sizes["depth"] + 10))

    def lin(shape):
        fan_in = math.prod(shape[:-1])
        return {"w": jax.random.normal(next(keys), shape)
                * jnp.sqrt(2.0 / fan_in),
                "b": 0.1 * jax.random.normal(next(keys), (shape[-1],))}

    def norm():
        return {"g": 1.0 + 0.1 * jax.random.normal(next(keys), (dim,)),
                "b": 0.1 * jax.random.normal(next(keys), (dim,))}

    params = {"patch": lin((p, p, sizes["input_ch"], dim)),
              "embed": {"cls": jax.random.normal(next(keys), (dim,)),
                        "pos": jax.random.normal(next(keys),
                                                 (tokens, dim))}}
    for i in range(sizes["depth"]):
        qkv, out = lin((dim, 3 * dim)), lin((dim, dim))
        params[f"b{i}_ln1"] = norm()
        params[f"b{i}_attn"] = {"wqkv": qkv["w"], "bqkv": qkv["b"],
                                "wo": out["w"], "bo": out["b"]}
        params[f"b{i}_ln2"] = norm()
        params[f"b{i}_fc1"] = lin((dim, hidden))
        params[f"b{i}_fc2"] = lin((hidden, dim))
    params["norm"] = norm()
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


def attention(ref: Ref, qkv, heads: int, name: str):
    """Scores with the 1/sqrt(hd) scale and softmax, and the context,
    of every (image, head), from the fused (B, T, 3D) projection."""
    b, t, three_d = qkv.shape
    d = three_d // 3
    hd = d // heads

    def split(u):
        return (u.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
                .reshape(b * heads, t, hd))

    q, k, v = (split(qkv[..., i * d:(i + 1) * d]) for i in range(3))
    scores = ref.batched_gemm(q, jnp.swapaxes(k, 1, 2), f"{name}.qk")
    probs = ref.store(softmax(scores * (1.0 / math.sqrt(hd))))
    ctx = ref.batched_gemm(probs, v, f"{name}.pv")
    ctx = ctx.reshape(b, heads, t, hd).transpose(0, 2, 1, 3)
    return ref.store(ctx.reshape(b, t, d))


def gelu(x):
    """Exact GELU: x * Phi(x)."""
    return 0.5 * x * (1.0 + erf(x * (1.0 / math.sqrt(2.0))))


def reference(params, x, sizes, ref: Ref):
    """Probabilities (B, classes) of images (B, H, W, C)."""
    b, dim, eps = x.shape[0], sizes["dim"], sizes["ln_eps"]

    def linear(h, w, bias, name, norm=None, residual=None):
        """A token or flat GEMM stage: [LN ->] GEMM + bias [+ residual]."""
        lead = h.shape[:-1]
        h = h.reshape(-1, h.shape[-1])
        if norm is not None:
            h = layer_norm(h, norm, eps)
        y = ref.gemm(h, w, name, residual=residual is not None) + bias
        y = y.reshape(*lead, -1)
        return y if residual is None else y + residual

    h = ref.conv(x, params["patch"], "patch", k=sizes["patch"],
                 stride=sizes["patch"], pad=0)
    h = h.reshape(b, -1, dim)                        # row-major tokens
    e = params["embed"]
    cls = jnp.broadcast_to(e["cls"].reshape(1, 1, dim), (b, 1, dim))
    h = ref.store(jnp.concatenate([cls, h], axis=1) + e["pos"])
    for i in range(sizes["depth"]):
        a = params[f"b{i}_attn"]
        qkv = ref.store(linear(h, a["wqkv"], a["bqkv"], f"b{i}_attn.qkv",
                               norm=params[f"b{i}_ln1"]))
        ctx = attention(ref, qkv, sizes["heads"], f"b{i}_attn")
        h = ref.store(linear(ctx, a["wo"], a["bo"], f"b{i}_attn",
                             residual=h))
        f1, f2 = params[f"b{i}_fc1"], params[f"b{i}_fc2"]
        y = ref.store(gelu(linear(h, f1["w"], f1["b"], f"b{i}_fc1",
                                  norm=params[f"b{i}_ln2"])))
        h = ref.store(linear(y, f2["w"], f2["b"], f"b{i}_fc2", residual=h))
    p = params["head"]
    logits = linear(h[:, 0], p["w"], p["b"], "head", norm=params["norm"])
    return ref.store(softmax(logits))
