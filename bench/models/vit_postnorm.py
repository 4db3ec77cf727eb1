"""Vision transformer as the program's sequence IR expresses it.

Sizes from the configuration file: ``input_hw``, ``input_ch``, ``patch``,
``dim``, ``depth``, ``heads``, ``mlp_ratio``, ``classes``.  A
``patch x patch`` stride-``patch`` convolution cuts the image into
``(input_hw / patch)^2`` tokens of width ``dim``; each of the ``depth``
blocks is ``x = LN(x + MHA(x)); x = LN(x + MLP(x))`` with a GELU MLP of
width ``mlp_ratio * dim``; the head averages the tokens and classifies.
The configuration file lists where this departs from the published
model (post-norm, mean-pooled head, no position embedding).

``graph`` builds the program's network with the public ``repro.api``
builder; ``init`` and ``reference`` are the benchmark's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import Ref


def graph(sizes):
    from repro.api import NetworkBuilder

    dim = sizes["dim"]
    nb = NetworkBuilder(sizes["name"], input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    entry = nb.conv(dim, k=sizes["patch"], stride=sizes["patch"], padding=0,
                    name="patch")
    for i in range(sizes["depth"]):
        nb.attention(sizes["heads"], name=f"b{i}_attn")
        nb.residual(entry, name=f"b{i}_res1")
        r1 = nb.layernorm(name=f"b{i}_ln1")
        nb.linear(dim * sizes["mlp_ratio"], name=f"b{i}_fc1")
        nb.gelu(name=f"b{i}_gelu")
        nb.linear(dim, name=f"b{i}_fc2")
        nb.residual(r1, name=f"b{i}_res2")
        entry = nb.layernorm(name=f"b{i}_ln2")
    nb.seqpool(name="pool")
    nb.fc(sizes["classes"], name="head")
    nb.softmax(name="softmax")
    return nb.build()


def init(key, sizes) -> dict:
    """He-normal weights; small random biases, and layer-norm gains and
    shifts near 1 and 0, so every functional-block operand is exercised."""
    dim, p = sizes["dim"], sizes["patch"]
    hidden = dim * sizes["mlp_ratio"]
    keys = iter(jax.random.split(key, 12 * sizes["depth"] + 4))

    def lin(shape):
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        return {"w": jax.random.normal(next(keys), shape)
                * jnp.sqrt(2.0 / fan_in),
                "b": 0.1 * jax.random.normal(next(keys), (shape[-1],))}

    def norm():
        return {"g": 1.0 + 0.1 * jax.random.normal(next(keys), (dim,)),
                "b": 0.1 * jax.random.normal(next(keys), (dim,))}

    params = {"patch": lin((p, p, sizes["input_ch"], dim))}
    for i in range(sizes["depth"]):
        qkv, out = lin((dim, 3 * dim)), lin((dim, dim))
        params[f"b{i}_attn"] = {"wqkv": qkv["w"], "bqkv": qkv["b"],
                                "wo": out["w"], "bo": out["b"]}
        params[f"b{i}_ln1"] = norm()
        params[f"b{i}_fc1"] = lin((dim, hidden))
        params[f"b{i}_fc2"] = lin((hidden, dim))
        params[f"b{i}_ln2"] = norm()
    params["head"] = lin((dim, sizes["classes"]))
    return params


def reference(params, x, sizes, ref: Ref):
    """Probabilities (B, classes) of images (B, H, W, C)."""
    b = x.shape[0]
    h = ref.conv(x, params["patch"], "patch", k=sizes["patch"],
                 stride=sizes["patch"], pad=0)
    h = h.reshape(b, -1, sizes["dim"])               # row-major tokens
    depth = sizes["depth"]
    for i in range(depth):
        a = params[f"b{i}_attn"]
        qkv = ref.dense(h, {"w": a["wqkv"], "b": a["bqkv"]}, f"b{i}_attn.qkv")
        ctx = ref.attention_core(qkv, sizes["heads"], f"b{i}_attn")
        r1 = ref.dense(ctx, {"w": a["wo"], "b": a["bo"]}, f"b{i}_attn",
                       residual=h, norm=params[f"b{i}_ln1"])
        y = ref.dense(r1, params[f"b{i}_fc1"], f"b{i}_fc1", act="gelu")
        h = ref.dense(y, params[f"b{i}_fc2"], f"b{i}_fc2", residual=r1,
                      norm=params[f"b{i}_ln2"], seqmean=i == depth - 1)
    return ref.dense(h, params["head"], "head", softmax=True)
