"""CIFAR ResNet with basic blocks (He et al., arXiv:1512.03385; the stem
and head of github.com/kuangliu/pytorch-cifar ``models/resnet.py``).

Sizes come from the configuration file: ``input_hw``, ``input_ch``,
``stem_ch``, ``widths`` (one per stage), ``blocks`` (basic blocks per
stage), ``classes``.  The first block of every stage after the first
downsamples by stride 2; a 1x1 projection carries the shortcut where the
width changes.  The head is a 4x4 average pool and one linear layer,
then softmax.

``graph`` builds the program's network with the public ``repro.api``
builder; ``init`` and ``reference`` are the benchmark's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import Ref


def _blocks(sizes):
    """(name, in_ch, out_ch, stride) of every basic block, in order."""
    in_ch = sizes["stem_ch"]
    for s, (ch, n) in enumerate(zip(sizes["widths"], sizes["blocks"])):
        for b in range(n):
            yield f"s{s}b{b}", in_ch, ch, (2 if s > 0 and b == 0 else 1)
            in_ch = ch


def _final_hw(sizes) -> int:
    hw = sizes["input_hw"]
    for _, _, _, stride in _blocks(sizes):
        hw = (hw + 2 - 3) // stride + 1
    return hw


def graph(sizes):
    from repro.api import NetworkBuilder

    nb = NetworkBuilder(sizes["name"], input_hw=sizes["input_hw"],
                        input_ch=sizes["input_ch"])
    nb.conv(sizes["stem_ch"], name="conv0")
    entry = nb.relu(name="relu0")
    for n, cin, ch, s in _blocks(sizes):
        res = entry
        if cin != ch:
            res = nb.conv(ch, k=1, stride=s, padding=0, name=f"{n}_proj",
                          input_from=entry)
        nb.conv(ch, stride=s, name=f"{n}_conv1", input_from=entry)
        nb.relu(name=f"{n}_relu1")
        nb.conv(ch, name=f"{n}_conv2")
        nb.residual(res, name=f"{n}_res")
        entry = nb.relu(name=f"{n}_relu2")
    hw = _final_hw(sizes)
    nb.avgpool(k=hw, stride=hw, name="avgpool")
    nb.fc(sizes["classes"], name="fc")
    nb.softmax(name="softmax")
    return nb.build()


def _layers(sizes):
    """(param name, weight shape) of every GEMM layer."""
    yield "conv0", (3, 3, sizes["input_ch"], sizes["stem_ch"])
    for n, cin, ch, _ in _blocks(sizes):
        if cin != ch:
            yield f"{n}_proj", (1, 1, cin, ch)
        yield f"{n}_conv1", (3, 3, cin, ch)
        yield f"{n}_conv2", (3, 3, ch, ch)
    yield "fc", (sizes["widths"][-1], sizes["classes"])


def init(key, sizes) -> dict:
    """He-normal weights and small random biases (so the bias and the
    residual paths carry nonzero values)."""
    params = {}
    for i, (name, shape) in enumerate(_layers(sizes)):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        params[name] = {
            "w": jax.random.normal(kw, shape) * jnp.sqrt(2.0 / fan_in),
            "b": 0.1 * jax.random.normal(kb, (shape[-1],))}
    return params


def reference(params, x, sizes, ref: Ref):
    """Probabilities (B, classes) of images (B, H, W, C)."""
    h = ref.conv(x, params["conv0"], "conv0", k=3, stride=1, pad=1,
                 relu=True)
    blocks = list(_blocks(sizes))
    hw = _final_hw(sizes)
    for i, (n, cin, ch, s) in enumerate(blocks):
        res = h
        if cin != ch:
            res = ref.conv(h, params[f"{n}_proj"], f"{n}_proj", k=1,
                           stride=s, pad=0)
        y = ref.conv(h, params[f"{n}_conv1"], f"{n}_conv1", k=3, stride=s,
                     pad=1, relu=True)
        last = i == len(blocks) - 1
        h = ref.conv(y, params[f"{n}_conv2"], f"{n}_conv2", k=3, stride=1,
                     pad=1, residual=res, relu=True,
                     pool=("avg", hw) if last else ())
    h = h.reshape(h.shape[0], -1)
    return ref.dense(h, params["fc"], "fc", softmax=True)
