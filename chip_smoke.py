#!/usr/bin/env python3
"""Chip smoke test: the crossbar program stack's main path on one TPU.

    python chip_smoke.py [--nets deit_ti,resnet18]

One process, one chip.  It drives what a user calls —
``api.compile(graph, HurryConfig) -> CompiledModel.run`` — for every
network of the zoo at its full geometry: ``alexnet``, ``vgg16`` and
``resnet18`` at 32x32, ``vit_tiny`` at depth 12 (dim 192, 3 heads, MLP
ratio 4, 64 tokens), ``deit_ti`` (DeiT-Ti at 224x224: pre-norm
blocks, 197 tokens with the class token, erf GELU) and ``swin_t``
(Swin-T at 224x224: shifted windows, patch merging, served at batch 2).
``--nets`` keeps
the networks named (the kernel phase always runs).  Weights are random
from a fixed seed.  Phases:

* kernels — ``mounted_gemm`` on the exact path (mount and dense
  layouts) and the sliced path at real stage shapes, bit-exact against the XLA integer references of
  ``kernels/ref.py``; ``fb_epilogue`` in every mode against its XLA
  reference, held to ``FB_TAIL_ULP`` normwise ulps (DESIGN.md §5);
* serve — request batches of 1, 3 and 8 through ``CompiledModel.run``
  (batch buckets 1, 4, 8) under the clip-free config, each set beside
  the jitted functional oracle on the same chip: bit-exact, or the
  first stage that departs is a float FB tail within ``FB_TAIL_ULP``
  (later stages inherit its departure through their quantization);
  argmax agreement 1.0;
* stages — at batch 8 every stage is fed the oracle's own input
  buffers (``stage_outputs(feed=...)``), so no departure carries over,
  and run twice: with the Pallas kernels and with their XLA references
  (one integer dot; ``fb_epilogue_ref``).  Each stage's int32 GEMM
  result is held bit-exact and its output within ``FB_TAIL_ULP``, times
  the largest logit magnitude for a softmax tail (``tail_bound``);
* sliced — an 8-bit-ADC config, under which every mount over 255 rows
  runs the sliced kernel, on ``alexnet`` and ``vit_tiny``, within
  ``tests/test_program.py``'s tolerance of the oracle (the kernel phase
  holds ADC clipping itself to ``crossbar_gemm_ref`` bit for bit);
* save/load — ``save`` -> ``api.load`` -> ``run``, bit-identical.

Timings printed on the way are smoke output, not benchmark numbers.
The last line is ``{"ok": true, "device": {...}}``.  Off a TPU the
script exits non-zero before any phase and prints no result; so does a
failed check, after all phases ran.  The phase functions take their
sizes as arguments, so ``tests/test_chip_smoke.py`` runs them small on
the CPU in interpret mode.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.api import HurryConfig  # noqa: E402
from repro.api.zoo import GRAPHS  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.crossbar import fp_matmul, make_crossbar_matmul  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.crossbar_gemm import (dense_blocks,  # noqa: E402
                                        dense_layout, mount_layout,
                                        mounted_gemm)
from repro.kernels.fb_epilogue import fb_epilogue  # noqa: E402
from repro.kernels.ops import interpret_default  # noqa: E402
from repro.program.execute import (Kernels, execute_packed,  # noqa: E402
                                   stage_outputs)

SEED = 0
CLIP_FREE = HurryConfig(array_rows=511)      # every mount clip-free (§4)
# 8-bit ADC: every mount over 255 rows can clip, so it takes the sliced
# kernel (kernels/crossbar_gemm.py::clip_possible)
SLICED = HurryConfig(adc_bits=8)
NETS = (("alexnet", 0), ("vgg16", 0), ("resnet18", 0), ("vit_tiny", 12),
        ("deit_ti", 12), ("swin_t", 0))
# request batches of a network that is not served at ``BATCHES``
NET_BATCHES = {"swin_t": (2,)}
SLICED_NETS = (("alexnet", 0), ("vit_tiny", 12))
BATCHES = (1, 3, 8)
STEADY_RUNS = 5
# largest normwise error (``ulp_error``) allowed between the fused FB
# epilogue (Mosaic) and its XLA oracle on identical inputs (DESIGN.md §5)
FB_TAIL_ULP = 16

# real stage shapes (name, M, K, N, rows per mount); M is cut to 256 —
# the mount layout under test is K, N and rows — except in the edge-block
# cases, whose M is the point
GEMM_CASES = (
    ("alexnet_conv1", 256, 27, 64, 27),
    ("alexnet_conv2", 256, 576, 192, 485),
    ("conv_k4608", 256, 4608, 512, 485),
    ("vit_fc1", 256, 192, 768, 192),
    ("vit_fc2", 256, 768, 192, 451),
    ("alexnet_fc8", 8, 1024, 10, 429),
    # DeiT-Ti at batch 64 (12,608 token rows): M and N divide no block,
    # so both end in edge blocks
    ("deit_qkv_b64", 64 * 197, 192, 576, 485),
    ("deit_fc2_b64", 64 * 197, 768, 192, 485),
)
# fb_epilogue modes (name, M, N, kwargs, residual?)
EPILOGUE_CASES = (
    ("plain_n1024", 8, 1024, dict(act="relu"), False),
    ("maxpool_32to16", 8 * 1024, 64, dict(act="relu", pool="max", window=2,
                                          img_hw=32), False),
    ("maxpool_8to4", 8 * 64, 256, dict(act="relu", pool="max", window=2,
                                       img_hw=8), False),
    ("maxpool_4to2", 8 * 16, 512, dict(act="relu", pool="max", window=2,
                                       img_hw=4), False),
    ("maxpool_2to1", 8 * 4, 512, dict(act="relu", pool="max", window=2,
                                      img_hw=2), False),
    ("avgpool_4x4", 8 * 16, 512, dict(act="relu", pool="avg", window=4,
                                      img_hw=4), True),
    ("gelu_n768", 8 * 64, 768, dict(act="gelu"), False),
    ("dequant_t197_n768", 8 * 197, 768, dict(), False),
    ("softmax_n10", 8, 10, dict(softmax=True), False),
    ("scores_t64", 64, 64, dict(softmax=True, post_scale=0.125), False),
    ("scores_t197", 197, 197, dict(softmax=True, post_scale=0.125), False),
    # Swin-T at batch 64: a windowed scores mount (7x7 tokens, head dim
    # 32), the patch stage and fc1 of stage 1 before their XLA tails
    # (200,704 token rows), and fc2 of stage 4 with its residual
    ("scores_m49", 49, 49, dict(post_scale=32 ** -0.5), False),
    ("swin_patch_b64", 64 * 3136, 96, dict(), False),
    ("swin_fc1_b64", 64 * 3136, 384, dict(), False),
    ("swin_fc2_res_s4_b64", 64 * 49, 768, dict(), True),
    # DeiT-Ti at batch 64: 12,608 rows end in an edge row block
    ("deit_fc2_res_b64", 64 * 197, 192, dict(), True),
)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def ulp_distance(a, b) -> int:
    """Largest distance between two f32 arrays in units in the last place."""
    def ordered(v):
        i = np.asarray(v, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        raise ValueError(f"shape {a.shape} != {b.shape}")
    return int(np.abs(ordered(a) - ordered(b)).max(initial=0))


def ulp_error(a, b) -> float:
    """``max |a - b|`` in ulps of ``max |b|``: the normwise error.

    A float tail's elementwise ulp distance explodes where a result
    cancels to near zero (layer norm's ``+ beta``, a mean of signed
    values), though the absolute error stays at the rounding level of
    the tensor's magnitude; this measure counts in that level.
    """
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if a.shape != b.shape:
        raise ValueError(f"shape {a.shape} != {b.shape}")
    unit = np.spacing(np.abs(b).max(initial=np.float32(0)))
    return float(np.abs(a - b).max(initial=0) / unit)


def graph_of(net: str, depth: int):
    """The zoo's network, cut to ``depth`` blocks where it takes one."""
    if net in ("vit_tiny", "deit_ti"):
        return GRAPHS[net](depth=depth)
    return GRAPHS[net]()


def random_params(graph, seed: int) -> dict:
    """He-init weights plus random biases, gammas and betas, so every FB
    operand is exercised (the init leaves biases at zero)."""
    params = graph.init_params(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4096))
    return {layer: {k: (v if k in ("w", "wqkv", "wo")
                        else v + 0.1 * jax.random.normal(next(keys), v.shape))
                    for k, v in p.items()}
            for layer, p in params.items()}


def request(graph, batch: int, seed: int) -> jnp.ndarray:
    return jax.random.normal(jax.random.PRNGKey(seed + 1000 * batch),
                             graph.input_shape(batch))


def oracle_buffers(graph, config: HurryConfig, params, x) -> dict:
    """Every buffer of the jitted functional oracle (crossbar ``mm``):
    logits, probabilities and each stage's output in one compile."""
    mm = make_crossbar_matmul(config.crossbar())
    return jax.jit(lambda p, v: graph.buffers(p, v, mm=mm))(params, x)


def oracle_name(buffer: str) -> str:
    """The oracle's name for a program buffer: attention's inner stage
    ``<layer>@qkv`` is the oracle's ``<layer>.qkv`` (``graph.buffers``)."""
    return buffer.replace("@", ".")


def run_stages(model, x, **kw) -> list:
    """``stage_outputs`` under one jit -> [(name, value, acc)] in
    program order."""
    names: list[str] = []

    def run(pk, v):              # a list keeps program order (dicts sort)
        outs = list(stage_outputs(pk, v, **kw))
        names[:] = [o.name for o in outs]
        return [(o.value, o.acc) for o in outs]

    return [(n, v, a) for n, (v, a) in zip(names,
                                           jax.jit(run)(model.packed, x))]


def first_divergence(model, x, oracle: dict
                     ) -> tuple[str, int, float] | None:
    """The first stage buffer (program order) that differs from the
    ``oracle`` buffer of the same name, with its elementwise ulp
    distance and normwise ``ulp_error``; None when every buffer is
    bit-identical."""
    for name, value, _ in run_stages(model, x):
        want = oracle[oracle_name(name)]
        if not np.array_equal(np.asarray(value), np.asarray(want)):
            return name, ulp_distance(value, want), ulp_error(value, want)
    return None


def xla_gemm(x, w, *, rows, layout="mounted", **_):
    """The clip-free crossbar GEMM as one XLA integer dot on the laid-out
    operands (zero rows add nothing): ``mounted_gemm``'s reference."""
    if layout == "dense":
        return ref.crossbar_gemm_exact_ref(x, w[:x.shape[1]])
    return ref.crossbar_gemm_exact_ref(mount_layout(x, rows, 1), w)


def xla_epilogue(y, scale, bias, residual=None, *, block_m=None,
                 block_n=None, interpret=None, **kw):
    """``fb_epilogue``'s XLA reference (``kernels/ref.py``), same
    signature."""
    return ref.fb_epilogue_ref(y, scale, bias, residual, **kw)


def xla_logits_epilogue(*args, softmax=False, **kw):
    """``xla_epilogue`` without its softmax: the logits a softmax tail
    reads."""
    return xla_epilogue(*args, **kw)


def tail_bound(logits) -> float:
    """``FB_TAIL_ULP`` for a softmax tail scaled by its condition
    number, the largest logit magnitude: a logit ``z`` rounded one ulp
    apart moves ``p`` by about ``p * |z| * 2^-23`` (DESIGN.md §5)."""
    return FB_TAIL_ULP * max(1.0, float(np.abs(np.asarray(logits)).max()))


def stage_check(model, x, oracle: dict) -> dict:
    """Feed every stage the oracle's input buffers and run the program
    with its kernels and with their XLA references.

    Returns ``bad_gemm``, the stages whose int32 GEMM results differ;
    ``tail``, ``bound`` and ``tail_stage``: the normwise ``ulp_error``
    between the two runs' outputs at the stage where it is largest
    against its bound (``FB_TAIL_ULP``, or ``tail_bound`` for a softmax
    tail); and ``oracle`` and ``oracle_stage``, the largest departure of
    the XLA run's stage outputs from the oracle's buffers — what a
    stage's own XLA code (input quantization, im2col) departs by when
    compiled apart from the oracle.
    """
    feed = {name.replace(".", "@"): v for name, v in oracle.items()}
    got = run_stages(model, x, feed=feed)
    want = run_stages(model, x, feed=feed,
                      kernels=Kernels(xla_gemm, xla_epilogue))
    logits = run_stages(model, x, feed=feed,
                        kernels=Kernels(xla_gemm, xla_logits_epilogue))
    softmax = [any(op.kind == "softmax" for op in posts)
               for _, posts in model.program.stages()]
    bad_gemm = [name for (name, _, acc), (_, _, ref_acc) in zip(got, want)
                if not np.array_equal(np.asarray(acc), np.asarray(ref_acc))]
    tails = [(ulp_error(v, w), tail_bound(z) if sm else FB_TAIL_ULP, name)
             for (name, v, _), (_, w, _), (_, z, _), sm
             in zip(got, want, logits, softmax)]
    tail, bound, tail_stage = max(tails, key=lambda t: t[0] / t[1])
    dep, dep_stage = max((ulp_error(w, oracle[oracle_name(name)]), name)
                         for name, w, _ in want)
    return dict(bad_gemm=bad_gemm, tail=tail, bound=bound,
                tail_stage=tail_stage, oracle=dep, oracle_stage=dep_stage)


def layout_tally(model) -> str:
    """How many stages took each K layout (``PackedProgram.layouts``)."""
    layouts = model.packed.layouts()
    return "stage layouts " + ", ".join(
        f"{layouts.count(kind)}/{len(layouts)} {kind}"
        for kind in ("dense", "mounted"))


def check(checks: list, name: str, ok: bool, detail: str) -> None:
    print(f"check {'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
    checks.append((name, ok))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def kernel_phase(gemm_cases=GEMM_CASES, epilogue_cases=EPILOGUE_CASES,
                 seed: int = SEED) -> list:
    """Kernels at real stage shapes against their XLA references."""
    checks: list = []
    interpret = interpret_default()
    key = jax.random.PRNGKey(seed)
    for name, m, k, n, rows in gemm_cases:
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.randint(kx, (m, k), -128, 128).astype(jnp.int8)
        w = jax.random.randint(kw, (k, n), -128, 128).astype(jnp.int8)
        wm = mount_layout(w, rows, 0)
        y = mounted_gemm(x, wm, adc_bits=9, rows=rows, exact=True,
                         interpret=interpret)
        yr = ref.crossbar_gemm_exact_ref(x, w)
        check(checks, f"gemm/{name}/exact", np.array_equal(y, yr),
              f"M={m} K={k} N={n} rows={rows} -> K_mounted={wm.shape[0]}")
        wd = dense_layout(w, 0)
        y = mounted_gemm(x, wd, adc_bits=9, rows=rows, layout="dense",
                         interpret=interpret)
        check(checks, f"gemm/{name}/dense", np.array_equal(y, yr),
              f"dense layout: K_dense={wd.shape[0]}, K blocks of "
              f"{dense_blocks(k)[1]}")
        y = mounted_gemm(x, wm, adc_bits=7, rows=rows, exact=False,
                         interpret=interpret)
        yr = ref.crossbar_gemm_ref(x, w, adc_bits=7, rows=rows)
        check(checks, f"gemm/{name}/sliced", np.array_equal(y, yr),
              "7-bit ADC, per-mount clipping vs crossbar_gemm_ref")
    f32 = jnp.float32
    for name, m, n, kw, has_res in epilogue_cases:
        ks = jax.random.split(jax.random.fold_in(key, m * n), 3)
        y = jax.random.randint(ks[0], (m, n), -20000, 20000, jnp.int32)
        scale = jnp.full((1, 1), 3e-4, f32)
        bias = jax.random.normal(ks[1], (n,), f32)
        res = jax.random.normal(ks[2], (m, n), f32) if has_res else None
        out = fb_epilogue(y, scale, bias, res, interpret=interpret, **kw)
        want = jax.jit(lambda *a, kw=kw: ref.fb_epilogue_ref(*a, **kw))(
            y, scale, bias, res)
        e = ulp_error(out, want)
        check(checks, f"epilogue/{name}", e <= FB_TAIL_ULP,
              f"vs fb_epilogue_ref: normwise {e:.3g} ulp (bound "
              f"{FB_TAIL_ULP}), elementwise {ulp_distance(out, want)} ulp")
    return checks


def serve_phase(net: str, depth: int, batches=BATCHES,
                steady_runs: int = STEADY_RUNS, seed: int = SEED):
    """Compile one network clip-free, answer request batches, and set
    every answer beside the jitted oracle.  Returns (model, checks)."""
    checks: list = []
    graph = graph_of(net, depth)
    params = random_params(graph, seed)
    t0 = time.perf_counter()
    model = api.compile(graph, CLIP_FREE, params=params)
    print(f"smoke {net}: api.compile {time.perf_counter() - t0:.2f} s "
          f"(graph lowering + weight packing); {layout_tally(model)}",
          flush=True)
    agree, worst = [], 0
    for b in batches:
        x = request(graph, b, seed)
        t0 = time.perf_counter()
        y = jax.block_until_ready(model.run(x))
        first_s = time.perf_counter() - t0
        oracle = oracle_buffers(graph, CLIP_FREE, params, x)
        want = oracle[model.program.output]
        assert y.shape == want.shape, (y.shape, want.shape)
        u = ulp_distance(y, want)
        worst = max(worst, u)
        agree.append(float((np.argmax(y, -1) == np.argmax(want, -1)).mean()))
        print(f"smoke {net}: batch {b} -> shape {tuple(y.shape)}, first "
              f"run {first_s:.2f} s (trace + compile), probs vs oracle "
              f"{u} ulp elementwise, {ulp_error(y, want):.3g} normwise",
              flush=True)
    times = []
    for _ in range(steady_runs):
        t0 = time.perf_counter()
        jax.block_until_ready(model.run(x))
        times.append((time.perf_counter() - t0) * 1e3)
    print(f"smoke {net}: steady batch {batches[-1]} request "
          f"{statistics.median(times):.3f} ms (median of {steady_runs}; "
          f"smoke output, not a benchmark)", flush=True)
    check(checks, f"serve/{net}/finite", bool(np.isfinite(y).all()),
          "probabilities finite")
    check(checks, f"serve/{net}/argmax", min(agree) == 1.0,
          f"argmax agreement with the oracle per batch {agree}")

    logits = model.run(x, logits=True)
    exact = bool(np.array_equal(logits, oracle[model.program.logits]))
    div = None if exact and worst == 0 else first_divergence(model, x,
                                                             oracle)
    if div is None:
        check(checks, f"serve/{net}/bit_exact", exact and worst == 0,
              "logits and probabilities bit-exact vs the jitted oracle")
    else:
        check(checks, f"serve/{net}/fb_tail", div[2] <= FB_TAIL_ULP,
              f"logits bit-exact={exact}; first departing stage "
              f"{div[0]!r}: normwise {div[2]:.3g} ulp (bound "
              f"{FB_TAIL_ULP}), elementwise {div[1]} ulp")
    st = stage_check(model, x, oracle)
    n_stages, bad = len(model.packed.stages), st["bad_gemm"]
    check(checks, f"stages/{net}/gemm", not bad,
          f"batch {batches[-1]}, oracle inputs: int32 GEMM bit-exact vs "
          f"XLA in {n_stages - len(bad)} of {n_stages} stages"
          + (f"; differ: {bad}" if bad else ""))
    check(checks, f"stages/{net}/fb_tail", st["tail"] <= st["bound"],
          f"batch {batches[-1]}, oracle inputs: largest stage output "
          f"departure against its bound, kernels vs XLA references, "
          f"{st['tail']:.3g} normwise ulp at {st['tail_stage']!r} (bound "
          f"{st['bound']:.4g})")
    print(f"smoke {net}: oracle inputs, XLA-reference stages vs the "
          f"oracle's buffers: largest departure {st['oracle']:.3g} "
          f"normwise ulp at {st['oracle_stage']!r}", flush=True)
    with jax.default_matmul_precision("float32"):
        fp = jax.jit(lambda p, v: graph.forward(p, v, mm=fp_matmul,
                                                logits=True))(params, x)
    rel = float(np.linalg.norm(np.asarray(logits) - np.asarray(fp))
                / np.linalg.norm(np.asarray(fp)))
    fp_agree = float((np.argmax(logits, -1) == np.argmax(fp, -1)).mean())
    print(f"smoke {net}: vs float32 forward: logits rel-err {rel:.4g}, "
          f"argmax agreement {fp_agree}", flush=True)
    return model, checks


def compiled_phase(model, batch: int = 1, seed: int = SEED) -> list:
    """The program lowers to compiled Pallas kernels, not interpreted."""
    checks: list = []
    x = request(model.graph, batch, seed)
    hlo = jax.jit(lambda pk, v: execute_packed(pk, v)).lower(
        model.packed, x).as_text()
    n = hlo.count("tpu_custom_call")
    check(checks, f"compiled/{model.graph.name}",
          n > 0 and not interpret_default(),
          f"{n} tpu_custom_call sites, interpret_default()="
          f"{interpret_default()}")
    return checks


def sliced_phase(net: str, depth: int, batch: int = 8,
                 seed: int = SEED) -> list:
    """The 8-bit-ADC config runs the sliced kernel; program vs oracle
    within ``tests/test_program.py``'s tolerance (where clipping fires,
    chunk boundaries differ: mounts vs array rows, DESIGN.md §5)."""
    checks: list = []
    graph = graph_of(net, depth)
    params = random_params(graph, seed)
    model = api.compile(graph, SLICED, params=params)
    print(f"smoke {net}: sliced (8-bit ADC) {layout_tally(model)}",
          flush=True)
    x = request(graph, batch, seed)
    t0 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(model.run(x, logits=True)))
    first_s = time.perf_counter() - t0
    want = np.asarray(oracle_buffers(graph, SLICED, params,
                                     x)[model.program.logits])
    rel = float(np.linalg.norm(out - want) / np.linalg.norm(want))
    corr = float(np.corrcoef(out.ravel(), want.ravel())[0, 1])
    print(f"smoke {net}: sliced (8-bit ADC) batch {batch} first run "
          f"{first_s:.2f} s, equal to oracle={np.array_equal(out, want)}",
          flush=True)
    check(checks, f"sliced/{net}", rel < 0.2 and corr > 0.98,
          f"rel-err {rel:.4g} (< 0.2), corr {corr:.6f} (> 0.98)")
    return checks


def save_load_phase(model, batch: int = 3, seed: int = SEED) -> list:
    """save -> api.load -> run serves the same bits."""
    x = request(model.graph, batch, seed)
    y = model.run(x)
    with tempfile.TemporaryDirectory() as d:
        loaded = api.load(model.save(os.path.join(d, "model.npz")))
        y2 = loaded.run(x)
    checks: list = []
    check(checks, f"save_load/{model.graph.name}",
          bool(np.array_equal(y, y2)), "loaded model bit-identical")
    return checks


def main(argv=()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nets", default=",".join(n for n, _ in NETS),
                    help="comma-separated networks of NETS to run")
    nets = ap.parse_args(argv).nets.split(",")
    unknown = set(nets) - {n for n, _ in NETS}
    if unknown:
        ap.error(f"unknown networks {sorted(unknown)}")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"smoke device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    checks = kernel_phase()
    for net, depth in NETS:
        if net not in nets:
            continue
        model, c = serve_phase(net, depth,
                               batches=NET_BATCHES.get(net, BATCHES))
        checks += c + compiled_phase(model)
        if net in ("alexnet", "deit_ti", "swin_t"):
            checks += save_load_phase(model)
    for net, depth in SLICED_NETS:
        if net in nets:
            checks += sliced_phase(net, depth)
    failed = [name for name, ok in checks if not ok]
    if failed:
        print(f"chip_smoke: {len(failed)} of {len(checks)} checks failed: "
              f"{failed}", file=sys.stderr)
        return 1
    print(f"smoke: all {len(checks)} checks passed", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
