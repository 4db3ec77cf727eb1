"""Compile-only tests for a described TPU v5e chip: no chip needed.

The TPU compiler is installed even where no chip is attached, and it
refuses what interpret mode accepts: K blocks off the 128-lane tiling,
output blocks of fewer than 8 rows, 1-D operand blocks, tiles that
overflow VMEM.  These tests compile the main path's kernels at real
stage shapes, and whole programs (DeiT-Ti at its published size and a
depth-cut Swin-T among them), for one chip of a described ``v5e:2x2``
topology, and check that the Pallas kernels lowered to Mosaic (``tpu_custom_call``)
rather than to the interpreter.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, so every pytest
worker must collect the same tests and only the one given this file
may load it.  All such tests live in this one file for that reason.
"""

import importlib.util
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.api.zoo import deit_graph, swin_graph, vit_tiny_graph
from repro.kernels.crossbar_gemm import (dense_layout, mount_layout,
                                        mounted_gemm)
from repro.kernels.fb_epilogue import fb_epilogue
from repro.program.execute import execute_packed

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    """Lower + compile ``fn`` for the described chip; its HLO text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # Mosaic kernels, not interpreted
    return text


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("path", ["exact", "sliced"])
@pytest.mark.parametrize("case", smoke.GEMM_CASES, ids=lambda c: c[0])
def test_crossbar_gemm_compiles(one_chip, case, path):
    _, m, k, n, rows = case
    # the weights' K in the mount layout: what pack.plane_pack stores
    k_mounted = jax.eval_shape(lambda a: mount_layout(a, rows, 0),
                               jax.ShapeDtypeStruct((k, n), jnp.int8)).shape[0]
    exact = path == "exact"
    _compile(lambda x, w: mounted_gemm(x, w, adc_bits=9 if exact else 7,
                                       rows=rows, exact=exact,
                                       interpret=False),
             _shape(one_chip, (m, k), jnp.int8),
             _shape(one_chip, (k_mounted, n), jnp.int8))


@pytest.mark.parametrize("k,n", [(576, 64), (2304, 64), (4608, 64),
                                 (4608, 512)])
def test_dense_gemm_compiles(one_chip, k, n):
    """The dense layout's K blocks (one 576-row block; three and six
    768-row blocks) at a ResNet-18 stage's M per 8 images."""
    kp = jax.eval_shape(lambda a: dense_layout(a, 0),
                        jax.ShapeDtypeStruct((k, n), jnp.int8)).shape[0]
    _compile(lambda x, w: mounted_gemm(x, w, adc_bits=9, rows=493,
                                       layout="dense", interpret=False),
             _shape(one_chip, (8 * 1024, k), jnp.int8),
             _shape(one_chip, (kp, n), jnp.int8))


# batches off the pooled modes' images-per-step count and past the
# serving bucket ladder (256): padded by whole images
ODD_BATCH_CASES = (
    ("maxpool_2to1_b301", 301 * 4, 512, dict(act="relu", pool="max",
                                             window=2, img_hw=2), True),
    ("avgpool_4x4_b301", 301 * 16, 512, dict(act="relu", pool="avg",
                                             window=4, img_hw=4), True),
    ("maxpool_4to2_b301", 301 * 16, 512, dict(act="relu", pool="max",
                                              window=2, img_hw=4), False),
)


@pytest.mark.parametrize("case", smoke.EPILOGUE_CASES + ODD_BATCH_CASES,
                         ids=lambda c: c[0])
def test_fb_epilogue_compiles(one_chip, case):
    _, m, n, kw, has_res = case
    f32 = jnp.float32
    res = _shape(one_chip, (m, n), f32) if has_res else None
    _compile(lambda y, s, b, r: fb_epilogue(y, s, b, r, interpret=False,
                                            **kw),
             _shape(one_chip, (m, n), jnp.int32),
             _shape(one_chip, (1, 1), f32), _shape(one_chip, (n,), f32), res)


# net -> (graph, batch) of its whole-program compile
WHOLE = {"resnet18": (lambda: "resnet18", 8),
         "vit_tiny": (lambda: vit_tiny_graph(depth=2), 8),
         "deit_ti": (deit_graph, 64),      # the benchmark cell's batch
         # DeiT-shaped and small: 197 tokens, width 192, 3 heads, 2 blocks
         "deit_ti_depth2": (lambda: deit_graph(depth=2), 2),
         # Swin-T at 224x224 with one block in stages 2-4: every kind of
         # stage (shifted and one-window attention, three merges)
         "swin_t_cut": (lambda: swin_graph(depths=(2, 1, 1, 1)), 2)}


@pytest.fixture(scope="module")
def whole_program(one_chip):
    """net -> (model, HLO text of its whole program at its ``WHOLE``
    batch), each compiled once for the file."""
    done = {}

    def get(net):
        if net not in done:
            graph, batch = WHOLE[net]
            model = api.compile(graph(), smoke.CLIP_FREE)
            packed = jax.tree.map(
                lambda a: _shape(one_chip, a.shape, a.dtype), model.packed)
            x = _shape(one_chip, model.program.input_shape(batch),
                       jnp.float32)
            done[net] = model, _compile(
                lambda pk, v: execute_packed(pk, v, interpret=False),
                packed, x)
        return done[net]
    return get


@pytest.mark.parametrize("net", ["resnet18", "vit_tiny", "deit_ti"])
def test_whole_program_compiles(whole_program, net):
    model, text = whole_program(net)
    # one crossbar GEMM and one fused epilogue per static stage at least
    assert text.count("tpu_custom_call") >= 2 * len(model.program.stages())


def test_kernel_names_survive_in_the_compiled_program(whole_program):
    """Trace readers classify kernels by their custom call's instruction
    name, which the kernels' ``pallas_call(name=...)`` sets: one
    ``mounted_gemm.*`` and one ``fb_epilogue.*`` per ResNet-18 stage."""
    model, text = whole_program("resnet18")
    stages = len(model.program.stages())
    assert stages == 21
    for kernel in ("mounted_gemm", "fb_epilogue"):
        calls = re.findall(rf"^\s*(?:ROOT )?%{kernel}(?:\.\d+)? = .*"
                           r"custom-call\(", text, re.M)
        assert len(calls) == stages, kernel


def test_deit_program_lowers_its_new_modes(whole_program):
    """DeiT-Ti at 224x224, batch 64: 74 stages, 24 of them dynamic, one
    ``mounted_gemm`` and one ``fb_epilogue`` custom call each (Mosaic
    kernels); the exact GELU of the 12 MLP stages an XLA ``erf`` in
    their ``epilogue`` phase (Mosaic has no ``erf``), and the ``prenorm``
    and ``embed`` scopes named in the compiled text."""
    model, text = whole_program("deit_ti")
    stages = model.program.stages()
    assert len(stages) == 74
    assert sum(g.kind == "dyn_gemm" for g, _ in stages) == 24
    for kernel in ("mounted_gemm", "fb_epilogue"):
        calls = re.findall(rf"^\s*(?:ROOT )?%{kernel}(?:\.\d+)? = .*"
                           r"custom-call\(", text, re.M)
        assert len(calls) == len(stages), kernel
    erf_stages = [posts[-1].dst for _, posts in stages
                  if any(p.kind == "gelu" and p.approx == "erf"
                         for p in posts)]
    assert len(erf_stages) == 12
    for name in erf_stages:
        assert re.search(rf' erf\(.*op_name="[^"]*/s\d+\.{name}/epilogue/',
                         text), name
    assert "/prenorm/" in text and "/embed/" in text


def test_swin_program_lowers_its_windows(whole_program):
    """Swin-T cut to depths (2, 1, 1, 1), batch 2: 35 stages, 10 of them
    dynamic, one ``mounted_gemm`` and one ``fb_epilogue`` custom call
    each; the window partition, bias and mask, window reverse and merge
    named in their sub-scopes (``window``, ``relbias``, ``unwindow``,
    ``merge``), and the patch norm an XLA tail of the patch stage."""
    model, text = whole_program("swin_t_cut")
    stages = model.program.stages()
    assert len(stages) == 35
    assert sum(g.kind == "dyn_gemm" for g, _ in stages) == 10
    for kernel in ("mounted_gemm", "fb_epilogue"):
        calls = re.findall(rf"^\s*(?:ROOT )?%{kernel}(?:\.\d+)? = .*"
                           r"custom-call\(", text, re.M)
        assert len(calls) == len(stages), kernel
    for sub in ("im2col/window", "epilogue/relbias", "epilogue/unwindow",
                "im2col/merge"):
        assert f"/{sub}/" in text, sub
    assert re.search(r' sqrt\(.*op_name="[^"]*/s00\.patch_norm/epilogue/',
                     text)


# an f32 instruction: name, dtype, dims, layout (minor to major), op_name
_F32 = re.compile(r'\s*(?:ROOT )?%(\S+) = (f32)\[([\d,]*)\]\{([\d,]*)[^}]*\}'
                  r'\S* ([\w-]+)\(.*op_name="([^"]*)"')


def test_swin_scores_tail_runs_keys_major(whole_program):
    """Each windowed Q·Kᵀ stage's XLA tail runs with the keys on a major
    axis (DESIGN.md §9): no f32 level of the ordered row sum over the 49
    keys (24, 12, 6, 3 and 2 wide, 25, 13, 7 and 4 with the carry), in
    any computation of the program, has the key axis on the lanes, as
    ``f32[mounts,49,24]{2,1,0}`` had it, each level padded to 128 lanes;
    and no broadcast of the relative bias or the shift mask to all the
    stage's mounts is left in the entry computation: a table is tiled to
    one image's windows and heads at most."""
    model, text = whole_program("swin_t_cut")
    batch = WHOLE["swin_t_cut"][1]
    mounts = {f"s{si:02d}.{posts[-1].dst}".replace("@", "."):
              batch * (g.in_hw // g.window) ** 2 * g.heads
              for si, (g, posts) in enumerate(model.program.stages())
              if g.kind == "dyn_gemm" and g.dyn == "qk"}
    assert len(mounts) == 5 and all(s.endswith(".probs") for s in mounts)
    levels = {24, 12, 6, 3, 2, 25, 13, 7, 4}
    tails = 0
    for line in text.splitlines():
        m = _F32.match(line)
        if not m or "/epilogue/" not in m.group(6):
            continue
        stage = m.group(6).split("/epilogue/")[0].rsplit("/", 1)[-1]
        if stage not in mounts or "/relbias/" in m.group(6):
            continue
        dims = [int(d) for d in m.group(3).split(",") if d]
        if len(dims) == 3 and 49 in dims and levels & set(dims):
            tails += 1
            minor = dims[int(m.group(4).split(",")[0])]
            assert minor not in levels, line
    assert tails >= 5 * 5          # five levels at least in each stage
    entry = text[text.index("\nENTRY "):]
    for line in entry[:entry.index("\n}\n")].splitlines():
        m = _F32.match(line)
        if not m or m.group(5) != "broadcast":
            continue
        stage = m.group(6).split("/epilogue/")[0].rsplit("/", 1)[-1]
        if stage in mounts:
            dims = [int(d) for d in m.group(3).split(",") if d]
            assert math.prod(dims) <= mounts[stage] * 49 * 49 // batch, line


# an HLO instruction: name, shape, opcode, operands, the rest of the line
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w-]+)\((.*?)\)(.*)")


def _instrs(lines: str) -> dict:
    """name -> (shape, opcode, operand names, op_name) of each line."""
    out = {}
    for m in map(_INSTR.match, lines.splitlines()):
        if m:
            scope = re.search(r'op_name="([^"]*)"', m.group(5))
            out[m.group(1)] = (m.group(2), m.group(3),
                               re.findall(r"%([\w.\-]+)", m.group(4)),
                               scope.group(1) if scope else "")
    return out


def test_swin_probabilities_fold_into_the_pv_quantize(whole_program):
    """XLA folds the softmax's divide by the row sum into the P·V stage's
    quantize, ``e / (sum * scale)``, in the oracle as in the program, so
    the probabilities are never rounded on their own.  The keys-major
    tail moves ``e`` and its row sums back, not their quotient, and
    leaves the divide where the fold reaches it: each windowed P·V
    stage's int8 operand divides the ``.probs`` stage's ``exp`` by the
    product of its row sums (one per mount and query) and the P·V
    stage's per-mount quantize scale."""
    _, text = whole_program("swin_t_cut")
    batch = WHOLE["swin_t_cut"][1]
    entry = text[text.index("\nENTRY "):]
    entry = _instrs(entry[:entry.index("\n}\n")])
    builds = [(name, shape, args) for name, (shape, op, args, _)
              in entry.items() if op == "fusion"
              and re.match(r"s8\[\d+,49,49\]", shape)]
    mounts = sorted(int(re.match(r"s8\[(\d+)", s).group(1)) // batch
                    for _, s, _ in builds)
    assert mounts == [24, 48, 96, 192, 192]
    for name, shape, args in builds:
        m = int(re.match(r"s8\[(\d+)", shape).group(1))
        called = re.search(r"calls=%([\w.\-]+)", text[
            text.index(f"%{name} = "):].split("\n", 1)[0]).group(1)
        body = text[text.index(f"\n%{called} "):]
        body = _instrs(body[:body.index("\n}\n")])

        def source(n):
            """The entry operand a fusion value is read from: through
            broadcasts and bitcasts to the fusion's parameter."""
            while body[n][1] in ("broadcast", "bitcast"):
                n = body[n][2][0]
            assert body[n][1] == "parameter", (called, n)
            k = int(re.search(rf"%{re.escape(n)} = .* parameter\((\d+)\)",
                              text).group(1))
            return entry[args[k]]

        divides = [v for v in body.values() if v[1] == "divide"]
        assert len(divides) == 1, called
        exp, prod = divides[0][2]
        assert body[prod][1] == "multiply", called
        scale, sums = sorted((source(a) for a in body[prod][2]),
                             key=lambda v: v[0].split("]")[0].count(","))
        dividend = source(exp)
        probs = dividend[3].split("/epilogue/")[0]
        dims = re.match(r"f32\[([\d,]+)\]", dividend[0]).group(1)
        assert probs.endswith("_attn.probs") and math.prod(
            int(d) for d in dims.split(",")) == m * 49 * 49, dividend
        assert sums[3].startswith(probs + "/epilogue/"), sums
        assert sums[0].startswith(f"f32[{m},49]"), sums
        ctx = probs.removesuffix(".probs").rsplit(".", 1)[-1] + ".ctx/quantize/"
        assert ctx in scale[3] and scale[0].startswith(f"f32[{m}]"), scale


def test_deit_linear_stages_run_edge_blocks_unpadded(whole_program):
    """DeiT's M = 394 token rows and N = 576, 768 and 192 divide no
    block: both kernels take a linear stage's operands as they are (edge
    blocks), so no pad or slice sits in either kernel's wrapper there,
    for the weights neither.  What is left in a wrapper is attention's
    P·V lane pad (N = 64, under one 128-lane tile), one per block."""
    model, text = whole_program("deit_ti_depth2")
    assert len(model.program.stages()) == 14
    ops = re.findall(r' = (\S+) (pad|slice)\(.*op_name="[^"]*/'
                     r'(s\d+\.[\w.]+)/(\w*\(?)jit\((mounted_gemm|fb_epilogue)'
                     r'\)\)?/', text)
    assert sorted((stage, vmap + kernel, op) for _, op, stage, vmap, kernel
                  in ops) == [("s03.b0_attn.ctx", "vmap(mounted_gemm", "pad"),
                              ("s09.b1_attn.ctx", "vmap(mounted_gemm", "pad")]
    assert all(shape.startswith("s8[") and ",128]" in shape
               for shape, *_ in ops)


def _relayouts(text: str, dtype: str, tokens: int) -> list[tuple]:
    """The ``reshape``s and ``copy``s of ``dtype`` in the entry
    computation whose result or operand has an axis that is a multiple
    of ``tokens`` (a sequence's T, or B*T rows) -> [(op, shape,
    elements, op_name)]."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    inst = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                      r"([\w-]+)\(%?([\w.-]*)")
    dims, found = {}, []
    for line in entry.splitlines()[1:]:
        m = inst.match(line)
        if not m:
            continue
        name, dt, shape, op, arg = m.groups()
        dims[name] = [int(d) for d in shape.split(",") if d]
        if dt != dtype or op not in ("reshape", "copy"):
            continue
        if any(d % tokens == 0 for d in dims[name] + dims.get(arg, [])):
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append((op, tuple(dims[name]), math.prod(dims[name]),
                          scope.group(1) if scope else ""))
    return found


def test_deit_sequence_buffers_stay_token_rows(whole_program):
    """DeiT's sequence stage buffers stay (B*T, N) rows from stage to
    stage (T = 197, off the 8-row tiling, so a (B*T, N) <-> (B, T, N)
    reshape is a copy of the whole buffer): no f32 relayout on the
    token axis sits in a static sequence stage's ``epilogue/reshape``
    scope.  Kᵀ is moved in f32 before its quantize, so no int8 Kᵀ
    (B*H, hd, T) is relaid, nor an int8 context in heads order
    (B, H, T, hd); and the f32 token-axis relayouts left, those the
    pre-norms, the head splits and the softmax sums ask XLA for, are at
    most 38 instructions writing 7.350 MB at batch 2 (PERF.md §5)."""
    model, text = whole_program("deit_ti_depth2")
    static_seq = {f"s{si:02d}.{(posts[-1].dst if posts else g.dst)}"
                  .replace("@", ".")
                  for si, (g, posts) in enumerate(model.program.stages())
                  if g.seq and g.kind != "dyn_gemm"}
    assert len(static_seq) == 8
    ops = _relayouts(text, "f32", 197)
    assert not [o for o in ops for stage in static_seq
                if f"/{stage}/epilogue/reshape" in o[3]]
    assert not [o for o in _relayouts(text, "s8", 197)
                if o[1] in ((6, 64, 197), (2, 3, 197, 64))]
    assert len(ops) <= 38
    assert sum(4 * n for _, _, n, _ in ops) <= 7.351e6
