"""Persist compiled models: ``CompiledModel.save`` / ``api.load``.

Format (single ``.npz`` file, version 4):

* ``__meta__`` — a JSON document holding the graph (name, input spec,
  ``LayerSpec`` list), the ``HurryConfig``, the batch-bucket ladder,
  and the compiled ``CrossbarProgram`` *minus its array plans*: net
  name, derived ``CrossbarConfig``, the full ``ProgramOp`` list (with
  ``MountRound`` weight slices and FB placements), buffer names, and
  the input spec.
* ``p0 .. pN`` — the parameter arrays, ordered by the ``params`` index
  in the meta document (``[layer, key]`` pairs).
* ``w0/wa0/wb0 .. `` — the **packed weight planes** (since version 2):
  per GEMM stage the int8 mount-plane matrix (pre-quantized, im2col
  layout, K in the mount layout of ``kernels.crossbar_gemm``), the f32
  weight ``amax``, and the f32 bias, in ``program.stages()`` order.  A loaded model serves from
  these directly — ``api.load(...).run(...)`` never quantizes a weight
  (the analogue of shipping a programmed chip, not a netlist).
* ``wg{i}/wh{i}`` — (version 3) the fused layer-norm FB's gamma/beta
  for stages listed in the meta's ``ln_stages``.

Version 3 extends version 2 for graphs containing **dynamic-operand
stages** (attention, DESIGN.md §9): sequence fields ride on the graph /
program meta (``in_seq``, per-op ``dyn``/``heads``/``post_scale``/
``w_key`` fields), dynamic stages persist as 0-sized placeholder planes
(their operands mount per batch at run time), and layer-norm FB
parameters ride next to the planes so the packed executor never
touches the float param pytree.

Array plans are compile-time placement artifacts the executor never
reads, so a loaded model serves without them (``plans=()``);
``CompiledModel.simulate()`` re-derives placement from the graph.
Everything the jitted executor consumes — ops, tile shapes, mount
rounds, quantization config, packed planes — round-trips exactly, so a
loaded model's ``run`` is bit-identical to the in-memory one and a
serving process never invokes the compiler or the packer.

Version 4 changes only the planes' K layout: each mount's
``tile_rows`` rows are followed by zero rows up to a multiple of 128
(``mount_layout``), where versions 2-3 padded K once, at its end, to
whole mounts.  It also stores ``block_m``/``block_n`` as ``None`` when
the kernels pick their own tiles.

Version-1 files (pre-packing) still load: the packed planes are
re-derived once from the saved params at load time (repack fallback).
Version-2/3 files load without requantizing: their planes are re-laid
into the mount layout (exact — both paddings are zero rows), and the
old 512x512 block-size defaults they stored become ``None``.  Version-2
files have no sequence fields and no ln stages.
"""

from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np

from repro.core.workload import LayerSpec
from repro.kernels.crossbar_gemm import mount_layout
from repro.program.compile import CrossbarProgram, MountRound, ProgramOp
from repro.program.pack import (PackedProgram, PackedStage, pack_program)
from repro.program.serve import BUCKETS

from .config import HurryConfig
from .graph import NetworkGraph

FORMAT = "repro.api/compiled-model"
VERSION = 4
_LOADABLE = (1, 2, 3, 4)
_OLD_BLOCK_DEFAULT = 512      # versions <= 3 stored it for "no override"


def _program_meta(program: CrossbarProgram) -> dict:
    ops = []
    for op in program.ops:
        d = dataclasses.asdict(op)
        d["mount_rounds"] = [dataclasses.asdict(r)
                             for r in op.mount_rounds]
        ops.append(d)
    return {"net": program.net, "cfg": dataclasses.asdict(program.cfg),
            "ops": ops, "input": program.input, "output": program.output,
            "logits": program.logits, "in_hw": program.in_hw,
            "in_ch": program.in_ch, "in_features": program.in_features,
            "in_seq": program.in_seq}


def _program_from_meta(meta: dict) -> CrossbarProgram:
    from repro.core.crossbar import CrossbarConfig
    ops = []
    for d in meta["ops"]:
        d = dict(d)
        d["mount_rounds"] = tuple(MountRound(**r)
                                  for r in d["mount_rounds"])
        ops.append(ProgramOp(**d))
    return CrossbarProgram(
        net=meta["net"], cfg=CrossbarConfig(**meta["cfg"]),
        ops=tuple(ops), plans=(), input=meta["input"],
        output=meta["output"], logits=meta["logits"],
        in_hw=meta["in_hw"], in_ch=meta["in_ch"],
        in_features=meta["in_features"], in_seq=meta.get("in_seq", 0))


def _relayout(w8: jnp.ndarray, op: ProgramOp) -> jnp.ndarray:
    """A version 2/3 plane (K zero-padded at its end to whole
    ``tile_rows`` mounts) in the mount layout: cut to the real K, then
    lay out.  Exact, as the old padding was zero rows."""
    if w8.size == 0:                      # dynamic-stage placeholder
        return w8
    k = max(r.k1 for r in op.mount_rounds)
    return mount_layout(w8[:k], op.tile_rows, 0)


def save_model(model, path: str) -> str:
    """Write ``model`` (a ``CompiledModel``) to ``path``; returns path."""
    g = model.graph
    index = []
    arrays = {}
    for layer in sorted(model.params):
        for key in sorted(model.params[layer]):
            arrays[f"p{len(index)}"] = np.asarray(model.params[layer][key])
            index.append([layer, key])
    packed = model._packed()
    ln_stages = []
    for i, st in enumerate(packed.stages):
        arrays[f"w{i}"] = np.asarray(st.w8)
        arrays[f"wa{i}"] = np.asarray(st.w_amax)
        arrays[f"wb{i}"] = np.asarray(st.bias)
        if st.ln_g is not None:
            ln_stages.append(i)
            arrays[f"wg{i}"] = np.asarray(st.ln_g)
            arrays[f"wh{i}"] = np.asarray(st.ln_b)
    meta = {
        "format": FORMAT, "version": VERSION,
        "graph": {"name": g.name, "in_hw": g.in_hw, "in_ch": g.in_ch,
                  "in_features": g.in_features, "in_seq": g.in_seq,
                  "layers": [dataclasses.asdict(l) for l in g.layers]},
        "config": dataclasses.asdict(model.config),
        "program": _program_meta(model.program),
        "params": index,
        "packed_stages": len(packed.stages),
        "ln_stages": ln_stages,
        "buckets": list(model.buckets),
    }
    with open(path, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    return path


def load_model(path: str):
    """Load a ``CompiledModel`` saved by ``save_model`` — no compile step,
    and (version >= 2) no weight quantization: the packed planes are read
    back verbatim."""
    from .model import CompiledModel
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"][()]))
        if meta.get("format") != FORMAT:
            raise ValueError(f"{path}: not a {FORMAT} file")
        version = meta.get("version")
        if version not in _LOADABLE:
            raise ValueError(f"{path}: format version {version} not in "
                             f"supported {_LOADABLE}")
        params: dict = {}
        for i, (layer, key) in enumerate(meta["params"]):
            params.setdefault(layer, {})[key] = jnp.asarray(z[f"p{i}"])
        ln = set(meta.get("ln_stages", ()))
        stages = tuple(
            PackedStage(w8=jnp.asarray(z[f"w{i}"]),
                        w_amax=jnp.asarray(z[f"wa{i}"]),
                        bias=jnp.asarray(z[f"wb{i}"]),
                        ln_g=jnp.asarray(z[f"wg{i}"]) if i in ln else None,
                        ln_b=jnp.asarray(z[f"wh{i}"]) if i in ln else None)
            for i in range(meta.get("packed_stages", 0)))
    program = _program_from_meta(meta["program"])
    config = dict(meta["config"])
    if version < 4:
        for key in ("block_m", "block_n"):
            if config.get(key) == _OLD_BLOCK_DEFAULT:
                config[key] = None
    if version == 1:   # pre-packing save: re-derive planes once, now
        packed = pack_program(program, params)
    else:
        gemms = [gemm for gemm, _ in program.stages()]
        if len(stages) != len(gemms):
            raise ValueError(f"{path}: corrupt file — {len(stages)} packed "
                             f"weight planes for {len(gemms)} GEMM stages")
        if version < 4:
            stages = tuple(dataclasses.replace(st, w8=_relayout(st.w8, op))
                           for st, op in zip(stages, gemms))
        packed = PackedProgram(stages=stages, program=program)
    gm = meta["graph"]
    graph = NetworkGraph(
        name=gm["name"], in_hw=gm["in_hw"], in_ch=gm["in_ch"],
        in_features=gm["in_features"], in_seq=gm.get("in_seq", 0),
        layers=tuple(LayerSpec(**d) for d in gm["layers"]))
    return CompiledModel(graph=graph, config=HurryConfig(**config),
                         program=program, params=params, packed=packed,
                         buckets=tuple(meta.get("buckets", BUCKETS)))
