"""Persist compiled models: ``CompiledModel.save`` / ``api.load``.

Format (single ``.npz`` file, version 7):

* ``__meta__`` — a JSON document holding the graph (name, input spec,
  ``LayerSpec`` list), the ``HurryConfig``, the batch-bucket ladder,
  and the compiled ``CrossbarProgram`` *minus its array plans*: net
  name, derived ``CrossbarConfig``, the full ``ProgramOp`` list (with
  ``MountRound`` weight slices and FB placements), buffer names, and
  the input spec.
* ``p0 .. pN`` — the parameter arrays, ordered by the ``params`` index
  in the meta document (``[layer, key]`` pairs).
* ``w0/wa0/wb0 .. `` — the **packed weight planes** (since version 2):
  per GEMM stage the int8 plane matrix (pre-quantized, in the stage's
  im2col order and K layout, see version 5 below), the f32
  weight ``amax``, and the f32 bias, in ``program.stages()`` order.  A loaded model serves from
  these directly — ``api.load(...).run(...)`` never quantizes a weight
  (the analogue of shipping a programmed chip, not a netlist).
* ``wg{i}/wh{i}`` — (version 3) the fused layer-norm FB's gamma/beta
  for stages listed in the meta's ``ln_stages``.
* ``wpg{i}/wpb{i}`` and ``wec{i}/wep{i}`` — (version 6) a stage's
  pre-norm gamma/beta (meta ``pre_stages``) and its ``embed`` FB's
  class token and position table (meta ``embed_stages``).
* ``wrb{i}`` — (version 7) a windowed Q·Kᵀ stage's relative position
  bias, gathered to ``(heads, M², M²)`` (meta ``bias_stages``).

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

Version 5 stores each stage's planes in the layout
``program.pack.stage_layout`` gives it, listed per stage in the meta's
``layouts``: a clip-free weight-mounted stage is **dense** — a conv's K
in ``(i, j, c)`` order, K zero-padded only at its end to whole kernel
blocks (``dense_layout``); every other stage keeps version 4's
``(c, i, j)`` order and mount layout.

Version 6 adds what pre-norm transformers need (DeiT): the optional
per-stage arrays above, and the new fields of the graph's layers
(``prenorm``, ``eps``, ``approx``, ``mode``) and of the program's ops
(``prenorm``, ``eps``, ``approx``, ``select``).  Files of versions 2-5
have none of them and load with their defaults, which are the meaning
those files had (no pre-norm, LN epsilon 1e-5, tanh GELU, mean pool).

Version 7 adds what windowed, hierarchical transformers need (Swin):
the optional per-stage bias arrays above (the ``(2M-1)² x heads``
tables themselves ride with the parameters), the new fields of the
graph's layers (``window``, ``shift``, ``merge``, ``bias``) and of the
program's ops (``merge``, ``shift``; a windowed dynamic stage's
``window`` and ``in_hw``, a bias-free stage's empty ``b_key``), and a
merge stage's bias-free planes.  Files of versions 2-6 have none of
them and load with their defaults (global attention, no merge, a
bias on every GEMM).  Version 7 also reads a mean pool as a ``select``
of the head after it, where versions 2-6 stored a ``seqpool`` op on
the stage before: such an op becomes that select at load.

Version-1 files (pre-packing) still load: the packed planes are
re-derived once from the saved params at load time (repack fallback).
Version-2/3/4 files load without requantizing: each plane is cut back
to its real K rows (versions 2-3 padded K at its end, version 4 each
mount) and laid out as version 5 lays that stage out — for a dense conv
the rows are permuted from ``(c, i, j)`` to ``(i, j, c)`` — exact, as
every padding is zero rows.  The old 512x512 block-size defaults that
versions 2-3 stored become ``None``.  Version-2 files have no sequence
fields and no ln stages.
"""

from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np

from repro.core.workload import LayerSpec
from repro.kernels.crossbar_gemm import dense_layout, mount_layout, mount_rows
from repro.program.compile import CrossbarProgram, MountRound, ProgramOp
from repro.program.pack import (PackedProgram, PackedStage, pack_program,
                                stage_layout)

from .config import HurryConfig
from .graph import NetworkGraph

FORMAT = "repro.api/compiled-model"
VERSION = 7
_LOADABLE = (1, 2, 3, 4, 5, 6, 7)
# meta key listing the stages that hold them -> (PackedStage field,
# array prefix) of each optional per-stage array
_OPTIONAL = {"ln_stages": (("ln_g", "wg"), ("ln_b", "wh")),
             "pre_stages": (("pre_g", "wpg"), ("pre_b", "wpb")),
             "embed_stages": (("emb_cls", "wec"), ("emb_pos", "wep")),
             "bias_stages": (("rel_bias", "wrb"),)}
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


def _mean_pools_as_selects(ops: list[ProgramOp]) -> tuple[ProgramOp, ...]:
    """Versions 2-6 kept a mean pool as a ``seqpool`` post-op of the stage
    before its head; version 7 has the head read the pooled buffer with
    ``select="mean"``, as a class-token pool is read.  Same values."""
    pools = {op.dst: op.src for op in ops if op.kind == "seqpool"}
    return tuple(dataclasses.replace(op, src=pools[op.src], select="mean")
                 if op.src in pools else op
                 for op in ops if op.kind != "seqpool")


def _program_from_meta(meta: dict, version: int) -> CrossbarProgram:
    from repro.core.crossbar import CrossbarConfig
    ops = []
    for d in meta["ops"]:
        d = dict(d)
        d["mount_rounds"] = tuple(MountRound(**r)
                                  for r in d["mount_rounds"])
        ops.append(ProgramOp(**d))
    if version < 7:
        ops = _mean_pools_as_selects(ops)
    return CrossbarProgram(
        net=meta["net"], cfg=CrossbarConfig(**meta["cfg"]),
        ops=tuple(ops), plans=(), input=meta["input"],
        output=meta["output"], logits=meta["logits"],
        in_hw=meta["in_hw"], in_ch=meta["in_ch"],
        in_features=meta["in_features"], in_seq=meta.get("in_seq", 0))


def _relayout(w8: jnp.ndarray, op: ProgramOp, cfg,
              version: int) -> jnp.ndarray:
    """A version 2-4 plane in the layout version 5 gives its stage: cut
    to the real K rows (versions 2-3 padded K at its end to whole
    mounts, version 4 each mount to 128-row tiles), then laid out dense
    (a conv's rows permuted from ``(c, i, j)`` to ``(i, j, c)``) or
    mounted.  Exact: every padding is zero rows."""
    if w8.size == 0:                      # dynamic-stage placeholder
        return w8
    k, rows = max(r.k1 for r in op.mount_rounds), op.tile_rows
    if version == 4 and k > rows:
        n = -(-k // rows)
        w8 = w8.reshape(n, mount_rows(rows), -1)[:, :rows]
        w8 = w8.reshape(n * rows, -1)
    w8 = w8[:k]
    if stage_layout(op, cfg) == "mounted":
        return mount_layout(w8, rows, 0)
    if op.is_conv:                        # (c, i, j) -> (i, j, c)
        kk = op.ksize * op.ksize
        w8 = w8.reshape(k // kk, kk, -1).transpose(1, 0, 2).reshape(k, -1)
    return dense_layout(w8, 0)


def save_model(model, path: str) -> str:
    """Write ``model`` (a ``CompiledModel``) to ``path``; returns path."""
    g = model.graph
    index = []
    arrays = {}
    for layer in sorted(model.params):
        for key in sorted(model.params[layer]):
            arrays[f"p{len(index)}"] = np.asarray(model.params[layer][key])
            index.append([layer, key])
    packed = model.packed
    optional = {key: [] for key in _OPTIONAL}
    for i, st in enumerate(packed.stages):
        arrays[f"w{i}"] = np.asarray(st.w8)
        arrays[f"wa{i}"] = np.asarray(st.w_amax)
        arrays[f"wb{i}"] = np.asarray(st.bias)
        for key, fields in _OPTIONAL.items():
            if getattr(st, fields[0][0]) is not None:
                optional[key].append(i)
                for field, prefix in fields:
                    arrays[f"{prefix}{i}"] = np.asarray(getattr(st, field))
    meta = {
        "format": FORMAT, "version": VERSION,
        "graph": {"name": g.name, "in_hw": g.in_hw, "in_ch": g.in_ch,
                  "in_features": g.in_features, "in_seq": g.in_seq,
                  "layers": [dataclasses.asdict(l) for l in g.layers]},
        "config": dataclasses.asdict(model.config),
        "program": _program_meta(model.program),
        "params": index,
        "packed_stages": len(packed.stages),
        **optional,
        "layouts": list(packed.layouts()),
        "buckets": list(model.buckets),
    }
    with open(path, "wb") as f:
        np.savez(f, __meta__=np.asarray(json.dumps(meta)), **arrays)
    return path


def load_model(path: str):
    """Load a ``CompiledModel`` saved by ``save_model`` — no compile step,
    and (version >= 2) no weight quantization: the packed planes are read
    back verbatim."""
    from .model import BUCKETS, CompiledModel
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
        def extras(i):
            return {field: jnp.asarray(z[f"{prefix}{i}"])
                    for key, fields in _OPTIONAL.items()
                    if i in meta.get(key, ())
                    for field, prefix in fields}
        stages = tuple(
            PackedStage(w8=jnp.asarray(z[f"w{i}"]),
                        w_amax=jnp.asarray(z[f"wa{i}"]),
                        bias=jnp.asarray(z[f"wb{i}"]), **extras(i))
            for i in range(meta.get("packed_stages", 0)))
    program = _program_from_meta(meta["program"], version)
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
        if version < 5:
            stages = tuple(
                dataclasses.replace(st, w8=_relayout(st.w8, op, program.cfg,
                                                     version))
                for st, op in zip(stages, gemms))
        packed = PackedProgram(stages=stages, program=program)
        if version >= 5 and list(packed.layouts()) != meta["layouts"]:
            raise ValueError(f"{path}: corrupt file — stage layouts "
                             f"{meta['layouts']} where this program lays "
                             f"out {list(packed.layouts())}")
    gm = meta["graph"]
    graph = NetworkGraph(
        name=gm["name"], in_hw=gm["in_hw"], in_ch=gm["in_ch"],
        in_features=gm["in_features"], in_seq=gm.get("in_seq", 0),
        layers=tuple(LayerSpec(**d) for d in gm["layers"]))
    return CompiledModel(graph=graph, config=HurryConfig(**config),
                         program=program, params=params, packed=packed,
                         buckets=tuple(meta.get("buckets", BUCKETS)))
