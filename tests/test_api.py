"""`repro.api` front-door surface: builder IR, unified config, sessions.

Covers ISSUE 3's acceptance criteria: a user-defined ``NetworkBuilder``
graph (never touching ``core/workload.py``) compiles, runs bit-exactly
against the functional crossbar forward under a clip-free config, and
round-trips through ``save``/``load`` bit-exactly (both sides jitted,
DESIGN.md §5); the paper CNNs compile by their zoo name; warmup shapes
derive from the compiled program's input spec; malformed graphs fail at
build time with the offending layer's name; and the layers below
``repro.api`` never import it.
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import GRAPHS, HurryConfig, NetworkBuilder, NetworkGraph
from repro.core.crossbar import CrossbarConfig, make_crossbar_matmul
from repro.core.simulator import ChipConfig, simulate_hurry
from repro.core.workload import LayerSpec, layer_groups
from repro.program import compile_network

CLIP_FREE = HurryConfig(array_rows=511)      # DESIGN.md §4 predicate holds


def _custom_graph() -> NetworkGraph:
    """A branching custom net — not one of the three paper CNNs."""
    nb = NetworkBuilder("custom8", input_hw=8, input_ch=4)
    nb.conv(16, name="c1")
    r1 = nb.relu(name="r1")
    proj = nb.conv(24, k=1, padding=0, name="proj", input_from=r1)
    nb.conv(24, name="c2", input_from=r1)
    nb.residual(proj, name="res")
    nb.relu(name="r2")
    nb.maxpool(name="p1")
    nb.fc(10, name="fc")
    nb.softmax(name="sm")
    return nb.build()


def _model_and_input(batch=2, seed=0):
    graph = _custom_graph()
    model = api.compile(graph, CLIP_FREE, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(seed), graph.input_shape(batch))
    return graph, model, x


# ---------------------------------------------------------------------------
# builder IR: shape inference + build-time validation
# ---------------------------------------------------------------------------

def test_builder_infers_shapes_and_wiring():
    graph = _custom_graph()
    by_name = {l.name: l for l in graph.layers}
    assert by_name["c1"].out_hw == 8 and by_name["c1"].in_ch == 4
    assert by_name["proj"].input_from == "r1"
    assert by_name["res"].residual_from == "proj"
    assert by_name["p1"].out_hw == 4
    assert by_name["fc"].features_in == 4 * 4 * 24
    assert graph.input_shape(3) == (3, 8, 8, 4)


def test_builder_rejects_headless_group():
    nb = NetworkBuilder("bad", input_hw=8, input_ch=3)
    with pytest.raises(ValueError, match="'relu0'.*precedes any GEMM"):
        nb.relu(name="relu0")


def test_layer_groups_rejects_headless_group():
    layers = [LayerSpec("lonely_relu", "relu", out_ch=3, out_hw=8),
              LayerSpec("c", "conv", in_ch=3, out_ch=8, ksize=3, stride=1,
                        padding=1, in_hw=8, out_hw=8)]
    with pytest.raises(ValueError, match="'lonely_relu'.*precedes any GEMM"):
        list(layer_groups(layers))


def test_builder_rejects_bad_residual_and_wiring():
    nb = NetworkBuilder("bad", input_hw=8, input_ch=3)
    nb.conv(8, name="c1")
    nb.relu(name="r1")
    with pytest.raises(ValueError, match="nope"):
        nb.residual("nope", name="res")
    nb.conv(16, name="c2")         # 8x8x16: shape mismatch vs r1 (8x8x8)
    with pytest.raises(ValueError, match="shape"):
        nb.residual("r1", name="res")
    with pytest.raises(ValueError, match="duplicate"):
        nb.conv(8, name="c1")
    with pytest.raises(ValueError, match="window == stride"):
        nb.maxpool(k=3, stride=2, name="p")


def test_builder_rejects_non_canonical_chain_at_build():
    nb = NetworkBuilder("bad", input_hw=8, input_ch=3)
    nb.conv(8, name="c1")
    nb.maxpool(name="p1")
    nb.relu(name="r_late")         # relu after pool: out of FB chain order
    with pytest.raises(ValueError, match="r_late.*canonical"):
        nb.build()


# ---------------------------------------------------------------------------
# unified HurryConfig: one derivation point
# ---------------------------------------------------------------------------

def test_hurry_config_derivations_agree():
    hc = HurryConfig(array_rows=511, adc_bits=9, sim_batch=4)
    chip, cfg = hc.chip(), hc.crossbar()
    assert isinstance(chip, ChipConfig) and chip.array_rows == 511
    assert chip.batch == 4
    assert isinstance(cfg, CrossbarConfig) and cfg.rows == 511
    assert cfg.clip_free and hc.clip_free
    base = hc.baseline()
    assert base.array_rows == 511 and base.cell_bits == 2   # baseline MLC
    # lifting a bare ChipConfig goes through the same single point
    assert HurryConfig.from_chip(chip).crossbar() == cfg


def test_compile_and_serve_consume_hurry_config():
    program = compile_network(GRAPHS["alexnet"](), config=CLIP_FREE)
    assert program.cfg == CLIP_FREE.crossbar()
    model = api.compile("alexnet", CLIP_FREE)
    assert model.program.cfg == CLIP_FREE.crossbar()


def test_simulator_and_baselines_consume_hurry_config():
    layers = list(GRAPHS["alexnet"]().layers)
    via_api = simulate_hurry(layers, chip=HurryConfig())
    via_chip = simulate_hurry(layers, chip=ChipConfig())
    assert via_api.throughput_cycles == via_chip.throughput_cycles
    assert via_api.energy_pj == via_chip.energy_pj


# ---------------------------------------------------------------------------
# acceptance: custom net bit-exact, save/load roundtrip, zoo by name
# ---------------------------------------------------------------------------

def test_custom_net_bit_exact_vs_functional_forward():
    """Builder-defined net: compiled program == functional crossbar
    forward, bitwise, under a clip-free config (both sides jitted)."""
    graph, model, x = _model_and_input()
    logits = model.run(x, logits=True)
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(CLIP_FREE.crossbar()), logits=True))
    ref = fwd(model.params, x)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(model.run(x)),
        np.asarray(jax.nn.softmax(ref, axis=-1)), atol=1e-7)


def test_save_load_roundtrip_bit_exact(tmp_path):
    """api.load(save(model)).run == model.run, bitwise — serving skips
    compilation entirely."""
    _, model, x = _model_and_input()
    y_mem = model.run(x, logits=True)
    path = model.save(str(tmp_path / "custom8.npz"))
    loaded = api.load(path)
    # static program + config + graph round-trip exactly (plans are
    # compile-time placement artifacts the executor never reads)
    assert loaded.config == model.config
    assert loaded.program.ops == model.program.ops
    assert loaded.program.cfg == model.program.cfg
    assert loaded.graph.layers == model.graph.layers
    y_loaded = loaded.run(x, logits=True)
    np.testing.assert_array_equal(np.asarray(y_mem), np.asarray(y_loaded))
    np.testing.assert_array_equal(np.asarray(model.run(x)),
                                  np.asarray(loaded.run(x)))


def test_paper_cnn_through_api_by_name():
    model = api.compile("alexnet", CLIP_FREE)
    assert model.graph.name == "alexnet"
    assert model.program.input_shape(2) == (2, 32, 32, 3)
    assert {l.kind for l in model.graph.layers} == \
        {"conv", "relu", "maxpool", "fc", "softmax"}


def test_graph_init_params_shapes_are_graph_derived():
    graph = GRAPHS["alexnet"]()
    params = graph.init_params(jax.random.PRNGKey(0))
    assert params["conv1"]["w"].shape == (3, 3, 3, 64)
    assert params["fc6"]["w"].shape == (256 * 4 * 4, 1024)
    # one entry per GEMM layer, shaped by its spec
    gemms = [l for l in graph.layers if l.kind in ("conv", "fc")]
    assert sorted(params) == sorted(l.name for l in gemms)
    for l in gemms:
        w, b = params[l.name]["w"], params[l.name]["b"]
        if l.kind == "conv":
            assert w.shape == (l.ksize, l.ksize, l.in_ch, l.out_ch)
        else:
            assert w.shape == (l.features_in, l.features_out)
        assert b.shape == (w.shape[-1],)


# ---------------------------------------------------------------------------
# serving warmup derives its shape from the program input spec
# ---------------------------------------------------------------------------

def test_warmup_shape_derived_from_program():
    graph, model, _ = _model_and_input()
    assert model.program.input_shape(5) == (5, 8, 8, 4)
    model.warmup(2)                # non-CIFAR shape: used to hardcode 32x32x3
    assert (False, 2) in model._called
    y = model.run(jnp.zeros(graph.input_shape(2), jnp.float32))
    assert y.shape == (2, 10)


def test_model_simulate_matches_direct_simulator():
    _, model, _ = _model_and_input()
    rep = model.simulate()
    direct = simulate_hurry(list(model.graph.layers),
                            chip=model.config.chip())
    assert rep.throughput_cycles == direct.throughput_cycles
    assert rep.energy_pj == direct.energy_pj
    assert model.simulate("isaac-128").throughput_cycles > 0
    with pytest.raises(ValueError, match="unknown arch"):
        model.simulate("tpu")
    with pytest.raises(ValueError, match="unknown arch"):
        model.simulate("isaac-64")


def test_summary_mentions_net_and_clip_free():
    _, model, _ = _model_and_input()
    s = model.summary()
    assert "custom8" in s and "clip-free" in s and "gemm" in s


# ---------------------------------------------------------------------------
# batch-shape bucketing: odd traffic shares executables, slice-exact
# ---------------------------------------------------------------------------

def test_odd_batch_sizes_share_one_executable():
    """b=5 and b=7 both pad to bucket 8: ONE trace serves both, and the
    padded run is bit-exact vs an unbucketed model (edge replication
    preserves every per-tensor quantization max)."""
    import repro.api.model as apimodel
    graph, model, _ = _model_and_input()
    traces = []
    orig = apimodel.execute_packed

    def spy(pk, v, **kw):
        traces.append(v.shape[0])
        return orig(pk, v, **kw)

    apimodel.execute_packed = spy
    try:
        x5 = jax.random.normal(jax.random.PRNGKey(5), graph.input_shape(5))
        x7 = jax.random.normal(jax.random.PRNGKey(7), graph.input_shape(7))
        y5, y7 = model.run(x5), model.run(x7)
    finally:
        apimodel.execute_packed = orig
    assert traces == [8]            # one bucket-8 executable, no retrace
    assert y5.shape == (5, 10) and y7.shape == (7, 10)
    exact = api.compile(graph, CLIP_FREE, seed=1, buckets=())
    np.testing.assert_array_equal(np.asarray(y5), np.asarray(exact.run(x5)))
    np.testing.assert_array_equal(np.asarray(y7), np.asarray(exact.run(x7)))


def test_buckets_roundtrip_and_packed_by_default(tmp_path):
    graph, model, x = _model_and_input()
    assert model.packed is not None          # api.compile packs
    assert model.buckets[:4] == (1, 2, 4, 8)
    path = model.save(str(tmp_path / "m.npz"))
    loaded = api.load(path)
    assert loaded.buckets == model.buckets
    assert loaded.packed is not None and len(loaded.packed.stages) == \
        len(model.packed.stages)


# ---------------------------------------------------------------------------
# tracing: run's host spans
# ---------------------------------------------------------------------------

def _spans(trace_dir):
    """The ``repro.`` host spans of the trace under ``trace_dir``:
    (name, start_ns, end_ns, args)."""
    import glob
    import os

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in data.planes for line in plane.lines
                   for e in line.events if e.name.startswith("repro.")),
                  key=lambda s: (s[1], -s[2]))     # parents first


def test_run_writes_spans_under_a_trace(tmp_path):
    """Under a profiler trace, a padded request writes ``repro.run`` and
    its ``pad``, ``call`` and ``slice`` children, which share the
    request's id; the first call of a bucket says ``new=1``."""
    graph, model, _ = _model_and_input()
    jax.block_until_ready(model.run(jnp.zeros(graph.input_shape(5))))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(model.run(jnp.zeros(graph.input_shape(7))))
        jax.block_until_ready(model.run(jnp.zeros(graph.input_shape(3))))
    spans = _spans(str(tmp_path))
    names = [s[0] for s in spans]
    assert names == ["repro.run", "repro.run.pad", "repro.run.call",
                     "repro.run.slice"] * 2
    for req, (parent, pad, call, slc) in zip(
            (2, 3), (spans[:4], spans[4:])):
        assert all(s[3]["request"] == req for s in (parent, pad, call, slc))
        assert parent[1] <= pad[1] <= pad[2] <= call[1] <= call[2] \
            <= slc[1] <= slc[2] <= parent[2]
    assert [(s[3]["batch"], s[3]["bucket"]) for s in spans[::4]] == [
        (7, 8), (3, 4)]
    assert [(s[3]["bucket"], s[3]["new"]) for s in spans[2::4]] == [
        (8, 0), (4, 1)]


def test_compiled_text_is_the_scoped_program():
    graph, model, x = _model_and_input()
    text = model.compiled_text(x)
    assert text.startswith("HloModule")
    assert "/s00." in text and "/quantize/" in text


# ---------------------------------------------------------------------------
# layering: core -> kernels -> program never import the layers above
# ---------------------------------------------------------------------------

def test_lower_layers_import_no_upper_layer():
    """No module of ``repro.core``, ``repro.kernels`` or ``repro.program``
    imports ``repro.api`` or ``repro.models``, at top level or lazily."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    upper = ("repro.api", "repro.models")
    found = []
    for pkg in ("core", "kernels", "program"):
        for path in sorted((src / pkg).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    base = node.module or ""
                    if node.level:          # relative: resolve to repro.*
                        parts = ["repro", *path.relative_to(src).parts[:-1]]
                        parts = parts[:len(parts) - node.level + 1]
                        base = ".".join(parts + ([base] if base else []))
                    names = [base] + [f"{base}.{a.name}" for a in node.names]
                else:
                    continue
                if any(n == u or n.startswith(u + ".")
                       for n in names for u in upper):
                    found.append(f"{path.relative_to(src)}:{node.lineno}")
    assert not found, found

