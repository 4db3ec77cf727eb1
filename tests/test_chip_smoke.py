"""``chip_smoke.py``'s control flow, run small on the CPU in interpret mode.

The script proves the main path on a TPU; these tests call its phase
functions at a size the CPU can interpret (``vit_tiny`` at depth 1,
batches of 1 and 2), so a later change cannot break the script without
a failing test.  Its platform check stays in ``main``: off a TPU it must
refuse to run and print no result.
"""

import importlib.util
import pathlib

import jax
import numpy as np
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def _passed(checks):
    return {name: ok for name, ok in checks}


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == ""                      # no result line, no phase output
    assert "no TPU" in err


def test_ulp_distance():
    a = np.array([1.0, -2.0, 0.0], np.float32)
    b = a.copy()
    assert smoke.ulp_distance(a, b) == 0
    b[0] = np.nextafter(b[0], np.float32(2))
    b[1] = np.nextafter(np.nextafter(b[1], np.float32(0)), np.float32(0))
    assert smoke.ulp_distance(a, b) == 2
    # across zero: -0.0 and +0.0 coincide, the smallest subnormals are 2 apart
    tiny = np.float32(1e-45)
    assert smoke.ulp_distance(np.float32([-tiny]), np.float32([tiny])) == 2
    assert smoke.ulp_distance(np.float32([-0.0]), np.float32([0.0])) == 0


def test_ulp_error_is_normwise():
    b = np.array([4.0, 1e-6], np.float32)
    a = b + np.float32(1e-6)           # ~2 ulp of 1e-6 away from zero-ish
    assert smoke.ulp_distance(a, b) > 1000          # elementwise explodes
    assert smoke.ulp_error(a, b) == pytest.approx(
        1e-6 / np.spacing(np.float32(4.0)), rel=1e-3)   # ~2.1 ulp of 4.0
    assert smoke.ulp_error(b, b) == 0


def test_kernel_phase_small():
    gemm = (("two_mounts_unaligned", 16, 150, 19, 64),)
    epi = (("maxpool_4to2", 2 * 16, 24, dict(act="relu", pool="max",
                                              window=2, img_hw=4), False),
           ("avgpool_res", 2 * 16, 24, dict(act="relu", pool="avg",
                                            window=4, img_hw=4), True),
           ("softmax", 4, 10, dict(softmax=True), False))
    checks = smoke.kernel_phase(gemm, epi)
    assert len(checks) == 3 + len(epi)     # exact, dense, sliced per GEMM
    assert all(ok for _, ok in checks), checks


@pytest.fixture(scope="module")
def vit_model():
    model, checks = smoke.serve_phase("vit_tiny", 1, batches=(1, 2),
                                      steady_runs=1)
    return model, checks


def test_serve_phase_vit_depth1(vit_model):
    model, checks = vit_model
    passed = _passed(checks)
    assert passed == {"serve/vit_tiny/finite": True,
                      "serve/vit_tiny/argmax": True,
                      "serve/vit_tiny/bit_exact": True,
                      "stages/vit_tiny/gemm": True,
                      "stages/vit_tiny/fb_tail": True}
    assert model.config == smoke.CLIP_FREE


def test_first_divergence_names_the_departing_stage(vit_model):
    model, _ = vit_model
    x = smoke.request(model.graph, 2, smoke.SEED)
    oracle = smoke.oracle_buffers(model.graph, model.config, model.params, x)
    assert smoke.first_divergence(model, x, oracle) is None
    # shift one bias on the oracle's side only
    name, ulps, err = smoke.first_divergence(model, x,
                                             _shifted_oracle(model, x))
    assert name == "b0_gelu" and ulps > 0 and err > 0


def _shifted_oracle(model, x):
    """The oracle with fc1's bias shifted: it parts from the program at
    fc1's stage (fc1 + gelu, buffer "b0_gelu")."""
    params = dict(model.params)
    params["b0_fc1"] = dict(params["b0_fc1"],
                            b=params["b0_fc1"]["b"] + 1e-3)
    return smoke.oracle_buffers(model.graph, model.config, params, x)


def _stage_check(model, oracle=None):
    x = smoke.request(model.graph, 2, smoke.SEED)
    if oracle is None:
        oracle = smoke.oracle_buffers(model.graph, model.config,
                                      model.params, x)
    return smoke.stage_check(model, x, oracle)


def test_stage_check_passes_and_feeds_every_stage(vit_model):
    model, _ = vit_model
    st = _stage_check(model)
    assert st["bad_gemm"] == [] and st["tail"] <= st["bound"]
    # fed separately, a stage compiles apart from the whole-program
    # oracle: a few ulps at most on the CPU
    assert st["oracle"] <= smoke.FB_TAIL_ULP
    # every stage reads the fed buffers: a shifted oracle parts from the
    # program at fc1's stage only, never in a GEMM or between kernels
    x = smoke.request(model.graph, 2, smoke.SEED)
    st = _stage_check(model, _shifted_oracle(model, x))
    assert st["bad_gemm"] == [] and st["tail"] <= st["bound"]
    assert st["oracle_stage"] == "b0_gelu"
    assert st["oracle"] > smoke.FB_TAIL_ULP


def test_stage_check_holds_every_gemm_bit_exact(vit_model, monkeypatch):
    model, _ = vit_model
    exact = smoke.xla_gemm
    monkeypatch.setattr(smoke, "xla_gemm",
                        lambda *a, **kw: exact(*a, **kw).at[0, 0].add(1))
    x = smoke.request(model.graph, 2, smoke.SEED)
    names = [n for n, _, _ in smoke.run_stages(model, x)]
    assert _stage_check(model)["bad_gemm"] == names
    assert "b0_attn@probs" in names


def test_stage_check_holds_every_fb_tail(vit_model, monkeypatch):
    model, _ = vit_model
    tail = smoke.xla_epilogue
    monkeypatch.setattr(smoke, "xla_epilogue",
                        lambda *a, **kw: tail(*a, **kw) * 1.001)
    st = _stage_check(model)
    assert st["bad_gemm"] == [] and st["tail"] > st["bound"]


def test_tail_bound_scales_with_the_logits():
    assert smoke.tail_bound(np.float32([0.5, -0.25])) == smoke.FB_TAIL_ULP
    assert smoke.tail_bound(np.float32([3.0, -20.0])) == \
        20 * smoke.FB_TAIL_ULP


def test_compiled_phase_fails_in_interpret_mode(vit_model):
    model, _ = vit_model
    checks = smoke.compiled_phase(model)
    assert _passed(checks) == {"compiled/vit_tiny": False}


def test_sliced_and_save_load_phases(vit_model):
    model, _ = vit_model
    assert all(ok for _, ok in smoke.sliced_phase("vit_tiny", 1, batch=2))
    assert all(ok for _, ok in smoke.save_load_phase(model, batch=2))


def test_nets_option_selects_and_checks_names(capsys):
    """``--nets`` names networks of ``NETS``; an unknown one is refused
    before anything runs.  ``deit_ti`` is among them, at its depth."""
    assert ("deit_ti", 12) in smoke.NETS
    with pytest.raises(SystemExit):
        smoke.main(["--nets", "resnet18,nope"])
    assert "nope" in capsys.readouterr().err
    g = smoke.graph_of("deit_ti", 1)
    assert sum(l.kind == "attention" for l in g.layers) == 1
    assert g.input_shape(1) == (1, 224, 224, 3)
