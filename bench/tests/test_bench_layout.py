"""The harness finds every part of a cell by its name; a later change adds
a configuration, a mix or a metric as files and entries only."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.tiny import BENCH, REPO, make_tree


def test_real_cells_resolve():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(REPO, w["name"])
        assert cell.chips == w["chips"] == 1
        names = {m["name"] for m in cell.end_to_end}
        assert {"images_per_s", "setup_s"} <= names
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.reader(cell, m["name"]))
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()


def test_dropped_in_files_are_found(tmp_path):
    root = make_tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a new configuration (a wider tiny resnet), a new mix and a new metric
    sizes = json.loads((root / "bench/configs/tiny-resnet.json").read_text())
    sizes.update(name="wide-resnet", stem_ch=16, widths=[16, 32])
    (root / "bench/configs/wide-resnet.json").write_text(json.dumps(sizes))
    (root / "bench/traffic/one-at-a-time.json").write_text(json.dumps({
        "loop": "closed", "in_flight": 1, "sizes": {"2": 1}, "block": 4,
        "pool_images": 8, "check_requests": 1}))
    (root / "bench/metrics/requests_sent.py").write_text(
        "def read(ctx):\n    return float(len(ctx.sent))\n")
    spec["configs"].append({"name": "wide-resnet", "source": "test",
                            "file": "bench/configs/wide-resnet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "wide-resnet.one-at-a-time",
                              "config": "wide-resnet",
                              "traffic": "one-at-a-time", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests_sent", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "images_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell(root, "wide-resnet.one-at-a-time",
                             root / "bench")
    assert cell.sizes["widths"] == [16, 32]
    assert cell.mix.in_flight == 1 and cell.mix.distinct_sizes() == [2]
    assert [m["name"] for m in cell.per_layer] == ["requests_sent"]
    assert harness.reader(cell, "requests_sent")(
        type("Ctx", (), {"sent": [1, 2, 3]})) == 3.0


def test_unknown_workload_fails():
    with pytest.raises(KeyError):
        harness.find_cell(REPO, "no-such-model.no-such-mix")


def _run(cwd, *args, path=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if not path:                 # only what the directory itself holds
        env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_unknown_workload_and_the_cpu():
    p = _run(REPO, "--workload", "nope", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""
    p = _run(REPO, "--workload", "resnet18-cifar10.offline-b256",
             "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "resnet18-cifar10.offline-b256",
             "--seed", "1", "--seconds", "1", path=False)
    assert p.returncode != 0 and p.stdout.strip() == ""
