"""A copy of the benchmark's tree with CPU-sized cells, for the tests.

The tiny configurations are the real ones with their scale cut and the
limits of ``resnet18-cifar10`` (the only ones measured so far), so a
test of the check holds a real limit.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {
    "tiny-resnet": ("resnet18-cifar10",
                    dict(input_hw=8, stem_ch=8, widths=[8, 16],
                         blocks=[1, 1])),
    "tiny-vit": ("deit-tiny-224",
                 dict(input_hw=16, patch=8, dim=16, depth=1, heads=2,
                      mlp_ratio=2, classes=10)),
}
MIX = {"loop": "closed", "in_flight": 2, "sizes": {"1": 1, "3": 2},
       "block": 8, "pool_images": 16, "check_requests": 3}


def make_tree(root: Path) -> Path:
    """``root`` gets a BENCHMARK.json naming one tiny cell per family and a
    copy of ``bench/`` with their files added; returns ``root``."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    limits = json.loads((BENCH / "configs" / "resnet18-cifar10.json")
                        .read_text())["limits"]
    for name, (real, cut) in TINY.items():
        sizes = json.loads((BENCH / "configs" / f"{real}.json").read_text())
        sizes.update(name=name, limits=limits, **cut)
        path = root / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(sizes))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": sorted(cut), "why": "test"})
        spec["workloads"].append({"name": f"{name}.tiny-mix", "config": name,
                                  "traffic": "tiny-mix", "chips": 1,
                                  "why": "test"})
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(MIX))
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
