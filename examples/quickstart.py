"""Quickstart: the ``repro.api`` front door, end to end.

    PYTHONPATH=src python examples/quickstart.py [--net alexnet] [--batch 2]

One network definition drives everything:

  1. get a graph — a paper CNN from ``api.zoo`` or a custom
     ``NetworkBuilder`` program (``--net custom``);
  2. ``api.compile`` it under one ``HurryConfig`` into a
     ``CompiledModel``;
  3. ``.simulate()`` the paper's headline comparison (Figs 6-8:
     HURRY vs ISAAC/MISCA cycles, energy, area, utilization);
  4. ``.run()`` it numerically on the Pallas crossbar + fused-FB
     kernels and check bit-exactness against the functional crossbar
     forward (clip-free config, DESIGN.md §4/§5);
  5. ``.save()`` / ``api.load()`` it and verify the loaded model —
     which never touches the compiler — serves the same bits.
"""

import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro import api
from repro.api import HurryConfig, NetworkBuilder
from repro.compile_cache import use_compile_cache
from repro.core.crossbar import make_crossbar_matmul


def custom_graph():
    """A user-defined net: the builder is not limited to the paper CNNs."""
    nb = NetworkBuilder("custom", input_hw=16, input_ch=8)
    nb.conv(32, name="c1")
    r1 = nb.relu(name="r1")
    proj = nb.conv(48, k=1, padding=0, name="proj", input_from=r1)
    nb.conv(48, name="c2", input_from=r1)
    nb.residual(proj, name="res")
    nb.relu(name="r2")
    nb.maxpool(name="p1")
    nb.fc(10, name="fc")
    nb.softmax(name="softmax")
    return nb.build()


def print_sim_table(model: api.CompiledModel) -> None:
    reports = {name: model.simulate(arch)
               for name, arch in [("HURRY", "hurry"), ("ISAAC-128", "isaac-128"),
                                  ("ISAAC-256", "isaac-256"),
                                  ("ISAAC-512", "isaac-512"),
                                  ("MISCA", "misca")]}
    print(f"{'arch':10s} {'cycles':>10s} {'energy uJ':>10s} "
          f"{'area mm2':>9s} {'spatial':>8s} {'temporal':>9s}")
    for name, r in reports.items():
        print(f"{name:10s} {r.throughput_cycles:10.0f} "
              f"{r.energy_pj / 1e6:10.2f} {r.area_mm2:9.2f} "
              f"{r.spatial_utilization:8.2%} {r.temporal_utilization:9.2%}")
    h, i = reports["HURRY"], reports["ISAAC-128"]
    print(f"\nHURRY vs ISAAC-128:  speedup "
          f"{i.throughput_cycles / h.throughput_cycles:.2f}x"
          f"  energy-eff {i.energy_pj / h.energy_pj:.2f}x"
          f"  area-eff {h.area_efficiency / i.area_efficiency:.2f}x")
    print("paper claims:        speedup 1.21-3.35x | energy 2.66-5.72x | "
          "area 2.98-7.91x (across nets/baselines)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="alexnet",
                    choices=["alexnet", "vgg16", "resnet18", "vit-tiny",
                             "custom"])
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args()
    use_compile_cache()

    # one config for chip geometry, crossbar numerics, and the executor;
    # 511 rows keeps every ADC read clip-free (DESIGN.md §4) so the
    # compiled program is bit-exact vs the functional model
    config = HurryConfig(array_rows=511)
    network = {"custom": custom_graph, "vit-tiny": "vit_tiny"}.get(
        args.net, args.net)
    if callable(network):
        network = network()
    model = api.compile(network, config)
    graph = model.graph
    is_seq = args.net == "vit-tiny"

    print(f"=== {graph.name} (int8, one 16-tile chip) ===")
    print(model.summary())

    if is_seq:
        # the analytical chip model does not cover dynamic-operand
        # mounts yet (DESIGN.md §9) — numeric execution is the story here
        print(f"\n=== analytical simulation ({graph.name}): n/a for "
              "sequence workloads ===")
    else:
        print(f"\n=== analytical simulation ({graph.name}) ===")
        print_sim_table(model)

    print(f"\n=== compiled-program inference ({graph.name}) ===")
    x = jax.random.normal(jax.random.PRNGKey(0),
                          graph.input_shape(args.batch))
    model.warmup(args.batch, logits=True)     # pay trace+compile once
    t0 = time.perf_counter()
    y_prog = jax.block_until_ready(model.run(x, logits=True))
    us = (time.perf_counter() - t0) * 1e6
    fwd = jax.jit(lambda p, v: graph.forward(
        p, v, mm=make_crossbar_matmul(config.crossbar()), logits=True))
    y_fn = fwd(model.params, x)
    exact = bool(np.array_equal(np.asarray(y_fn), np.asarray(y_prog)))
    agree = float((np.argmax(np.asarray(y_fn), 1)
                   == np.argmax(np.asarray(y_prog), 1)).mean())
    print(f"model.run vs functional crossbar forward: bit-exact={exact}  "
          f"argmax-agree={agree:.0%}  steady-state {us:.0f} us/batch{args.batch}")

    print(f"\n=== save / load ({graph.name}) ===")
    with tempfile.TemporaryDirectory() as d:
        path = model.save(os.path.join(d, f"{graph.name}.npz"))
        kb = os.path.getsize(path) / 1024
        loaded = api.load(path)               # no compiler involved
        y_loaded = loaded.run(x, logits=True)
        roundtrip = bool(np.array_equal(np.asarray(y_prog),
                                        np.asarray(y_loaded)))
        print(f"saved {kb:.0f} KiB -> loaded model bit-exact={roundtrip}")

    if not (exact and roundtrip):
        raise SystemExit("bit-exactness check failed")


if __name__ == "__main__":
    main()
