"""Paper-table benchmarks: one function per figure of HURRY §IV.

Each function returns a list of (name, us_per_call, derived) rows, where
``derived`` is the figure's headline quantity (a ratio vs ISAAC, or a
utilization percentage).  Paper targets:
  Fig 6a energy efficiency 2.66-5.72x | Fig 6b area efficiency 2.98-7.91x
  Fig 7 speedup 1.21-3.35x | Fig 8 spatial/temporal utilization gains.
"""

from __future__ import annotations

import time

from repro.api.zoo import GRAPHS
from repro.core.simulator import simulate_hurry
from repro.core.baselines import simulate_isaac, simulate_misca

NETS = ("alexnet", "vgg16", "resnet18")


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, (time.perf_counter() - t0) * 1e6


def _reports(net):
    layers = list(GRAPHS[net]().layers)
    rs = {}
    us = 0.0
    for name, fn, args in [
            ("hurry", simulate_hurry, ()),
            ("isaac128", simulate_isaac, (128,)),
            ("isaac256", simulate_isaac, (256,)),
            ("isaac512", simulate_isaac, (512,)),
            ("misca", simulate_misca, ())]:
        r, t = _timed(fn, layers, *args)
        rs[name] = r
        us += t
    return rs, us


def fig6_efficiency():
    rows = []
    for net in NETS:
        rs, us = _reports(net)
        h = rs["hurry"]
        for b in ("isaac128", "isaac256", "isaac512", "misca"):
            rows.append((f"fig6a_energy_eff/{net}/vs_{b}", us,
                         rs[b].energy_pj / h.energy_pj))
            rows.append((f"fig6b_area_eff/{net}/vs_{b}", us,
                         h.area_efficiency / rs[b].area_efficiency))
    return rows


def fig7_speedup():
    rows = []
    for net in NETS:
        rs, us = _reports(net)
        h = rs["hurry"]
        for b in ("isaac128", "isaac256", "isaac512", "misca"):
            rows.append((f"fig7_speedup/{net}/vs_{b}", us,
                         rs[b].throughput_cycles / h.throughput_cycles))
    return rows


def fig8_utilization():
    rows = []
    for net in NETS:
        rs, us = _reports(net)
        for name, r in rs.items():
            rows.append((f"fig8a_spatial/{net}/{name}", us,
                         r.spatial_utilization))
            rows.append((f"fig8b_temporal/{net}/{name}", us,
                         r.temporal_utilization))
        rows.append((f"fig8a_spatial_std/{net}/hurry", us,
                     rs["hurry"].spatial_utilization_std))
    return rows


def accuracy_drop():
    """§IV-B2: marginal accuracy drop from 1-bit cells + read noise.

    Runs the zoo CNNs' functional oracle through the bit-sliced crossbar
    (int8, with read noise) vs fp32 and reports logit agreement on
    random probes.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.crossbar import CrossbarConfig, make_crossbar_matmul

    rows = []
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 32, 3))
    for net in NETS:
        graph = GRAPHS[net]()
        params = graph.init_params(jax.random.PRNGKey(1))
        t0 = time.perf_counter()
        y_fp = graph.forward(params, x, logits=True)
        y_clean = graph.forward(params, x, mm=make_crossbar_matmul(),
                                logits=True)
        mm = make_crossbar_matmul(CrossbarConfig(noise_sigma_thermal=0.3),
                                  noise_key=jax.random.PRNGKey(9))
        y_noisy = graph.forward(params, x, mm=mm, logits=True)
        us = (time.perf_counter() - t0) * 1e6
        a_clean = float((jnp.argmax(y_fp, 1) == jnp.argmax(y_clean, 1)).mean())
        a_noisy = float((jnp.argmax(y_fp, 1) == jnp.argmax(y_noisy, 1)).mean())
        rows.append((f"accuracy/argmax_agree_int8_clean/{net}", us, a_clean))
        rows.append((f"accuracy/argmax_agree_noise0.3/{net}", us, a_noisy))
    return rows


ALL = [fig6_efficiency, fig7_speedup, fig8_utilization, accuracy_drop]
