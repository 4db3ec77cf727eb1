"""System-level simulator tests: the paper's headline claims hold."""

import pytest

from repro.api import GRAPHS
from repro.core.simulator import simulate_hurry
from repro.core.baselines import simulate_isaac, simulate_misca

NETS = ("alexnet", "vgg16", "resnet18")


@pytest.fixture(scope="module")
def reports():
    out = {}
    for net in NETS:
        layers = list(GRAPHS[net]().layers)
        out[net] = {
            "hurry": simulate_hurry(layers),
            "isaac128": simulate_isaac(layers, 128),
            "isaac256": simulate_isaac(layers, 256),
            "isaac512": simulate_isaac(layers, 512),
            "misca": simulate_misca(layers),
        }
    return out


def test_speedup_over_isaac_in_paper_band(reports):
    """Paper Fig 7: 1.21-3.35x speedup over ISAAC."""
    for net in NETS:
        r = reports[net]
        s = r["isaac128"].throughput_cycles / r["hurry"].throughput_cycles
        assert 1.0 < s < 4.0, (net, s)


def test_energy_efficiency_band(reports):
    """Paper Fig 6a: 2.66-5.72x energy efficiency vs baselines."""
    for net in NETS:
        r = reports[net]
        e = r["isaac128"].energy_pj / r["hurry"].energy_pj
        assert 1.5 < e < 7.0, (net, e)


def test_area_efficiency_band(reports):
    """Paper Fig 6b: 2.98-7.91x area efficiency vs baselines."""
    for net in NETS:
        r = reports[net]
        a = r["hurry"].area_efficiency / r["isaac128"].area_efficiency
        assert 2.0 < a < 9.0, (net, a)


def test_spatial_utilization_ordering(reports):
    """HURRY > ISAAC-512 spatial utilization; 128 > 256 > 512 (Fig 1a)."""
    for net in NETS:
        r = reports[net]
        assert r["hurry"].spatial_utilization > r["isaac512"].spatial_utilization
        assert (r["isaac128"].spatial_utilization
                >= r["isaac256"].spatial_utilization
                >= r["isaac512"].spatial_utilization)


def test_temporal_utilization_ordering(reports):
    """HURRY >> ISAAC and MISCA temporal utilization (Fig 8b)."""
    for net in NETS:
        r = reports[net]
        assert r["hurry"].temporal_utilization > 2 * r["isaac128"].temporal_utilization
        assert r["hurry"].temporal_utilization > 2 * r["misca"].temporal_utilization


def test_hurry_spatial_lowest_std(reports):
    """Paper: HURRY has the most consistent per-layer spatial utilization."""
    for net in NETS:
        r = reports[net]
        assert (r["hurry"].spatial_utilization_std
                <= r["isaac512"].spatial_utilization_std + 0.05)


def test_misca_spatial_beats_isaac512(reports):
    """MISCA's mixed sizes raise spatial utilization over static 512."""
    for net in NETS:
        r = reports[net]
        assert (r["misca"].spatial_utilization
                >= r["isaac512"].spatial_utilization)


def test_adc_dominates_baseline_power(reports):
    """Paper §I: ADCs contribute over 60% of RIA power."""
    for net in NETS:
        e = reports[net]["isaac128"].energy
        assert e.adc / e.total_pj > 0.5, (net, e.adc / e.total_pj)


def test_chip_area_reduction(reports):
    """Paper §IV-B4: total chip area reduction vs ISAAC ~2.6x."""
    r = reports["alexnet"]
    ratio = r["isaac128"].area_mm2 / r["hurry"].area_mm2
    assert 1.8 < ratio < 3.5, ratio
