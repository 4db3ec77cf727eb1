"""Unified HURRY configuration — the single derivation point.

``HurryConfig`` holds everything a user can turn: chip geometry (tiles,
IMAs, array size — the simulator's knobs), crossbar numerics
(quantization bit widths, ADC resolution, read noise — the functional
model's knobs), and executor block-size overrides (the Pallas kernels'
knobs; by default each kernel picks its own per path).
Every downstream structure is *derived* here and nowhere else:

  ``chip()``      -> ``core.simulator.ChipConfig``   (analytical model)
  ``crossbar()``  -> ``core.crossbar.CrossbarConfig`` (numeric model +
                     compiled-program executor)
  ``baseline()``  -> ``core.baselines.BaselineConfig`` (ISAAC/MISCA
                     comparison chips sharing this geometry)

``program/compile.py`` accepts a ``HurryConfig`` directly;
``core/simulator.py`` and ``core/baselines.py`` accept one via duck
typing (anything with a ``.chip()`` / ``.baseline()`` derivation), so
``core`` never imports ``api``.  Legacy callers that pass only a
``ChipConfig`` are routed through ``HurryConfig.from_chip`` so the
ChipConfig -> CrossbarConfig derivation also lives here, not in each
consumer.
"""

from __future__ import annotations

import dataclasses

from repro.core.baselines import BaselineConfig
from repro.core.crossbar import CrossbarConfig
from repro.core.simulator import ChipConfig

# geometry/quantization fields shared verbatim with ChipConfig
_CHIP_FIELDS = ("n_tiles", "imas_per_tile", "array_rows", "array_cols",
                "cell_bits", "weight_bits", "input_bits",
                "bus_bytes_per_cycle", "edram_kb_per_tile", "ir_kb",
                "or_kb", "controller_area_mult")


@dataclasses.dataclass(frozen=True)
class HurryConfig:
    """One config for the whole stack: chip + crossbar + executor."""

    # -- chip geometry (paper §II-A) ---------------------------------------
    n_tiles: int = 16
    imas_per_tile: int = 8
    array_rows: int = 512
    array_cols: int = 512
    cell_bits: int = 1
    bus_bytes_per_cycle: int = 32
    edram_kb_per_tile: int = 512
    ir_kb: int = 32
    or_kb: int = 4
    controller_area_mult: float = 1.12
    sim_batch: int = 16           # pipeline batch of the analytical model

    # -- crossbar numerics (quantization / ADC / read noise) ---------------
    weight_bits: int = 8
    input_bits: int = 8
    adc_bits: int = 9             # paper pairs 512 rows with a 9-bit ADC
    dac_bits: int = 1
    noise_sigma_thermal: float = 0.0
    noise_sigma_shot: float = 0.0

    # -- executor (Pallas kernel block sizes) ------------------------------
    # None keeps each kernel's per-path default (kernels/tiling.py)
    block_m: int | None = None
    block_n: int | None = None

    # -- derivations (the only place these conversions exist) --------------

    def chip(self) -> ChipConfig:
        """Chip geometry for the analytical simulator and the scheduler."""
        kw = {f: getattr(self, f) for f in _CHIP_FIELDS}
        return ChipConfig(batch=self.sim_batch, **kw)

    def crossbar(self) -> CrossbarConfig:
        """Numeric array model for the functional path and the executor.

        Delegates to ``ChipConfig.crossbar`` (the base geometry mapping)
        and overlays the knobs only this config carries.
        """
        return self.chip().crossbar(
            adc_bits=self.adc_bits, dac_bits=self.dac_bits,
            noise_sigma_thermal=self.noise_sigma_thermal,
            noise_sigma_shot=self.noise_sigma_shot)

    def baseline(self, **overrides) -> BaselineConfig:
        """ISAAC/MISCA comparison chip sharing this geometry.

        Baseline-specific structure (2-bit MLC cells, halved OR, static
        arrays) keeps ``BaselineConfig`` defaults unless overridden.
        """
        kw = {f: getattr(self, f) for f in _CHIP_FIELDS
              if f not in ("cell_bits", "or_kb", "controller_area_mult")}
        kw.update(batch=self.sim_batch, **overrides)
        return BaselineConfig(**kw)

    @classmethod
    def from_chip(cls, chip: ChipConfig, **overrides) -> "HurryConfig":
        """Lift a bare ChipConfig into the unified config (compat path)."""
        kw = {f: getattr(chip, f) for f in _CHIP_FIELDS}
        kw.update(sim_batch=chip.batch, **overrides)
        return cls(**kw)

    @property
    def clip_free(self) -> bool:
        """DESIGN.md §4 predicate for the derived crossbar numerics."""
        return self.crossbar().clip_free
