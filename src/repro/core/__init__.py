"""HURRY core: reconfigurable, multifunctional ReRAM in-situ accelerator model.

Public surface:
  crossbar      — bit-sliced functional GEMM (the compute oracle)
  functional_blocks, scheduling, bas — BAS + Algorithms 1 & 2
  simulator     — end-to-end HURRY chip model
  baselines     — ISAAC(-128/256/512) and MISCA
  balance       — Algorithm 2's predicate re-used as a TPU tile balancer
"""

from .crossbar import (CrossbarConfig, crossbar_matmul, crossbar_linear,
                       quantize_symmetric)
from .functional_blocks import FBRequest, FunctionalBlock
from .scheduling import (ArrayPlan, fb_relative_positioning,
                         fb_size_balancing, decode_sequence_pair, place_fbs,
                         plan_array, balance_feasible)
from .bas import ArrayConfig, ArraySchedule, schedule_array, check_legal
from .simulator import ChipConfig, SimReport, simulate_hurry
from .baselines import BaselineConfig, simulate_isaac, simulate_misca
from .workload import LayerSpec, layer_groups

__all__ = [
    "CrossbarConfig", "crossbar_matmul", "crossbar_linear", "quantize_symmetric",
    "FBRequest", "FunctionalBlock",
    "ArrayPlan", "fb_relative_positioning", "fb_size_balancing",
    "decode_sequence_pair", "place_fbs", "plan_array", "balance_feasible",
    "ArrayConfig", "ArraySchedule", "schedule_array", "check_legal",
    "ChipConfig", "SimReport", "simulate_hurry",
    "BaselineConfig", "simulate_isaac", "simulate_misca",
    "LayerSpec", "layer_groups",
]
