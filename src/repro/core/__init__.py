"""Core: the paper's contribution — tail-effect modeling and elimination."""

from repro.core.hardware import (
    HardwareSpec, TPU_V5E, TPU_V4, TPU_V5P, TPU_LITE, device_hardware,
    get_hardware, hardware_for_kind,
)
from repro.core.tail_model import (
    LayerShape, StairPoint, StairTable, ModelStairTable,
    WaveQuantizationModel, GridWaveModel, staircase_edges, ceil_div,
)
from repro.core.candidates import (
    analytic_candidates, profile_candidates, model_profile_candidates,
    realizable_candidates, snap_down, snap_up, snap_nearest,
)
from repro.core.tail_optimizer import (
    TailEffectOptimizer, TunableLayer, OptimizationResult, Move,
    discretize_pruning_space, tunable_from_profile,
)
from repro.core.table_cache import ProfileTableCache, hardware_fingerprint
from repro.core.plan_address import ModuleRef, plan_key, snap_heads
from repro.core.roofline import RooflineReport, build_report
from repro.core.hlo_analysis import (
    parse_collectives, CollectiveSummary, cost_summary, count_ops,
)

__all__ = [
    "HardwareSpec", "TPU_V5E", "TPU_V4", "TPU_V5P", "TPU_LITE",
    "device_hardware", "get_hardware", "hardware_for_kind", "LayerShape",
    "StairPoint", "StairTable",
    "ModelStairTable", "WaveQuantizationModel",
    "GridWaveModel", "staircase_edges", "ceil_div", "analytic_candidates",
    "profile_candidates", "model_profile_candidates",
    "realizable_candidates", "snap_down", "snap_up", "snap_nearest",
    "TailEffectOptimizer", "TunableLayer", "OptimizationResult", "Move",
    "discretize_pruning_space", "tunable_from_profile",
    "ProfileTableCache", "hardware_fingerprint", "RooflineReport",
    "build_report", "ModuleRef", "plan_key", "snap_heads",
    "parse_collectives", "CollectiveSummary", "cost_summary", "count_ops",
]
