"""The paper's primary contribution: distributed k-core decomposition as a
composable JAX module, with exact message accounting, termination-detection
models, and a simulated-network cost model."""

from repro.core.bz import bz_core_numbers, max_core
from repro.core.dispatch import DispatchPlan, resolve_plan
from repro.core.jit_telemetry import compile_count, compile_seconds
from repro.core.kcore import (
    KCoreConfig,
    KCoreResult,
    fused_round_stats,
    kcore_decompose,
    kcore_decompose_sharded,
    make_sharded_superstep,
)
from repro.core.cost_model import SeedCostModel, choose_seed, estimate_ub_passes
from repro.core.messages import MessageStats, heartbeat_overhead, work_bound
from repro.core.outofcore import (
    OutOfCoreResult,
    OutOfCoreStats,
    outofcore_decompose,
)
from repro.core.runtime import (
    FusedOutcome,
    fused_converge_dense,
    fused_converge_sharded,
)

__all__ = [
    "SeedCostModel",
    "choose_seed",
    "estimate_ub_passes",
    "FusedOutcome",
    "fused_converge_dense",
    "fused_converge_sharded",
    "bz_core_numbers",
    "max_core",
    "DispatchPlan",
    "resolve_plan",
    "compile_count",
    "compile_seconds",
    "KCoreConfig",
    "KCoreResult",
    "fused_round_stats",
    "kcore_decompose",
    "kcore_decompose_sharded",
    "make_sharded_superstep",
    "MessageStats",
    "heartbeat_overhead",
    "work_bound",
    "OutOfCoreResult",
    "OutOfCoreStats",
    "outofcore_decompose",
]
