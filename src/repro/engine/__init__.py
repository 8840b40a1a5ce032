"""Unified MTTKRP execution engine.

One planner (``plan``), one dispatch layer (``execute``), and the
kernel-backed dimension tree (``tree``). Every consumer — the Pallas kernel
wrappers, the two-level-memory simulator, CP-ALS, the shard_map parallel
algorithms, and the benchmarks — quotes blocking decisions and traffic
numbers from the same :class:`~repro.engine.plan.BlockPlan` objects.

Layering (see docs/ARCHITECTURE.md):

    plan      — Memory descriptors, BlockPlan, choose_blocks, Eq 9/10 models
    context   — ExecutionContext: the one immutable config object + the
                validation catalog
    execute   — mttkrp(x, factors, mode, ctx=...) + partial contractions
                (a leading batch axis vmaps B problems over ONE plan)
    batch     — cp_als_batched / tucker_hooi_batched: B decompositions
                as one vmapped sweep with per-element convergence masks
    tree      — all-mode MTTKRP / ALS sweeps over a binary dimension tree
"""

from .context import (
    VALID_BACKENDS,
    Distribution,
    ExecutionContext,
    PlanDecision,
    ProblemSpec,
    check_backend,
)
from .plan import (
    LANE,
    SUBLANE,
    VMEM_BUDGET,
    VMEM_BYTES,
    BlockPlan,
    Memory,
    MultiTTMPlan,
    best_uniform_block,
    choose_blocks,
    choose_multi_ttm_blocks,
    mttkrp_traffic_model,
    uniform_block_feasible,
    uniform_multi_ttm_plan,
)
from .batch import (
    BatchedCPResult,
    BatchedTuckerResult,
    batched_choose_blocks,
    cp_als_batched,
    tucker_hooi_batched,
)
from .execute import mttkrp, contract_partial, multi_ttm
from .tree import all_mode_mttkrp, dimtree_als_sweep

__all__ = [
    "VALID_BACKENDS",
    "Distribution",
    "ExecutionContext",
    "PlanDecision",
    "ProblemSpec",
    "check_backend",
    "LANE",
    "SUBLANE",
    "VMEM_BUDGET",
    "VMEM_BYTES",
    "BlockPlan",
    "Memory",
    "MultiTTMPlan",
    "best_uniform_block",
    "choose_blocks",
    "choose_multi_ttm_blocks",
    "uniform_multi_ttm_plan",
    "mttkrp_traffic_model",
    "uniform_block_feasible",
    "mttkrp",
    "contract_partial",
    "multi_ttm",
    "BatchedCPResult",
    "BatchedTuckerResult",
    "batched_choose_blocks",
    "cp_als_batched",
    "tucker_hooi_batched",
    "all_mode_mttkrp",
    "dimtree_als_sweep",
]
