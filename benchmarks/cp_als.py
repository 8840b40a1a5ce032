"""Benchmark: CP-ALS end-to-end + dimension-tree reuse (§VII outlook).

Wall-time per sweep for plain per-mode MTTKRP vs the dimension tree, and
fit trajectories (both must match: the tree is exactly Gauss-Seidel ALS).
Each case also reports the distributed-sweep communication model at P=64:
the Eq (12) sweep-optimal grid from ``distributed.grid_select`` and the
amortization ratio of one stationary ALS sweep vs N independent per-mode
Alg-3 calls (HLO-measured equivalents live in tests/dist_worker.py).

The ``cp_als_sweep[...]`` rows are the fused-sweep success metric: sweep
walltime under ``sweep="fused"`` (the arXiv:1708.08976 mode-reuse
schedule, 2 tensor passes) vs ``sweep="per_mode"`` (N passes), plus the
fused sweep under the bf16 ``compute_dtype`` policy.  Both timings warm
the dispatch caches first so the comparison is steady-state walltime.

``REPRO_BENCH_TINY=1`` shrinks to one tiny shape for CI smoke runs.
"""

from __future__ import annotations

import os
import time

import jax

from repro.core.bounds import par_stationary_cost
from repro.core.cp_als import cp_als
from repro.core.dimension_tree import dimtree_flops, naive_all_mode_flops
from repro.core.tensor import random_low_rank_tensor
from repro.distributed.grid_select import (
    select_stationary_grid,
    stationary_sweep_words,
)
from repro.engine.context import ExecutionContext

CASES = [
    ((48, 48, 48), 8),
    ((32, 32, 32, 32), 6),
    ((96, 64, 32), 12),
]

GRID_PROCS = 64


def _time_als(x, rank, tree: bool) -> tuple[float, float]:
    t0 = time.perf_counter()
    res = cp_als(
        x, rank, n_iters=5, key=jax.random.PRNGKey(1),
        sweep="dimtree" if tree else "per_mode",
    )
    jax.block_until_ready(res.factors[0])
    return (time.perf_counter() - t0) / 5, res.final_fit


def _time_sweep(x, rank, sweep: str, ctx=None, n_iters=5, reps=3):
    """Steady-state per-sweep walltime under one sweep schedule.

    Best-of-``reps``: these rows feed the perf-trajectory gate's
    fused-speedup floor, so a single scheduler hiccup must not flip the
    recorded winner."""
    kw = {"key": jax.random.PRNGKey(1), "sweep": sweep}
    if ctx is not None:
        kw["ctx"] = ctx
    cp_als(x, rank, n_iters=1, **kw)  # warm dispatch/jit caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        res = cp_als(x, rank, n_iters=n_iters, **kw)
        jax.block_until_ready(res.factors[0])
        best = min(best, (time.perf_counter() - t0) / n_iters)
    return best, res.final_fit


def rows() -> list[tuple[str, float, str]]:
    tiny = os.environ.get("REPRO_BENCH_TINY") == "1"
    cases = [((16, 16, 16), 4)] if tiny else CASES
    out = []
    for dims, rank in cases:
        x, _ = random_low_rank_tensor(jax.random.PRNGKey(0), dims, rank)
        t_plain, fit_plain = _time_als(x, rank, tree=False)
        t_tree, fit_tree = _time_als(x, rank, tree=True)
        model_naive = naive_all_mode_flops(dims, rank)
        model_tree = dimtree_flops(dims, rank)
        choice = select_stationary_grid(dims, rank, GRID_PROCS, mode=None)
        # MTTKRP traffic only on both sides (neither baseline includes the
        # ALS solve's R^2 Gram collectives): the BHK amortization is 2/N
        sweep_w = stationary_sweep_words(
            dims, rank, choice.grid, include_solve_terms=False
        )
        indep_w = sum(
            par_stationary_cost(dims, rank, choice.grid, m)
            for m in range(len(dims))
        )
        name = f"cp_als[{'x'.join(map(str, dims))},R{rank}]"
        derived = (
            f"fit={fit_plain:.4f};fit_tree={fit_tree:.4f};"
            f"tree_speedup={t_plain / max(t_tree, 1e-9):.2f}x;"
            f"modeled_flop_ratio={model_naive / max(model_tree, 1):.2f};"
            f"grid_p{GRID_PROCS}={'x'.join(map(str, choice.grid))};"
            f"sweep_vs_indep_comm={sweep_w / max(indep_w, 1e-9):.2f}"
        )
        out.append((name, t_tree * 1e6, derived))

    # fused-sweep success metric: mode-reuse schedule (2 tensor passes)
    # vs per-mode dispatch (N passes), same driver, steady-state walltime
    # per sweep.  Backend pinned to einsum so the comparison isolates the
    # schedule (named in derived, per the harness convention); the last
    # case is sized so the tensor passes dominate the Gram/solve work.
    sweep_cases = (
        [((16, 16, 16), 4)] if tiny else CASES + [((96, 96, 96), 16)]
    )
    for dims, rank in sweep_cases:
        x, _ = random_low_rank_tensor(jax.random.PRNGKey(0), dims, rank)
        ctx = ExecutionContext.create(backend="einsum")
        t_pm, fit_pm = _time_sweep(x, rank, "per_mode", ctx=ctx)
        t_fu, fit_fu = _time_sweep(x, rank, "fused", ctx=ctx)
        ctx_bf16 = ExecutionContext.create(
            backend="einsum", compute_dtype="bfloat16"
        )
        t_bf, _ = _time_sweep(x, rank, "fused", ctx=ctx_bf16)
        sweep_name = f"cp_als_sweep[{'x'.join(map(str, dims))},R{rank}]"
        sweep_derived = (
            f"backend=einsum;t_per_mode_us={t_pm * 1e6:.1f};"
            f"fused_speedup={t_pm / max(t_fu, 1e-9):.2f}x;"
            f"t_fused_bf16_us={t_bf * 1e6:.1f};"
            f"fit_per_mode={fit_pm:.4f};fit_fused={fit_fu:.4f}"
        )
        out.append((sweep_name, t_fu * 1e6, sweep_derived))
    return out
