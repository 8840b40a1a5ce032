"""The fused (mode-reuse) ALS sweep: the two-output pair kernel, the
Gauss-Seidel-exactness of the schedule, the sweep planner, the ``sweep=``
driver knob, and the ``kind="sweep"`` tune-cache path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cp_als import cp_als
from repro.core.tensor import random_low_rank_tensor
from repro.engine import Memory, mttkrp
from repro.engine.context import ExecutionContext
from repro.engine.plan import (
    choose_sweep_blocks,
    fused_pair_working_set_words,
)
from repro.engine.sweep import fused_als_sweep
from repro.kernels.sweep import (
    fused_pair_canonical_pallas,
    mttkrp_fused_pair_pallas,
)
from repro.tune import PlanCache, cache_key, isolated_cache
from repro.tune.search import resolve_sweep, tune_sweep


def _mk(dims, rank, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    kx, *kf = jax.random.split(key, len(dims) + 1)
    x = jax.random.normal(kx, dims, dtype)
    fs = [jax.random.normal(k, (d, rank), dtype) for k, d in zip(kf, dims)]
    return x, fs


def _pair_oracle(x, factors):
    """B0 (full MTTKRP mode 0) and P' = X x_{N-1} A_{N-1} via einsum."""
    n = x.ndim
    b0 = mttkrp(x, factors, 0, ctx=ExecutionContext.create(backend="einsum"))
    letters = "abcdefg"[:n]
    p = jnp.einsum(
        f"{letters},{letters[-1]}r->{letters[:-1]}r", x, factors[n - 1]
    )
    return b0, p


# ---------------------------------------------------------------------------
# The two-output pair kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims,rank", [((16, 8, 8), 4), ((8, 8, 4, 8), 3)])
def test_fused_pair_kernel_matches_oracle(dims, rank):
    x, fs = _mk(dims, rank, seed=1)
    b0_ref, p_ref = _pair_oracle(x, fs)
    b0, p = fused_pair_canonical_pallas(x, fs[1:], interpret=True)
    np.testing.assert_allclose(
        np.asarray(b0), np.asarray(b0_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(p_ref), rtol=1e-4, atol=1e-4
    )


def test_fused_pair_kernel_raw_blocked():
    """The raw kernel on aligned shapes, non-trivial grid in every axis."""
    dims, rank = (16, 8, 16), 8
    x, fs = _mk(dims, rank, seed=2)
    b0, p = mttkrp_fused_pair_pallas(
        x, fs[1:], block_i=8, block_contract=(4, 8), block_r=8,
        interpret=True,
    )
    b0_ref, p_ref = _pair_oracle(x, fs)
    np.testing.assert_allclose(
        np.asarray(b0), np.asarray(b0_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(p_ref), rtol=1e-4, atol=1e-4
    )


def test_fused_pair_padding_path():
    """Ragged shapes go through the canonical wrapper's pad/unpad."""
    dims, rank = (13, 9, 17), 5
    x, fs = _mk(dims, rank, seed=3)
    b0, p = fused_pair_canonical_pallas(x, fs[1:], interpret=True)
    b0_ref, p_ref = _pair_oracle(x, fs)
    np.testing.assert_allclose(
        np.asarray(b0), np.asarray(b0_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(p_ref), rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# Gauss-Seidel exactness of the fused schedule
# ---------------------------------------------------------------------------

def _als_update_closure(factors, rank, solve_dtype=jnp.float32):
    grams = [f.T @ f for f in factors]

    def update(mode, b):
        gamma = jnp.ones((rank, rank), solve_dtype)
        for k, g in enumerate(grams):
            if k != mode:
                gamma = gamma * g.astype(solve_dtype)
        ridge = 1e-5 * jnp.trace(gamma) / rank + 1e-12
        a = jnp.linalg.solve(
            gamma + ridge * jnp.eye(rank, dtype=solve_dtype),
            b.astype(solve_dtype).T,
        ).T.astype(b.dtype)
        grams[mode] = a.T @ a
        return a

    return update


@pytest.mark.parametrize("dims,rank", [((12, 10, 8), 4), ((8, 6, 5, 7), 3)])
@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_fused_sweep_is_gauss_seidel_exact(dims, rank, backend):
    """One fused sweep == one per-mode sweep with the SAME update closure:
    every mode's MTTKRP sees exactly the factors sequential GS would."""
    x, fs0 = _mk(dims, rank, seed=4)
    ctx = ExecutionContext.create(backend=backend, interpret=True)

    ref = [f for f in fs0]
    upd = _als_update_closure(ref, rank)
    for it in range(2):
        for mode in range(len(dims)):
            ref[mode] = upd(mode, mttkrp(x, ref, mode, ctx=ctx))

    fused = [f for f in fs0]
    upd2 = _als_update_closure(fused, rank)
    for it in range(2):
        fused_als_sweep(x, fused, upd2, ctx=ctx)

    for k in range(len(dims)):
        np.testing.assert_allclose(
            np.asarray(fused[k]), np.asarray(ref[k]), rtol=1e-3, atol=1e-4
        )


def test_fused_sweep_matrix_fallback():
    """ndim < 3 falls back to the per-mode chain (nothing to reuse)."""
    x, fs0 = _mk((12, 9), 3, seed=5)
    ctx = ExecutionContext.create(backend="einsum")
    ref = [f for f in fs0]
    upd = _als_update_closure(ref, 3)
    for mode in range(2):
        ref[mode] = upd(mode, mttkrp(x, ref, mode, ctx=ctx))
    fused = [f for f in fs0]
    fused_als_sweep(x, fused, _als_update_closure(fused, 3), ctx=ctx)
    for k in range(2):
        np.testing.assert_allclose(
            np.asarray(fused[k]), np.asarray(ref[k]), rtol=1e-4, atol=1e-5
        )


# ---------------------------------------------------------------------------
# Sweep planner: the mode-reuse working set fits the budget
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("budget", [1 << 15, 1 << 17, 1 << 20])
def test_choose_sweep_blocks_fits_budget(budget):
    shape, rank = (64, 48, 96), 16
    mem = Memory(budget_bytes=budget)
    plan = choose_sweep_blocks(shape, rank, 4, memory=mem)
    ws = fused_pair_working_set_words(plan) * 4
    assert ws <= budget, (ws, budget, plan)
    # and the plan still tiles the (padded) problem
    for s, b in zip(plan.padded_shape(shape)[1:], plan.block_contract):
        assert s % b == 0


def test_fused_working_set_exceeds_single_mode():
    """The pair kernel keeps BOTH accumulators resident, so its working
    set strictly contains the single-MTTKRP one (the planner must budget
    for the P' tile too)."""
    from repro.engine.plan import choose_blocks

    shape, rank = (64, 48, 96), 16
    plan = choose_blocks(shape, rank, 4)
    assert fused_pair_working_set_words(plan) > plan.working_set_words()


# ---------------------------------------------------------------------------
# The cp_als sweep= knob
# ---------------------------------------------------------------------------

def test_cp_als_fused_matches_per_mode():
    dims, rank = (16, 14, 12), 4
    # data key 17: under JAX's partitionable threefry stream the tensor
    # from key 6 traps ALS from this init in a swamp (fit 0.74 after 60
    # sweeps, in float64 numpy too)
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(17), dims, rank)
    key = jax.random.PRNGKey(7)
    per = cp_als(x, rank, n_iters=10, key=key, sweep="per_mode")
    fus = cp_als(x, rank, n_iters=10, key=key, sweep="fused")
    for fp, ff in zip(per.fits, fus.fits):
        assert abs(fp - ff) < 1e-3, (per.fits, fus.fits)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(fus.factors[k]), np.asarray(per.factors[k]),
            rtol=2e-3, atol=2e-4,
        )
    assert fus.final_fit > 0.999


def test_cp_als_sweep_knob_validation():
    x, _ = _mk((8, 8, 8), 3)
    with pytest.raises(ValueError, match="unknown sweep"):
        cp_als(x, 3, n_iters=1, sweep="bogus")
    ctx = ExecutionContext.create(distributed=True, procs=1)
    with pytest.raises(ValueError, match="distributed"):
        cp_als(x, 3, n_iters=1, sweep="fused", ctx=ctx)


# ---------------------------------------------------------------------------
# kind="sweep" tune-cache keys
# ---------------------------------------------------------------------------

def test_tune_sweep_persists_and_resolves():
    dims, rank = (24, 20, 16), 6
    x, _ = _mk(dims, rank, seed=10)
    mem = Memory.tpu_vmem(itemsize=x.dtype.itemsize)
    with isolated_cache() as path:
        cache = PlanCache(path)
        res = tune_sweep(x, rank, cache=cache, metric="traffic")
        assert res.winner.variant in ("fused", "per_mode")
        assert not res.cache_hit
        key = cache_key(dims, rank, -1, x.dtype, mem, kind="sweep")
        assert cache.get(key) is not None
        # second call is a cache hit (no re-measure): same resolution
        res2 = tune_sweep(x, rank, cache=cache, metric="traffic")
        assert res2.cache_hit and res2.winner.variant == res.winner.variant
        hit = resolve_sweep(dims, rank, x.dtype, cache=cache)
        assert hit.variant == res.winner.variant and hit.cache_hit
    # traffic model prefers fused for N>=3 (2 passes vs N)
    assert res.winner.variant == "fused"


def test_resolve_sweep_miss_defaults():
    with isolated_cache() as path:
        cache = PlanCache(path)
        miss = resolve_sweep((16, 16, 16), 4, jnp.float32, cache=cache)
        assert miss.variant == "fused" and not miss.cache_hit
        miss2 = resolve_sweep((16, 16), 4, jnp.float32, cache=cache)
        assert miss2.variant == "per_mode"


def test_cp_als_sweep_auto_converges():
    dims, rank = (16, 12, 10), 3
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(11), dims, rank)
    with isolated_cache():
        res = cp_als(x, rank, n_iters=15, key=jax.random.PRNGKey(12),
                     sweep="auto")
    assert res.final_fit > 0.999
