"""Autotuning subsystem: plan cache, empirical search, ``backend="auto"``,
calibration, and the ``choose_blocks`` degenerate-input regressions.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import ExecutionContext, execute
from repro.engine.plan import BlockPlan, Memory, choose_blocks
from repro.kernels.ref import mttkrp_ref
from repro.tune.cache import (
    SCHEMA_VERSION,
    CacheEntry,
    PlanCache,
    cache_key,
    plan_from_dict,
    plan_to_dict,
)
from repro.tune.calibrate import calibrate, calibration_report
from repro.tune.search import (
    generate_candidates,
    resolve,
    search,
    tune_mttkrp,
)

# empirical searches + interpret-mode kernel measurement are slow on CPU
pytestmark = pytest.mark.slow


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    """Isolated plan cache for everything that goes through default_cache."""
    path = str(tmp_path / "plans.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    return path


def _problem(dims=(16, 12, 8), rank=4, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, *kf = jax.random.split(key, len(dims) + 1)
    x = jax.random.normal(kx, dims, jnp.float32)
    fs = [
        jax.random.normal(k, (d, rank), jnp.float32)
        for k, d in zip(kf, dims)
    ]
    return x, fs


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_roundtrip_exact():
    plan = BlockPlan(24, (8, 120), 40, x_has_rank=True)
    assert plan_from_dict(plan_to_dict(plan)) == plan


def test_cache_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "c.json")
    plan = BlockPlan(16, (8, 128), 64)
    key = cache_key((16, 12, 8), 4, 0, jnp.float32, Memory.tpu_vmem())
    c1 = PlanCache(path)
    c1.put(key, CacheEntry("pallas", plan_to_dict(plan), variant="generic",
                           score=12.5, walltime_us=12.5))
    c2 = PlanCache(path)  # fresh instance: must read from disk
    entry = c2.get(key)
    assert entry is not None
    assert entry.backend == "pallas"
    assert entry.variant == "generic"
    assert entry.to_plan() == plan  # exact BlockPlan reproduction


def test_cache_key_invalidation_on_memory_and_dtype():
    base = cache_key((16, 12, 8), 4, 0, jnp.float32, Memory.tpu_vmem())
    other_mem = cache_key(
        (16, 12, 8), 4, 0, jnp.float32,
        Memory.tpu_vmem(budget_bytes=1 << 20),
    )
    other_dtype = cache_key((16, 12, 8), 4, 0, jnp.bfloat16, Memory.tpu_vmem())
    other_kind = cache_key(
        (16, 12, 8), 4, 0, jnp.float32, Memory.tpu_vmem(), kind="partial"
    )
    assert len({base, other_mem, other_dtype, other_kind}) == 4


def test_cache_schema_version_invalidates(tmp_path):
    path = str(tmp_path / "c.json")
    c1 = PlanCache(path)
    c1.put("k", CacheEntry("einsum"))
    raw = json.load(open(path))
    raw["schema"] = SCHEMA_VERSION + 1
    json.dump(raw, open(path, "w"))
    c2 = PlanCache(path)
    assert c2.get("k") is None  # whole file invalidated
    assert len(c2) == 0
    c2.put("k2", CacheEntry("einsum"))  # and it can re-persist cleanly
    assert PlanCache(path).get("k2") is not None


@pytest.mark.parametrize(
    "content", [b"not json{{{", b"", b'{"schema": 1, "entries": 42}',
                b'[1, 2, 3]']
)
def test_cache_corrupted_file_recovers(tmp_path, content):
    path = str(tmp_path / "c.json")
    with open(path, "wb") as f:
        f.write(content)
    c = PlanCache(path)
    assert len(c) == 0  # never crashes
    c.put("k", CacheEntry("einsum"))
    assert PlanCache(path).get("k").backend == "einsum"


def test_corrupted_cache_falls_back_to_analytic(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        f.write("garbage")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    x, fs = _problem()
    out = execute.mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="auto"))  # must not raise
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


# ---------------------------------------------------------------------------
# choose_blocks degenerate inputs (regression pins)
# ---------------------------------------------------------------------------

def test_choose_blocks_size1_output_mode_not_padded():
    plan = choose_blocks((1, 64, 64), 16)
    assert plan.block_i == 1  # not padded to a sublane tile
    assert plan.padded_shape((1, 64, 64))[0] == 1


def test_choose_blocks_size1_contract_mode_not_padded():
    plan = choose_blocks((64, 1, 64), 16)
    assert plan.block_contract[0] == 1
    assert plan.padded_shape((64, 1, 64))[1] == 1


def test_choose_blocks_small_rank_not_padded_to_lane():
    plan = choose_blocks((64, 64, 64), 4)
    assert plan.block_r == 4  # rank below the lane width: full extent
    ws_small = plan.working_set_words()
    ws_padded = BlockPlan(
        plan.block_i, plan.block_contract, 128
    ).working_set_words()
    assert ws_small < ws_padded  # no phantom 32x factor traffic


def test_choose_blocks_aligned_when_extent_allows():
    plan = choose_blocks((512, 512, 512), 256)
    assert plan.block_i % 8 == 0
    assert plan.block_r % 128 == 0
    assert plan.block_contract[-1] % 128 == 0


def test_choose_blocks_tiny_budget_still_feasible():
    """Before the fix the shrink loop bottomed out at alignment floors and
    returned Eq-9-infeasible plans for small memories."""
    mem = Memory.tpu_vmem(budget_bytes=32 * 1024)
    plan = choose_blocks((512, 512, 512), 256, memory=mem)
    assert plan.fits(mem)


@pytest.mark.parametrize("dims,rank", [((1, 32, 24), 4), ((24, 1, 16), 3),
                                       ((16, 12, 1), 5), ((1, 1, 8, 8), 2)])
def test_degenerate_plans_run_correctly(dims, rank):
    """Kernel correctness with the unpadded degenerate plans."""
    x, fs = _problem(dims, rank)
    plan = choose_blocks(dims, rank)
    out = execute.mttkrp(
        x, fs, 0, plan=plan,
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


# ---------------------------------------------------------------------------
# candidate generation + search
# ---------------------------------------------------------------------------

def test_candidates_cover_executors_and_variants():
    cands = generate_candidates((16, 12, 8), 4, Memory.tpu_vmem())
    backends = {c.backend for c in cands}
    assert backends == {"einsum", "blocked_host", "pallas"}
    variants = {c.variant for c in cands if c.backend == "pallas"}
    assert variants == {"specialized", "generic"}  # both 3-way kernels
    plans = {c.plan for c in cands if c.backend == "pallas"}
    assert len(plans) > 1  # perturbed neighborhood, not just the analytic


def test_candidates_4way_generic_only():
    cands = generate_candidates((8, 8, 8, 8), 4, Memory.tpu_vmem())
    variants = {c.variant for c in cands if c.backend == "pallas"}
    assert variants == {"generic"}


def test_search_winner_is_fastest_measured(tuned_env):
    x, fs = _problem()
    res = search(x, fs, 0, interpret=True, reps=1, warmup=0)
    finite = [
        m for m in res.measurements
        if m.ok and np.isfinite(m.walltime_us)
    ]
    assert res.winner == min(finite, key=lambda m: m.walltime_us).candidate


def test_kernel_variant_generic_on_3way_correct():
    x, fs = _problem()
    out = execute.mttkrp(
        x, fs, 0, kernel_variant="generic",
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


# ---------------------------------------------------------------------------
# backend="auto"
# ---------------------------------------------------------------------------

def test_auto_cold_falls_back_to_model_best(tuned_env):
    x, fs = _problem()
    r = resolve(x.shape, 4, 0, x.dtype, None)
    assert not r.cache_hit
    out = execute.mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="auto"))
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


def test_auto_tune_persists_and_replays_exactly(tuned_env):
    x, fs = _problem()
    res = tune_mttkrp(x, fs, 0, interpret=True, reps=1, warmup=0)
    assert not res.cache_hit
    # warm: resolve reproduces the tuned configuration exactly, no search
    r = resolve(x.shape, 4, 0, x.dtype, None)
    assert r.cache_hit
    assert r.backend == res.winner.backend
    assert r.plan == res.winner.plan
    assert r.variant == res.winner.variant
    assert r.block == res.winner.block
    # a second tune call is a pure cache hit
    res2 = tune_mttkrp(x, fs, 0, interpret=True)
    assert res2.cache_hit and res2.winner == res.winner
    # and the entry survives a fresh cache instance reading the same file
    fresh = PlanCache(tuned_env)
    entry = fresh.get(r.key)
    assert entry is not None and entry.backend == res.winner.backend
    assert entry.to_plan() == res.winner.plan
    out = execute.mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="auto"))
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


def test_auto_via_execute_tune_flag(tuned_env):
    x, fs = _problem((12, 10, 8), 3)
    out = execute.mttkrp(
        x, fs, 0, ctx=ExecutionContext.create(backend="auto", tune=True)
    )
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )
    r = resolve((12, 10, 8), 3, 0, x.dtype, None)
    assert r.cache_hit  # the tune flag persisted the winner


def test_auto_is_trace_safe(tuned_env):
    """resolve() under jit: static shapes only, no measurement attempted."""
    x, fs = _problem()

    @jax.jit
    def f(x, fs):
        return execute.mttkrp(
            x, tuple(fs), 0, ctx=ExecutionContext.create(backend="auto", tune=True)
        )

    np.testing.assert_allclose(
        f(x, fs), mttkrp_ref(x, fs, 0), rtol=5e-4, atol=5e-4
    )


def test_auto_in_dimtree_and_cp_als(tuned_env):
    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_low_rank_tensor
    from repro.engine.tree import all_mode_mttkrp

    x, fs = _problem()
    outs = all_mode_mttkrp(
        x, fs, method="dimtree", ctx=ExecutionContext.create(backend="auto")
    )
    for m, b in enumerate(outs):
        np.testing.assert_allclose(
            b, mttkrp_ref(x, fs, m), rtol=5e-4, atol=5e-4
        )
    xt, _ = random_low_rank_tensor(jax.random.PRNGKey(0), (12, 10, 8), 3)
    res = cp_als(
        xt, 3, n_iters=8, ctx=ExecutionContext.create(backend="auto", tune=True)
    )
    assert res.final_fit > 0.8


def test_cache_key_includes_platform():
    """Winners are platform-specific: a CPU-tuned entry must never be
    replayed on TPU (and vice versa)."""
    key = cache_key((16, 12, 8), 4, 0, jnp.float32, Memory.tpu_vmem())
    assert f"platform={jax.default_backend()}" in key


def test_traffic_metric_scores_are_modeled_bytes(tuned_env):
    x, fs = _problem()
    res = search(x, fs, 0, metric="traffic", interpret=True, reps=1,
                 warmup=0)
    for m in res.measurements:
        if m.candidate.backend == "pallas":
            assert m.score == float(m.modeled_bytes)
        elif np.isfinite(m.walltime_us):
            assert m.score == m.walltime_us


def test_tune_partial_persists_and_replays(tuned_env):
    from repro.tune.search import tune_partial

    x, fs = _problem((12, 10, 8), 3)
    res = tune_partial(x, fs, (0, 1, 2), (1, 2), False, interpret=True,
                       reps=1, warmup=0)
    assert not res.cache_hit
    assert res.key.startswith("partial|")
    res2 = tune_partial(x, fs, (0, 1, 2), (1, 2), False, interpret=True)
    assert res2.cache_hit and res2.winner == res.winner


def test_dimtree_auto_tune_writes_partial_entries(tuned_env):
    """cp_als(sweep="dimtree") under an "auto" + tune context must
    actually tune the tree edges, not silently cache nothing."""
    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_low_rank_tensor

    xt, _ = random_low_rank_tensor(jax.random.PRNGKey(0), (12, 10, 8), 3)
    res = cp_als(xt, 3, n_iters=4, sweep="dimtree",
                 ctx=ExecutionContext.create(backend="auto", tune=True))
    assert res.final_fit > 0.8
    partial_keys = [
        k for k in PlanCache(tuned_env).keys() if k.startswith("partial|")
    ]
    assert partial_keys  # the sweep persisted tuned tree edges
    # and the warm sweep replays them (resolve hits, same fit path)
    res2 = cp_als(xt, 3, n_iters=4, sweep="dimtree",
                  ctx=ExecutionContext.create(backend="auto"))
    assert res2.final_fit == pytest.approx(res.final_fit, abs=1e-6)


def test_unknown_backend_message_mentions_auto():
    x, fs = _problem()
    with pytest.raises(ValueError, match="auto"):
        execute.mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="nope"))


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def test_calibrate_requires_three_shapes(tuned_env):
    with pytest.raises(ValueError):
        calibrate([((8, 8, 8), 2)], persist=False)


def test_calibration_reports_model_vs_measured(tuned_env):
    cases = (((24, 20, 16), 4), ((32, 24, 16), 8), ((20, 16, 12, 8), 4))
    cal = calibrate(cases, reps=1)
    assert len(cal.rows) >= 3
    for r in cal.rows:
        assert r.model_bytes > 0 and r.measured_bytes > 0
        assert np.isfinite(r.traffic_rel_err)
        assert np.isfinite(r.predicted_us)
    report = calibration_report(cal)
    assert report.count("\n") >= 4  # header + fit + one line per shape
    assert "traffic_err" in report
    # persisted: a fresh cache instance can reload the coefficients
    from repro.tune.calibrate import load_calibration

    loaded = load_calibration(PlanCache(tuned_env))
    assert loaded is not None
    assert loaded.bandwidth_bytes_per_us == cal.bandwidth_bytes_per_us
    assert len(loaded.rows) == len(cal.rows)
