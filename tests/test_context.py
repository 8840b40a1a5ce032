"""ExecutionContext: eager validation, immutability, JSON round-trips, and
decision replay (two drivers given the same context resolve identical
plans — pallas dispatch counts included)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import BlockPlan, Distribution, ExecutionContext, Memory
from repro.engine.context import PlanDecision, ProblemSpec
from repro.observe.metrics import PALLAS_DISPATCHES, registry
from repro.tune.cache import isolated_cache


def _dispatches() -> int:
    """Current pallas dispatch counter (the migrated global: bracket
    reads with before/after instead of resetting anything)."""
    return registry().counter(PALLAS_DISPATCHES)


@pytest.fixture()
def tuned_env():
    """Redirect the plan cache so context tests never touch the user's."""
    with isolated_cache() as path:
        yield path


def _problem(dims=(8, 6, 5), rank=3, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), dims)
    fs = [
        jax.random.normal(jax.random.PRNGKey(seed + k + 1), (d, rank))
        for k, d in enumerate(dims)
    ]
    return x, fs


# ---------------------------------------------------------------------------
# frozen-ness / hashability
# ---------------------------------------------------------------------------

def test_context_is_frozen():
    ctx = ExecutionContext.create(backend="pallas")
    with pytest.raises(dataclasses.FrozenInstanceError):
        ctx.backend = "einsum"


def test_context_is_hashable_and_value_equal():
    a = ExecutionContext.create(
        backend="pallas", memory=Memory.abstract(4096), interpret=True
    )
    b = ExecutionContext.create(
        backend="pallas", memory=Memory.abstract(4096), interpret=True
    )
    assert a == b and hash(a) == hash(b)
    assert a != ExecutionContext.create(backend="einsum")
    assert len({a, b}) == 1  # usable as a dict/set key (e.g. program cache)


def test_mesh_is_excluded_from_identity():
    # a mesh is a process-local device handle, not part of the value
    d1 = Distribution(grid=(2, 2, 2), mesh=None)
    d2 = Distribution(grid=(2, 2, 2), mesh=object())
    assert d1 == d2 and hash(d1) == hash(d2)


# ---------------------------------------------------------------------------
# eager validation (the single catalog)
# ---------------------------------------------------------------------------

def test_invalid_backend_rejected_at_construction():
    with pytest.raises(ValueError, match="unknown backend"):
        ExecutionContext.create(backend="gpu-magic")
    with pytest.raises(ValueError, match="unknown backend"):
        ExecutionContext(backend="gpu-magic")  # direct construction too


def test_tune_requires_auto_backend():
    with pytest.raises(ValueError, match="tune=True requires"):
        ExecutionContext.create(backend="einsum", tune=True)
    ExecutionContext.create(backend="auto", tune=True)  # fine


def test_tune_distributed_conflict_rejected_eagerly():
    with pytest.raises(ValueError, match="tune=True is not supported"):
        ExecutionContext.create(
            backend="auto", tune=True, distributed=True
        )


def test_bad_memory_type_rejected():
    with pytest.raises(ValueError, match="Memory"):
        ExecutionContext.create(memory=4096)


def test_bad_grid_rejected_eagerly():
    with pytest.raises(ValueError, match="positive ints"):
        ExecutionContext.create(grid=(0, 2))


def test_grid_extent_mismatch_rejected_at_resolution():
    with pytest.raises(ValueError, match="does not divide tensor extent"):
        ExecutionContext.for_problem((9, 8, 8), 2, grid=(2, 2, 2))


def test_infeasible_memory_rejected_at_resolution():
    # 3-word fast memory cannot hold any Eq-9 working set for a 3-way MTTKRP
    with pytest.raises(ValueError, match="Eq-9"):
        ExecutionContext.for_problem(
            (64, 64, 64), 64, backend="pallas", memory=Memory.abstract(3)
        )


def test_bad_out_dtype_rejected():
    with pytest.raises(ValueError, match="dtype"):
        ExecutionContext.create(out_dtype="notadtype")


# ---------------------------------------------------------------------------
# JSON round-trips
# ---------------------------------------------------------------------------

def test_roundtrip_plain():
    ctx = ExecutionContext.create(backend="einsum")
    assert ExecutionContext.from_json(ctx.to_json()) == ctx


def test_roundtrip_full_fields(tuned_env):
    ctx = ExecutionContext.for_problem(
        (8, 6, 5), 3, dtype=jnp.float32,
        backend="auto", memory=Memory.tpu_vmem(itemsize=4),
        out_dtype="float32", interpret=True,
        grid=None, distributed=True, procs=4, overlap="ring",
    )
    back = ExecutionContext.from_json(ctx.to_json())
    assert back == ctx and hash(back) == hash(ctx)
    # field-level: Memory, grid, dtype policy, decisions all survive
    assert back.memory == ctx.memory
    assert back.distribution.grid == ctx.distribution.grid
    assert back.distribution.overlap == "ring"
    assert back.out_dtype == "float32"
    assert back.problem == ProblemSpec((8, 6, 5), 3, "float32")
    assert back.decisions == ctx.decisions


def test_roundtrip_preserves_blockplan_exactly():
    plan = BlockPlan(16, (8, 128), 128, x_has_rank=True)
    ctx = ExecutionContext(
        backend="auto",
        problem=ProblemSpec((8, 6, 5), 3),
        decisions=(PlanDecision(0, "pallas", plan, "generic", None, True),),
    )
    back = ExecutionContext.from_json(ctx.to_json())
    assert back.decisions[0].plan == plan
    assert back.decisions[0].variant == "generic"
    assert back.decisions[0].cache_hit is True


def test_json_is_schema_versioned():
    d = json.loads(ExecutionContext.create().to_json())
    assert d["schema"] == "repro.ExecutionContext/1"
    d["schema"] = "repro.ExecutionContext/999"
    with pytest.raises(ValueError, match="schema"):
        ExecutionContext.from_dict(d)


def test_save_load_and_env_seed(tmp_path, monkeypatch):
    ctx = ExecutionContext.create(
        backend="pallas", memory=Memory.abstract(2048), interpret=True
    )
    p = tmp_path / "ctx.json"
    ctx.save(str(p))
    assert ExecutionContext.load(str(p)) == ctx
    # REPRO_CONTEXT as a file path seeds the default context ...
    monkeypatch.setenv("REPRO_CONTEXT", str(p))
    assert ExecutionContext.default() == ctx
    # ... and as inline JSON
    monkeypatch.setenv("REPRO_CONTEXT", ctx.to_json())
    assert ExecutionContext.default() == ctx
    monkeypatch.delenv("REPRO_CONTEXT")
    assert ExecutionContext.default() == ExecutionContext()


def test_env_seed_reaches_drivers(tmp_path, monkeypatch):
    """A REPRO_CONTEXT seed changes what a bare driver call runs."""
    x, fs = _problem()
    ctx = ExecutionContext.create(backend="pallas", interpret=True)
    p = tmp_path / "ctx.json"
    ctx.save(str(p))
    monkeypatch.setenv("REPRO_CONTEXT", str(p))
    before = _dispatches()
    out = repro.mttkrp(x, fs, 0)  # no ctx, no kwargs — seeded from env
    after = _dispatches()
    assert after == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(repro.mttkrp(x, fs, 0, ctx=ExecutionContext())),
        rtol=2e-4, atol=2e-4,
    )


# ---------------------------------------------------------------------------
# decision replay: same context -> byte-identical plan resolution
# ---------------------------------------------------------------------------

def test_for_problem_pins_auto_decisions(tuned_env):
    ctx = ExecutionContext.for_problem((8, 6, 5), 3, backend="auto")
    assert ctx.problem == ProblemSpec((8, 6, 5), 3, "float32")
    assert [d.mode for d in ctx.decisions] == [0, 1, 2]
    # the pinned decision is what decision_for replays — for this problem
    assert ctx.decision_for((8, 6, 5), 3, 1) == ctx.decisions[1]
    assert ctx.decision_for((9, 6, 5), 3, 1) is None  # different problem


def test_same_context_same_plans_and_dispatch_counts(tuned_env):
    """Two drivers handed the same (round-tripped) context produce
    byte-identical plan resolutions: the tuned pallas plan replays in
    both, with matching kernel dispatch counts."""
    dims, rank = (16, 8, 128), 4
    x, fs = _problem(dims, rank)
    # seed the cache with a pinned pallas winner for this problem
    from repro.tune.cache import CacheEntry, cache_key, default_cache, \
        plan_to_dict

    plan = BlockPlan(8, (8, 128), 128)
    mem = Memory.tpu_vmem(itemsize=4)
    cache = default_cache()
    for mode in range(3):
        perm = (dims[mode],) + tuple(
            s for k, s in enumerate(dims) if k != mode
        )
        cache.put(
            cache_key(perm, rank, mode, jnp.float32, mem),
            CacheEntry(
                backend="pallas", plan=plan_to_dict(plan),
                variant="generic",
            ),
        )
    ctx = ExecutionContext.for_problem(
        dims, rank, backend="auto", interpret=True
    )
    assert all(d.backend == "pallas" and d.cache_hit for d in ctx.decisions)
    assert all(d.plan == plan for d in ctx.decisions)
    ctx2 = ExecutionContext.from_json(ctx.to_json())
    assert ctx2.decisions == ctx.decisions

    def run(c):
        before = _dispatches()
        res = repro.cp_als(
            x, rank, n_iters=2, key=jax.random.PRNGKey(7), ctx=c
        )
        return _dispatches() - before, res

    n1, r1 = run(ctx)
    n2, r2 = run(ctx2)
    assert n1 == n2 and n1 == 2 * 3  # every sweep: one kernel per mode
    for f1, f2 in zip(r1.fits, r2.fits):
        assert f1 == f2
    # the replay does not depend on the cache anymore: clear it, rerun
    cache.clear()
    n3, r3 = run(ctx2)
    assert n3 == n1 and r3.fits == r1.fits


def test_decisions_replay_without_reresolving(tuned_env):
    """A context pinned by for_problem replays its decision even when the
    live cache would now say something else (the point: drivers replay,
    never re-derive)."""
    dims, rank = (8, 6, 5), 3
    x, fs = _problem(dims, rank)
    ctx = ExecutionContext.for_problem(
        dims, rank, backend="auto", interpret=True
    )
    # on CPU the miss path resolves to einsum for every mode
    assert all(d.backend == "einsum" for d in ctx.decisions)
    before = _dispatches()
    repro.mttkrp(x, fs, 0, ctx=ctx)
    assert _dispatches() == before  # replayed einsum, no kernel


def test_for_problem_with_tune_leaves_decisions_unpinned(tuned_env):
    """tune=True must NOT pin model-best decisions (that would silently
    skip the search forever): the first concrete call runs the empirical
    search and persists, later resolution replays the cache."""
    from repro.tune.cache import default_cache

    dims, rank = (8, 6, 5), 2
    x, fs = _problem(dims, rank)
    ctx = ExecutionContext.for_problem(
        dims, rank, backend="auto", tune=True, interpret=True
    )
    assert ctx.decisions == ()  # unpinned: the live path must tune
    assert len(default_cache()) == 0
    repro.mttkrp(x, fs, 0, ctx=ctx)  # first concrete call searches
    assert len(default_cache()) == 1  # ... and persisted a winner


def test_decision_replay_is_dtype_keyed(tuned_env):
    """A context resolved for float32 must not replay its plans on
    float64 data (the Eq-9 working set doubles)."""
    dims, rank = (8, 6, 5), 3
    ctx = ExecutionContext.for_problem(dims, rank, backend="auto")
    assert ctx.decision_for(dims, rank, 0, jnp.float32) is not None
    assert ctx.decision_for(dims, rank, 0, jnp.float64) is None


def test_plan_decision_rejects_unresolved_backend():
    """A decision is a RESOLVED choice; 'auto' (e.g. from a hand-edited
    context file) must fail loudly, not fall into the kernel path."""
    with pytest.raises(ValueError, match="concrete executor"):
        PlanDecision(0, "auto")
    d = ExecutionContext(
        backend="auto", problem=ProblemSpec((4, 4, 4), 2),
        decisions=(PlanDecision(0, "einsum"),),
    ).to_dict()
    d["decisions"][0]["backend"] = "auto"
    with pytest.raises(ValueError, match="concrete executor"):
        ExecutionContext.from_dict(d)


def test_default_is_memoized(monkeypatch):
    monkeypatch.delenv("REPRO_CONTEXT", raising=False)
    assert ExecutionContext.default() is ExecutionContext.default()
    ctx = ExecutionContext.create(backend="blocked_host")
    monkeypatch.setenv("REPRO_CONTEXT", ctx.to_json())
    seeded = ExecutionContext.default()
    assert seeded == ctx and ExecutionContext.default() is seeded


def test_tree_path_honors_out_dtype_policy():
    """contract_partial applies ctx.out_dtype like the plain path: the
    dimension-tree leaves come out in the policy dtype."""
    from repro.engine.tree import all_mode_mttkrp

    x, fs = _problem()
    ctx = ExecutionContext.create(backend="einsum", out_dtype="float16")
    plain = repro.mttkrp(x, fs, 0, ctx=ctx)
    tree = all_mode_mttkrp(x, fs, method="dimtree", ctx=ctx)
    assert plain.dtype == jnp.float16
    assert all(b.dtype == jnp.float16 for b in tree)


def test_cp_sweep_rejects_rank_axis_context():
    x, _ = _problem((8, 8, 8), 2)
    ctx = ExecutionContext.create(grid=(1, 1, 1), p0=2)
    with pytest.raises(ValueError, match="stationary"):
        repro.cp_als(x, 2, ctx=ctx)


def test_distributed_for_problem_pins_grid_not_plans(tuned_env):
    """Distributed contexts pin the grid but no per-mode plan decisions
    (engine work runs on per-shard shapes, so global-shape decisions
    could never replay)."""
    ctx = ExecutionContext.for_problem(
        (16, 16, 16), 4, backend="auto", distributed=True, procs=8
    )
    assert ctx.distribution.grid == (2, 2, 2)
    assert ctx.decisions == ()


# ---------------------------------------------------------------------------
# distribution resolution
# ---------------------------------------------------------------------------

def test_for_problem_resolves_grid_once():
    ctx = ExecutionContext.for_problem(
        (16, 16, 16), 4, distributed=True, procs=8
    )
    assert ctx.distribution.grid == (2, 2, 2)
    # and the resolution is part of the portable value
    back = ExecutionContext.from_json(ctx.to_json())
    assert back.distribution.grid == (2, 2, 2)


def test_local_view_strips_distribution():
    ctx = ExecutionContext.for_problem(
        (16, 16, 16), 4, backend="pallas", distributed=True, procs=8
    )
    loc = ctx.local()
    assert not loc.is_distributed and loc.backend == "pallas"
    assert ctx.is_distributed  # original untouched (immutable)


def test_build_mesh_requires_distribution():
    with pytest.raises(ValueError, match="non-distributed"):
        ExecutionContext.create().build_mesh()


def test_context_as_program_cache_key():
    """The practical payoff of hashability: contexts key compiled-program
    caches directly."""
    cache = {}
    for _ in range(3):
        c = ExecutionContext.create(
            backend="pallas", memory=Memory.abstract(1 << 14)
        )
        cache.setdefault(c, object())
    assert len(cache) == 1
