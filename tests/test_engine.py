"""The unified MTTKRP execution engine: planner single-sourcing, Eq-10
regression, backend dispatch, kernel-backed dimension trees, and the exact
dimension-tree cost model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bounds
from repro.core.cp_als import cp_als
from repro.core.dimension_tree import (
    dimtree_flops,
    dimtree_intermediate_words,
    naive_all_mode_flops,
)
from repro.core.mttkrp import mttkrp as einsum_mttkrp
from repro.core.mttkrp import mttkrp_naive
from repro.engine import (
    BlockPlan,
    ExecutionContext,
    Memory,
    all_mode_mttkrp,
    best_uniform_block,
    choose_blocks,
    dimtree_als_sweep,
    mttkrp,
)
from repro.engine.plan import uniform_plan
from repro.kernels.ref import mttkrp_ref
from repro.observe.metrics import (
    PALLAS_DISPATCHES,
    TENSOR_RELAYOUTS,
    registry,
)


def _dispatches() -> int:
    return registry().counter(PALLAS_DISPATCHES)


def _mk(dims, rank, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    kx, *kf = jax.random.split(key, len(dims) + 1)
    x = jax.random.normal(kx, dims, dtype)
    fs = [jax.random.normal(k, (d, rank), dtype) for k, d in zip(kf, dims)]
    return x, fs


# --------------------------------------------------------------------------
# planner: single source of truth + Eq-10 regression
# --------------------------------------------------------------------------

def test_planner_is_single_sourced():
    """kernels.ops and repro.kernels re-export the engine planner objects —
    the logic exists in exactly one module."""
    from repro.engine import plan as engine_plan
    from repro.kernels import ops as kernel_ops

    assert kernel_ops.BlockPlan is engine_plan.BlockPlan
    assert kernel_ops.choose_blocks is engine_plan.choose_blocks
    assert kernel_ops.mttkrp_traffic_model is engine_plan.mttkrp_traffic_model


@pytest.mark.parametrize(
    "dims,rank,mem",
    [((24, 24, 24), 16, 512), ((16, 32, 64), 8, 1024), ((12, 12, 12, 12), 6, 4096)],
)
def test_eq10_regression_pins_bounds_formula(dims, rank, mem):
    """Satellite fix: a uniform-b plan's eq10 traffic must equal
    core.bounds.seq_blocked_cost exactly (the old model multiplied the
    block-count product by max(block) instead of summing per-mode factor
    traffic R*(N+1)*b)."""
    b = best_uniform_block(dims, Memory.abstract(mem))
    plan = BlockPlan(b, (b,) * (len(dims) - 1), rank)
    assert plan.eq10_words(dims, rank) == int(
        bounds.seq_blocked_cost(dims, rank, b)
    )
    # and the dict spelling agrees, in bytes
    m = plan.traffic_model(dims, rank, itemsize=4)
    assert m["eq10_bytes"] == plan.eq10_words(dims, rank) * 4
    # uniform_plan asserts the same identity internally
    uniform_plan(dims, rank, Memory.abstract(mem))


def test_eq10_heterogeneous_blocks_formula():
    """For per-mode blocks the generalized Eq-10 is I + prod ceil(I_k/b_k)
    * R * (sum_k b_k + b_out): factor loads per rank column plus output
    load+store."""
    dims, rank = (64, 32, 48), 4
    plan = BlockPlan(16, (8, 24), rank)
    nblocks = 4 * 4 * 2
    expect = 64 * 32 * 48 + nblocks * rank * ((16 + 8 + 24) + 16)
    assert plan.eq10_words(dims, rank) == expect


def test_memory_descriptor_drives_planning():
    """choose_blocks against a small explicit Memory must shrink blocks and
    still satisfy the Eq-9 working-set check for that memory."""
    small = Memory.tpu_vmem(budget_bytes=1024 * 1024)
    big = Memory.tpu_vmem()
    p_small = choose_blocks((512, 512, 512), 256, memory=small)
    p_big = choose_blocks((512, 512, 512), 256, memory=big)
    assert p_small.fits(small)
    assert p_small.working_set_words() < p_big.working_set_words()


def test_rank_augmented_working_set():
    """x_has_rank plans charge the tensor tile at bi*prod(bc)*br words."""
    plain = BlockPlan(8, (8,), 128)
    aug = BlockPlan(8, (8,), 128, x_has_rank=True)
    assert aug.working_set_words() - plain.working_set_words() == 8 * 8 * 127


# --------------------------------------------------------------------------
# executor: backends agree
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(8, 7, 9), (6, 5, 4, 3)])
@pytest.mark.parametrize("backend", ["einsum", "blocked_host", "pallas"])
def test_backends_agree(dims, backend):
    x, fs = _mk(dims, 4, seed=1)
    for mode in range(len(dims)):
        out = mttkrp(
            x, fs, mode,
            ctx=ExecutionContext.create(backend=backend, interpret=True),
        )
        np.testing.assert_allclose(
            out, mttkrp_ref(x, fs, mode), rtol=3e-4, atol=3e-4
        )


def test_unknown_backend_rejected():
    x, fs = _mk((4, 4, 4), 2)
    with pytest.raises(ValueError):
        mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="cuda"))


# --------------------------------------------------------------------------
# kernels: 4-way / 5-way + padding (satellite coverage)
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "dims", [(8, 8, 8, 8), (7, 5, 9, 3), (4, 4, 4, 4, 4), (5, 3, 4, 2, 3)]
)
def test_mttkrpn_4way_5way_vs_naive(dims):
    """4-/5-way kernel (interpret mode) vs the atomic-multiply oracle,
    including non-divisible shapes that exercise the padding path."""
    x, fs = _mk(dims, 5, seed=2)
    for mode in range(len(dims)):
        out = mttkrp(
            x, fs, mode, ctx=ExecutionContext.create(backend="pallas", interpret=True)
        )
        ref = mttkrp_naive(x, fs, mode)
        np.testing.assert_allclose(out, ref, rtol=5e-4, atol=5e-4)


def test_explicit_plan_padding_path():
    """Blocks larger than (and non-divisible into) the dims force padding
    everywhere; zero padding must not pollute real outputs."""
    dims = (10, 6, 11, 3)
    x, fs = _mk(dims, 7, seed=3)
    plan = BlockPlan(8, (8, 128, 8), 128)
    out = mttkrp(
        x, fs, 2, plan=plan,
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, 2), rtol=5e-4, atol=5e-4
    )


@pytest.mark.parametrize("dims,labels", [
    ((16, 16, 16), ["none", "none", "none"]),       # aligned: in place
    ((16, 12, 20), ["pad", "pad", "pad"]),          # off the blocks: pads
    ((8, 6, 5, 4), ["none", "transpose", "transpose", "transpose"]),
])
def test_tensor_relayouts_count_what_reaches_x(dims, labels):
    """``engine.tensor_relayouts`` counts the tensor-sized transposes and
    pads of a cp_als sweep, and each ``mttkrp`` event names them: none
    for an aligned 3-way tensor (every mode reads X in place), one pad a
    mode for an unaligned one, a transpose a mode for a 4-way tensor's
    modes 1..3 (the generic kernel reads a mode-first copy)."""
    from repro.observe import Trace

    x, _ = _mk(dims, 3, seed=6)
    ctx = ExecutionContext.create(
        backend="pallas", interpret=True, observe=True
    )
    before = registry().snapshot()
    with Trace() as tr:
        cp_als(x, 3, n_iters=1, ctx=ctx, sweep="per_mode")
    counted = registry().delta(before).get(TENSOR_RELAYOUTS, 0)
    events = [e for e in tr.events if e["kind"] == "mttkrp"]
    assert [e["relayout"] for e in events] == labels
    assert counted == sum(l.count("+") + 1 for l in labels if l != "none")


# --------------------------------------------------------------------------
# kernel-backed dimension tree
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(8, 7, 9), (6, 5, 4, 3), (4, 5, 3, 4, 3)])
def test_dimtree_pallas_all_modes(dims):
    x, fs = _mk(dims, 4, seed=4)
    before = _dispatches()
    outs = all_mode_mttkrp(
        x, fs, method="dimtree",
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    # every tree edge must have gone through the kernels
    assert _dispatches() - before >= 2 * (len(dims) - 1)
    for mode in range(len(dims)):
        np.testing.assert_allclose(
            outs[mode], mttkrp_ref(x, fs, mode), rtol=5e-4, atol=5e-4
        )


def test_dimtree_pallas_sweep_gauss_seidel_order():
    """The kernel-backed sweep must deliver each mode's MTTKRP computed
    with modes < n already updated (plain-ALS Gauss-Seidel order)."""
    dims = (5, 4, 6, 3)
    x, fs = _mk(dims, 3, seed=5)
    seen = {}

    def update(mode, b):
        seen[mode] = b
        return fs[mode] * 1.1

    fs_tree = [f + 0 for f in fs]
    dimtree_als_sweep(
        x, fs_tree, update,
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    cur = [f + 0 for f in fs]
    for mode in range(len(dims)):
        expected = einsum_mttkrp(x, cur, mode)
        np.testing.assert_allclose(seen[mode], expected, rtol=2e-3, atol=2e-3)
        cur[mode] = cur[mode] * 1.1


def test_cp_als_dimtree_pallas_matches_plain():
    """Acceptance: dimtree ALS through the Pallas backend matches plain ALS
    to fp32 tolerance, and the pallas path is actually taken."""
    x, fs = _mk((8, 7, 6, 5), 2, seed=6)
    x = x / jnp.linalg.norm(x.reshape(-1))
    plain = cp_als(x, 2, n_iters=6, init_factors=fs)
    before = _dispatches()
    tree = cp_als(
        x, 2, n_iters=6, init_factors=fs, sweep="dimtree",
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    assert _dispatches() > before  # kernel path taken
    for a, b in zip(plain.fits, tree.fits):
        assert abs(a - b) < 5e-3
    for fa, fb in zip(plain.factors, tree.factors):
        np.testing.assert_allclose(fa, fb, rtol=5e-3, atol=5e-3)


# --------------------------------------------------------------------------
# exact dimension-tree cost model (satellite fix)
# --------------------------------------------------------------------------

def test_dimtree_flops_exact_small_case():
    """Hand-computed N=3 cubical case: root (d,d,d) -> left child drops 2
    modes (cost d^3*R + d^2*R), right child drops 1 (d^3*R); the right
    child (d,d,R) then drops one mode twice (d^2*R each)."""
    d, r = 8, 4
    expect = (d**3 * r + d**2 * r) + d**3 * r + 2 * (d**2 * r)
    assert dimtree_flops((d, d, d), r) == expect


def test_dimtree_flops_drop_order_optimal():
    """Non-cubical dims: the model must drop the largest mode first (what
    einsum's 'optimal' path does), not average geometrically."""
    dims, r = (4, 100, 2), 3
    # root -> left: drop modes {100, 2}: largest first: 800R + 8R
    # root -> right: drop {4}: 800R ; right child (100, 2, R):
    #   drop {2}: 200R -> leaf (100, R); drop {100}: 200R -> leaf (2, R)
    expect = (800 + 8) * r + 800 * r + 200 * r + 200 * r
    assert dimtree_flops(dims, r) == expect


def test_dimtree_flops_beats_naive_and_is_exactish():
    for dims, rank in [((32, 32, 32), 8), ((16, 16, 16, 16), 4)]:
        tree = dimtree_flops(dims, rank)
        naive = naive_all_mode_flops(dims, rank)
        assert tree < naive
        # reuse ratio must be >= ~2 for these shapes
        assert naive / tree > 2.0


def test_dimtree_intermediate_words_counts_rank_axis():
    """Rank-augmented nodes hold prod(dims)*R words (the quantity the old
    geometric-mean model under-counted)."""
    d, r = 8, 4
    # root d^3 + two children d^2*R wait: N=3 children: left (d,) leaf? tree:
    # root (d,d,d): 1*d^3; left child (d,)*R; right child (d,d)*R; right's
    # leaves (d,)*R and (d,)*R
    expect = d**3 + d * r + d * d * r + d * r + d * r
    assert dimtree_intermediate_words((d, d, d), r) == expect


# --------------------------------------------------------------------------
# simulator + engine planner agree
# --------------------------------------------------------------------------

def test_simulator_uses_engine_block_selection(rng):
    from repro.core.simulator import simulate_blocked

    x = rng.standard_normal((6, 5, 4))
    fs = [rng.standard_normal((d, 3)) for d in x.shape]
    mem = 64
    b_engine = best_uniform_block(x.shape, Memory.abstract(mem))
    res = simulate_blocked(x, fs, 0, mem)
    assert res.words <= bounds.seq_blocked_cost(x.shape, 3, b_engine) + 1
