"""Multi-device distributed checks, run in a subprocess with
--xla_force_host_platform_device_count=8 (jax locks device count at init, so
the main pytest session, which must see 1 device, cannot run these inline).

Each check prints 'PASS <name>'; the parent test asserts on the transcript.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import make_mesh, shard_map  # noqa: E402
from repro.core.bounds import par_general_cost, par_stationary_cost  # noqa: E402
from repro.core.mttkrp import mttkrp  # noqa: E402
from repro.core.tensor import random_factors, random_tensor  # noqa: E402
from repro.distributed import (  # noqa: E402
    make_grid_mesh,
    mttkrp_general,
    mttkrp_stationary,
    parse_collectives,
    place_inputs,
)
from repro.distributed import (  # noqa: E402
    build_cp_sweep,
    cp_als_parallel,
    place_cp_state,
    stationary_sweep_words,
)
from repro.distributed.compression import (  # noqa: E402
    cp_compressed_mean,
    compression_ratio,
)
from repro.engine.context import ExecutionContext  # noqa: E402


def check_alg3_numerics():
    dims, rank = (8, 16, 24), 8
    x = random_tensor(jax.random.PRNGKey(0), dims)
    fs = random_factors(jax.random.PRNGKey(1), dims, rank)
    mesh = make_grid_mesh((2, 2, 2))
    for mode in range(3):
        f3 = mttkrp_stationary(mesh, mode, 3)
        xs, fl = place_inputs(mesh, x, fs, mode)
        out = f3(xs, *fl)
        ref = mttkrp(x, fs, mode)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5
        )
    print("PASS alg3_numerics")


def check_alg3_asymmetric_grid():
    dims, rank = (16, 8, 8), 4
    x = random_tensor(jax.random.PRNGKey(2), dims)
    fs = random_factors(jax.random.PRNGKey(3), dims, rank)
    mesh = make_grid_mesh((4, 1, 2))
    for mode in range(3):
        f3 = mttkrp_stationary(mesh, mode, 3)
        xs, fl = place_inputs(mesh, x, fs, mode)
        out = f3(xs, *fl)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(mttkrp(x, fs, mode)),
            rtol=1e-4, atol=1e-5,
        )
    print("PASS alg3_asymmetric_grid")


def check_alg4_numerics():
    dims, rank = (8, 16, 24), 8
    x = random_tensor(jax.random.PRNGKey(4), dims)
    fs = random_factors(jax.random.PRNGKey(5), dims, rank)
    for p0, grid in [(2, (2, 2, 1)), (4, (2, 1, 1)), (8, (1, 1, 1))]:
        mesh = make_grid_mesh(grid, p0=p0)
        for mode in range(3):
            f4 = mttkrp_general(mesh, mode, 3)
            xs, fl = place_inputs(mesh, x, fs, mode, rank_axis=True)
            out = f4(xs, *fl)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(mttkrp(x, fs, mode)),
                rtol=1e-4, atol=1e-5,
            )
    print("PASS alg4_numerics")


def check_alg4_4way():
    dims, rank = (4, 8, 4, 8), 4
    x = random_tensor(jax.random.PRNGKey(6), dims)
    fs = random_factors(jax.random.PRNGKey(7), dims, rank)
    mesh = make_grid_mesh((2, 2, 1, 1), p0=2)
    for mode in range(4):
        f4 = mttkrp_general(mesh, mode, 4)
        xs, fl = place_inputs(mesh, x, fs, mode, rank_axis=True)
        out = f4(xs, *fl)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(mttkrp(x, fs, mode)),
            rtol=1e-4, atol=1e-5,
        )
    print("PASS alg4_4way")


def check_comm_matches_eq12():
    """Measured ring bytes from compiled HLO == Eq (12), exactly."""
    dims, rank = (8, 16, 24), 8
    x = random_tensor(jax.random.PRNGKey(0), dims)
    fs = random_factors(jax.random.PRNGKey(1), dims, rank)
    mesh = make_grid_mesh((2, 2, 2))
    for mode in range(3):
        f3 = mttkrp_stationary(mesh, mode, 3)
        xs, fl = place_inputs(mesh, x, fs, mode)
        co = f3.lower(xs, *fl).compile()
        measured = parse_collectives(co.as_text()).ring_bytes
        predicted = par_stationary_cost(dims, rank, (2, 2, 2), mode) * 4
        assert measured == predicted, (mode, measured, predicted)
    print("PASS comm_matches_eq12")


def check_comm_matches_eq16():
    dims, rank = (8, 16, 24), 8
    x = random_tensor(jax.random.PRNGKey(0), dims)
    fs = random_factors(jax.random.PRNGKey(1), dims, rank)
    p0, grid = 2, (2, 2, 1)
    mesh = make_grid_mesh(grid, p0=p0)
    for mode in range(3):
        f4 = mttkrp_general(mesh, mode, 3)
        xs, fl = place_inputs(mesh, x, fs, mode, rank_axis=True)
        co = f4.lower(xs, *fl).compile()
        measured = parse_collectives(co.as_text()).ring_bytes
        predicted = par_general_cost(dims, rank, grid, p0, mode) * 4
        assert measured == predicted, (mode, measured, predicted)
    print("PASS comm_matches_eq16")


def check_stationary_tensor_never_moves():
    """Alg 3's defining property: no collective touches tensor-sized data."""
    dims, rank = (16, 16, 16), 4
    x = random_tensor(jax.random.PRNGKey(0), dims)
    fs = random_factors(jax.random.PRNGKey(1), dims, rank)
    mesh = make_grid_mesh((2, 2, 2))
    f3 = mttkrp_stationary(mesh, 0, 3)
    xs, fl = place_inputs(mesh, x, fs, 0)
    co = f3.lower(xs, *fl).compile()
    summ = parse_collectives(co.as_text())
    local_tensor_bytes = (16 ** 3) // 8 * 4
    for op in summ.ops:
        assert op.operand_bytes < local_tensor_bytes, (
            op.kind, op.operand_bytes
        )
    print("PASS stationary_tensor_never_moves")


def check_cp_compressed_mean():
    """Compressed DP mean == CP-ALS of the true mean gradient."""
    from jax.sharding import PartitionSpec as P

    from repro.core.tensor import random_low_rank_tensor

    mesh = make_mesh((8,), ("dp",))
    dims, rank = (16, 12, 1), 6
    # worker-dependent gradients share a low-rank core (realistic: gradient
    # subspaces overlap across DP replicas) + per-worker perturbation
    base, _ = random_low_rank_tensor(jax.random.PRNGKey(8), dims, 3)
    delta, _ = random_low_rank_tensor(jax.random.PRNGKey(9), dims, 2)
    workers = jnp.stack(
        [base + i * 0.01 * delta for i in range(8)]
    )  # (8, *dims)
    g_mean = jnp.mean(workers, axis=0)  # rank <= 5 exactly

    def body(g):
        g = g.reshape(dims)
        recon, _ = cp_compressed_mean(
            g, ("dp",), rank=rank, sweeps=25, key=jax.random.PRNGKey(10)
        )
        return recon[None]

    f = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P("dp", None, None, None),
            out_specs=P("dp", None, None, None), check_rep=False,
        )
    )
    recon_all = np.asarray(f(workers))
    # every worker must hold the SAME reconstruction (sync invariant)
    for i in range(1, 8):
        np.testing.assert_allclose(
            recon_all[i], recon_all[0], rtol=1e-5, atol=1e-6
        )
    # and it approximates the true mean well at adequate rank
    err = np.linalg.norm(recon_all[0] - g_mean) / np.linalg.norm(g_mean)
    assert err < 0.05, err
    # compression ratio sanity
    assert compression_ratio((4096, 14336), 8, 1) > 100
    print("PASS cp_compressed_mean")


def check_collective_only_factor_sized():
    """The compressed all-reduce must move only Σ I_k R words, never Π I_k."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((8,), ("dp",))
    dims, rank, sweeps = (32, 24, 1), 4, 2
    workers = random_tensor(jax.random.PRNGKey(11), (8,) + dims)

    def body(g):
        g = g.reshape(dims)
        recon, _ = cp_compressed_mean(
            g, ("dp",), rank=rank, sweeps=sweeps, key=jax.random.PRNGKey(0)
        )
        return recon[None]

    f = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P("dp", None, None, None),
            out_specs=P("dp", None, None, None), check_rep=False,
        )
    )
    co = f.lower(workers).compile()
    summ = parse_collectives(co.as_text())
    full_bytes = 32 * 24 * 1 * 4
    for op in summ.ops:
        assert op.operand_bytes < full_bytes, (op.kind, op.operand_bytes)
    # paper-predicted total: sweeps * sum_k I_k * rank words (pmean operand)
    predicted_operand = sweeps * sum(dims) * rank * 4
    assert summ.operand_bytes == predicted_operand, (
        summ.operand_bytes, predicted_operand
    )
    print("PASS collective_only_factor_sized")


def check_alg3_pallas_local():
    """Alg 3 with the engine's Pallas backend for the per-shard MTTKRP:
    the collectives are unchanged and the local blocked kernel matches."""
    dims, rank = (16, 16, 24), 8
    x = random_tensor(jax.random.PRNGKey(20), dims)
    fs = random_factors(jax.random.PRNGKey(21), dims, rank)
    mesh = make_grid_mesh((2, 2, 2))
    pallas = ExecutionContext.create(backend="pallas", interpret=True)
    for mode in range(3):
        f3 = mttkrp_stationary(mesh, mode, 3, ctx=pallas)
        xs, fl = place_inputs(mesh, x, fs, mode)
        np.testing.assert_allclose(
            np.asarray(f3(xs, *fl)), np.asarray(mttkrp(x, fs, mode)),
            rtol=1e-4, atol=1e-4,
        )
    mesh4 = make_grid_mesh((2, 2, 1), p0=2)
    f4 = mttkrp_general(mesh4, 0, 3, ctx=pallas)
    xs, fl = place_inputs(mesh4, x, fs, 0, rank_axis=True)
    np.testing.assert_allclose(
        np.asarray(f4(xs, *fl)), np.asarray(mttkrp(x, fs, 0)),
        rtol=1e-4, atol=1e-4,
    )
    print("PASS alg_pallas_local")


def check_cp_sweep_matches_sequential():
    """The distributed ALS sweep (one shard_map program per sweep) is
    numerically the sequential Gauss-Seidel driver: same fits, same
    factors, same weights, to fp32 collective-reordering tolerance."""
    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_low_rank_tensor

    dims, rank = (16, 16, 24), 4
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(30), dims, rank)
    # 12 sweeps: from this seed's initial factors the sequential driver
    # is still climbing at sweep 8 (fit 0.94) and converges by sweep 12
    par = cp_als_parallel(
        x, rank, n_iters=12, key=jax.random.PRNGKey(31),
        ctx=ExecutionContext.create(grid=(2, 2, 2)),
    )
    seq = cp_als(x, rank, n_iters=12, key=jax.random.PRNGKey(31))
    for fp, fs_ in zip(par.fits, seq.fits):
        assert abs(fp - fs_) < 1e-3, (fp, fs_)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(par.factors[k]), np.asarray(seq.factors[k]),
            rtol=1e-3, atol=1e-4,
        )
    np.testing.assert_allclose(
        np.asarray(par.weights), np.asarray(seq.weights),
        rtol=1e-3, atol=1e-4,
    )
    assert par.final_fit > 0.999
    print("PASS cp_sweep_matches_sequential")


def check_cp_sweep_comm_beats_independent():
    """HLO-measured bytes of ONE distributed ALS sweep < the sum of N
    independent single-mode Eq (12) calls (the BHK amortization), and
    == the sweep cost model exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.tensor import frob_norm
    from repro.distributed import make_grid_mesh

    dims, rank = (32, 32, 32), 4
    x = random_tensor(jax.random.PRNGKey(32), dims)
    fs = random_factors(jax.random.PRNGKey(33), dims, rank)
    for grid in ((2, 2, 2), (1, 2, 2)):
        procs = 1
        for g in grid:
            procs *= g
        mesh = make_grid_mesh(grid, dims=dims, rank=rank)
        sweep = build_cp_sweep(mesh, 3)
        xs, f_sh, blocks, grams = place_cp_state(mesh, x, fs)
        normx = jax.device_put(frob_norm(x), NamedSharding(mesh, P()))
        co = sweep.lower(xs, f_sh, blocks, grams, normx).compile()
        measured = parse_collectives(co.as_text()).ring_bytes
        independent = 0
        for mode in range(3):
            f3 = mttkrp_stationary(mesh, mode, 3)
            xsm, fl = place_inputs(mesh, x, fs, mode)
            independent += parse_collectives(
                f3.lower(xsm, *fl).compile().as_text()
            ).ring_bytes
        # the N independent calls cost exactly the Eq (12) sum ...
        eq12_sum = sum(
            par_stationary_cost(dims, rank, grid, m) for m in range(3)
        ) * 4
        assert independent == eq12_sum, (grid, independent, eq12_sum)
        # ... the sweep strictly beats it (factor gathers amortized) ...
        assert measured < independent, (grid, measured, independent)
        # ... and matches the sweep cost model exactly: the modeled factor
        # + Gram words plus the one scalar fit all-reduce (ring-truncated)
        predicted = stationary_sweep_words(dims, rank, grid) * 4 + int(
            2 * (procs - 1) / procs * 4
        )
        assert measured == predicted, (grid, measured, predicted)
    print("PASS cp_sweep_comm_beats_independent")


def check_ring_overlap_sweep():
    """overlap="ring": the sweep's per-factor all-gather/reduce-scatter
    become ppermute rings with chunked MTTKRP consumption — numerics match
    the monolithic-collective sweep, every factor collective is a
    collective-permute, and HLO-measured bytes equal the SAME
    stationary_sweep_words model exactly (the 2-collectives-per-factor
    traffic is preserved byte-for-byte)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.tensor import frob_norm

    dims, rank = (32, 32, 32), 4
    x = random_tensor(jax.random.PRNGKey(60), dims)
    fs = random_factors(jax.random.PRNGKey(61), dims, rank)
    for grid in ((2, 2, 2), (1, 2, 2)):
        procs = 1
        for g in grid:
            procs *= g
        ctx_ring = ExecutionContext.create(grid=grid, overlap="ring")
        # numerics: ring sweep == plain sweep (fp reordering tolerance)
        r_none = cp_als_parallel(x, rank, n_iters=4, init_factors=fs,
                                 ctx=ExecutionContext.create(grid=grid))
        r_ring = cp_als_parallel(x, rank, n_iters=4, init_factors=fs,
                                 ctx=ctx_ring)
        for k in range(3):
            np.testing.assert_allclose(
                np.asarray(r_ring.factors[k]), np.asarray(r_none.factors[k]),
                rtol=1e-3, atol=1e-4,
            )
        np.testing.assert_allclose(
            np.asarray(r_ring.weights), np.asarray(r_none.weights),
            rtol=1e-3, atol=1e-4,
        )
        for fp, fn_ in zip(r_ring.fits, r_none.fits):
            assert abs(fp - fn_) < 1e-3, (r_ring.fits, r_none.fits)
        # bytes: the ring spelling moves exactly the modeled words
        mesh = make_grid_mesh(grid, dims=dims, rank=rank)
        sweep = build_cp_sweep(mesh, 3, ctx=ctx_ring)
        xs, f_sh, blocks, grams = place_cp_state(mesh, x, fs)
        normx = jax.device_put(frob_norm(x), NamedSharding(mesh, P()))
        summ = parse_collectives(
            sweep.lower(xs, f_sh, blocks, grams, normx).compile().as_text()
        )
        predicted = stationary_sweep_words(dims, rank, grid) * 4 + int(
            2 * (procs - 1) / procs * 4
        )
        assert summ.ring_bytes == predicted, (
            grid, summ.ring_bytes, predicted
        )
        # every factor collective is now a ppermute hop; only the R x R
        # Gram / scalar fit all-reduces remain monolithic
        kinds = summ.by_kind()
        assert "all-gather" not in kinds and "reduce-scatter" not in kinds, (
            grid, kinds
        )
        assert kinds.get("collective-permute", {}).get("count", 0) > 0, kinds
    print("PASS ring_overlap_sweep")


def check_cp_auto_grid_driver():
    """cp_als(distributed=True): automatic Eq (12)-sweep-optimal grid
    selection end-to-end through the core driver entry."""
    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_low_rank_tensor, relative_error
    from repro.core.tensor import tensor_from_factors
    from repro.distributed.grid_select import choose_cp_grid

    dims, rank = (16, 16, 16), 4
    choice = choose_cp_grid(dims, rank, len(jax.devices()))
    assert choice.procs == 8 and choice.grid == (2, 2, 2), choice
    # data key 41: the tensor from key 34 traps ALS from this init in a
    # swamp (the sequential driver too stalls at fit 0.77 for 25 sweeps)
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(41), dims, rank)
    res = cp_als(x, rank, n_iters=25, key=jax.random.PRNGKey(2),
                 ctx=ExecutionContext.create(distributed=True))
    assert res.final_fit > 0.999, res.fits
    recon = tensor_from_factors(res.factors, res.weights)
    assert float(relative_error(x, recon)) < 0.02
    print("PASS cp_auto_grid_driver")


def check_cp_sweep_pallas_local():
    """Sweep driver with the engine's Pallas backend for every per-shard
    local MTTKRP: collectives unchanged, numerics match sequential."""
    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_low_rank_tensor

    dims, rank = (16, 16, 24), 4
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(36), dims, rank)
    par = cp_als_parallel(
        x, rank, n_iters=5, key=jax.random.PRNGKey(37),
        ctx=ExecutionContext.create(grid=(2, 2, 2), backend="pallas", interpret=True),
    )
    seq = cp_als(x, rank, n_iters=5, key=jax.random.PRNGKey(37))
    for fp, fs_ in zip(par.fits, seq.fits):
        assert abs(fp - fs_) < 1e-3, (fp, fs_)
    print("PASS cp_sweep_pallas_local")


def check_context_roundtrip_reproduces_sweep():
    """A serialized ExecutionContext is a reproducible artifact: building
    the distributed sweep from ``from_json(to_json(ctx))`` emits the SAME
    program — identical HLO-measured collective bytes — and the pallas
    local path dispatches the same number of kernels per trace.  Also the
    observability no-overhead guarantee: ``observe=True`` lowers to HLO
    *identical* to ``observe=False`` (recording is driver-side only;
    nothing observability-related may enter the traced program)."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.tensor import frob_norm
    from repro.observe.metrics import PALLAS_DISPATCHES, registry

    dims, rank = (16, 16, 24), 4
    x = random_tensor(jax.random.PRNGKey(40), dims)
    fs = random_factors(jax.random.PRNGKey(41), dims, rank)
    ctx = ExecutionContext.for_problem(
        dims, rank, backend="pallas", interpret=True, distributed=True,
        procs=len(jax.devices()),
    )
    ctx2 = ExecutionContext.from_json(ctx.to_json())
    assert ctx2 == ctx and hash(ctx2) == hash(ctx)
    assert ctx2.distribution.grid == ctx.distribution.grid

    def measure(c, want_text=False):
        mesh = c.build_mesh(dims, rank)
        sweep = build_cp_sweep(mesh, 3, ctx=c)
        xs, f_sh, blocks, grams = place_cp_state(mesh, x, fs)
        normx = jax.device_put(frob_norm(x), NamedSharding(mesh, P()))
        before = registry().counter(PALLAS_DISPATCHES)
        lowered = sweep.lower(xs, f_sh, blocks, grams, normx)
        dispatches = registry().counter(PALLAS_DISPATCHES) - before
        ring = parse_collectives(lowered.compile().as_text()).ring_bytes
        # the lowered program without source locations: the compiled text
        # also lists every call site's stack frames, which differ per call
        text = lowered.as_text()
        return (ring, dispatches, text) if want_text else (ring, dispatches)

    bytes1, disp1 = measure(ctx)
    bytes2, disp2 = measure(ctx2)
    assert bytes1 == bytes2, (bytes1, bytes2)
    assert disp1 == disp2 and disp1 > 0, (disp1, disp2)

    _, _, text_off = measure(
        dataclasses.replace(ctx, observe=False), want_text=True
    )
    _, _, text_on = measure(
        dataclasses.replace(ctx, observe=True), want_text=True
    )
    assert text_on == text_off, "observe=True changed the sweep HLO"
    print("PASS context_roundtrip_reproduces_sweep")


def check_multi_ttm_comm_matches_model():
    """Measured ring bytes of the stationary full-core Multi-TTM ==
    par_multi_ttm_cost, exactly (the Eq-12 analog for Tucker)."""
    from repro.core.bounds import par_multi_ttm_cost
    from repro.distributed.tucker_parallel import (
        multi_ttm_stationary,
        place_multi_ttm_inputs,
    )
    from repro.engine.execute import multi_ttm

    dims, ranks = (16, 16, 16), (4, 3, 2)
    x = random_tensor(jax.random.PRNGKey(50), dims)
    mats = [
        jax.random.normal(jax.random.PRNGKey(51 + k), (d, r))
        for k, (d, r) in enumerate(zip(dims, ranks))
    ]
    for grid in ((2, 2, 2), (1, 2, 4)):
        mesh = make_grid_mesh(grid)
        f = multi_ttm_stationary(mesh, 3)
        xs, ms = place_multi_ttm_inputs(mesh, x, mats)
        np.testing.assert_allclose(
            np.asarray(f(xs, *ms)), np.asarray(multi_ttm(x, mats, None)),
            rtol=1e-4, atol=1e-4,
        )
        measured = parse_collectives(
            f.lower(xs, *ms).compile().as_text()
        ).ring_bytes
        predicted = int(par_multi_ttm_cost(dims, ranks, grid) * 4)
        assert measured == predicted, (grid, measured, predicted)
    print("PASS multi_ttm_comm_matches_model")


def check_tucker_sweep_comm_matches_model():
    """HLO-measured bytes of ONE distributed HOOI sweep == the Multi-TTM
    sweep model (multi_ttm_sweep_words) exactly — per mode, one
    hyperslice all-reduce + one fiber all-gather of the partial Y^(k),
    and no factor collectives at all."""
    import math

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.tensor import frob_norm
    from repro.core.tucker import hosvd_init
    from repro.distributed.grid_select import multi_ttm_sweep_words
    from repro.distributed.tucker_parallel import (
        build_tucker_sweep,
        place_tucker_state,
    )

    dims, ranks = (16, 16, 16), (4, 3, 2)
    x = random_tensor(jax.random.PRNGKey(52), dims)
    factors = hosvd_init(x, ranks)
    for grid in ((2, 2, 2), (1, 2, 4)):
        mesh = make_grid_mesh(grid)
        sweep = build_tucker_sweep(mesh, 3, ranks)
        xs, fs = place_tucker_state(mesh, x, factors)
        normx = jax.device_put(frob_norm(x), NamedSharding(mesh, P()))
        summ = parse_collectives(
            sweep.lower(xs, fs, normx).compile().as_text()
        )
        measured = summ.ring_bytes
        procs = math.prod(grid)
        # exact expected bytes, truncating per op like CollectiveOp
        expected = 0
        for k, (d, pk) in enumerate(zip(dims, grid)):
            rbar = math.prod(r for j, r in enumerate(ranks) if j != k)
            w_bytes = (d // pk) * rbar * 4
            q = procs // pk
            expected += int(2 * (q - 1) / q * w_bytes) + (pk - 1) * w_bytes
        assert measured == expected, (grid, measured, expected)
        # ... which is exactly the grid-selection objective in words
        assert expected == int(multi_ttm_sweep_words(dims, ranks, grid) * 4)
        # factors never travel: every gather/reduce operand is Y^(k)-sized
        for op in summ.ops:
            assert op.operand_bytes <= max(
                (d // pk) * math.prod(
                    r for j, r in enumerate(ranks) if j != k
                ) * 4
                for k, (d, pk) in enumerate(zip(dims, grid))
            ), (op.kind, op.operand_bytes)
    print("PASS tucker_sweep_comm_matches_model")


def check_tucker_parallel_matches_sequential():
    """The distributed HOOI sweep is numerically the sequential driver:
    same fits, same factors (deterministic eigh sign convention), same
    core, to fp32 collective-reordering tolerance — and the core-driver
    entry (tucker_hooi with a distributed context) selects the
    Multi-TTM-sweep-optimal grid automatically."""
    from repro.core.tensor import random_tucker_tensor
    from repro.core.tucker import tucker_hooi
    from repro.distributed.grid_select import choose_tucker_grid
    from repro.distributed.tucker_parallel import tucker_hooi_parallel

    dims, ranks = (16, 16, 16), (4, 3, 2)
    x, _, _ = random_tucker_tensor(jax.random.PRNGKey(53), dims, ranks)
    seq = tucker_hooi(x, ranks, n_iters=5)
    par = tucker_hooi_parallel(x, ranks, n_iters=5, grid=(2, 2, 2))
    for fs_, fp in zip(seq.fits, par.fits):
        assert abs(fs_ - fp) < 1e-3, (seq.fits, par.fits)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(par.factors[k]), np.asarray(seq.factors[k]),
            rtol=1e-3, atol=1e-3,
        )
    np.testing.assert_allclose(
        np.asarray(par.core), np.asarray(seq.core), rtol=1e-3, atol=1e-3
    )
    assert par.final_fit > 0.999, par.fits
    # the unified driver entry: a distributed context routes here with
    # automatic grid selection
    choice = choose_tucker_grid(dims, ranks, len(jax.devices()))
    assert choice.procs == 8, choice
    ctx = ExecutionContext.create(distributed=True)
    res = tucker_hooi(x, ranks, n_iters=5, ctx=ctx)
    assert res.final_fit > 0.999, res.fits
    print("PASS tucker_parallel_matches_sequential")


def check_tucker_sweep_pallas_local():
    """Sweep driver with the engine's Pallas Kronecker kernel for every
    per-shard local Multi-TTM: numerics match the einsum-local sweep."""
    from repro.core.tensor import random_tucker_tensor
    from repro.distributed.tucker_parallel import tucker_hooi_parallel
    from repro.observe.metrics import PALLAS_DISPATCHES, registry

    dims, ranks = (16, 16, 24), (4, 3, 2)
    x, _, _ = random_tucker_tensor(jax.random.PRNGKey(54), dims, ranks)
    ctx = ExecutionContext.create(
        backend="pallas", interpret=True, distributed=True, grid=(2, 2, 2)
    )
    before = registry().counter(PALLAS_DISPATCHES)
    par = tucker_hooi_parallel(x, ranks, n_iters=4, ctx=ctx)
    assert registry().counter(PALLAS_DISPATCHES) > before
    ref = tucker_hooi_parallel(x, ranks, n_iters=4, grid=(2, 2, 2))
    for fp, fr in zip(par.fits, ref.fits):
        assert abs(fp - fr) < 1e-3, (par.fits, ref.fits)
    print("PASS tucker_sweep_pallas_local")


CHECKS = [
    check_alg3_numerics,
    check_alg3_asymmetric_grid,
    check_alg4_numerics,
    check_alg4_4way,
    check_comm_matches_eq12,
    check_comm_matches_eq16,
    check_stationary_tensor_never_moves,
    check_cp_compressed_mean,
    check_collective_only_factor_sized,
    check_alg3_pallas_local,
    check_cp_sweep_matches_sequential,
    check_cp_sweep_comm_beats_independent,
    check_ring_overlap_sweep,
    check_cp_auto_grid_driver,
    check_cp_sweep_pallas_local,
    check_context_roundtrip_reproduces_sweep,
    check_multi_ttm_comm_matches_model,
    check_tucker_sweep_comm_matches_model,
    check_tucker_parallel_matches_sequential,
    check_tucker_sweep_pallas_local,
]

if __name__ == "__main__":
    names = sys.argv[1:]
    for chk in CHECKS:
        if names and chk.__name__ not in names:
            continue
        chk()
    print("ALL_DIST_OK")
