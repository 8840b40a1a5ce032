"""Distributed Multi-TTM and the Tucker/HOOI sweep driver.

The Multi-TTM story (arXiv:2207.10437) parallelizes on the same
stationary-tensor distribution as Algorithm 3: X is block-distributed
over the N-way grid and never moves.  Two shard_map programs live here:

* :func:`multi_ttm_stationary` — one full-core Multi-TTM: matrices in
  the CP factor layout (block-rows spread over the mode hyperslices),
  gathered exactly like Alg 3's factors, then the local partial core is
  all-reduced.  Per-processor volume
  :func:`repro.core.bounds.par_multi_ttm_cost`, measured from compiled
  HLO in ``tests/dist_worker.py::check_multi_ttm_comm_matches_model``.

* :func:`build_tucker_sweep` — ONE shard_map program per HOOI sweep.
  Factor matrices are carried *replicated* (they are tall-skinny
  ``I_k x R_k``): each processor slices its own block-rows locally, runs
  the local Multi-TTM through the engine
  (:func:`repro.engine.execute.multi_ttm` — so ``backend="pallas"``
  runs the blocked Kronecker kernel per shard), all-reduces the partial
  ``Y^(k)`` block-rows over the mode-k hyperslice, all-gathers them over
  the mode-k fiber, and updates ``A_k`` by a replicated eigendecomposition
  — after which every processor again holds all of ``A_k``, so factors
  never travel in a collective at all.  Per-sweep volume
  :func:`repro.distributed.grid_select.multi_ttm_sweep_words`, measured
  in ``check_tucker_sweep_comm_matches_model``.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import shard_map
from ..core.tensor import frob_norm
from ..core.tucker import (
    TuckerResult,
    _check_ranks,
    _leading_eigvecs,
    _unfold_rows,
    hosvd_init,
)
from .grid_select import GridChoice, choose_tucker_grid
from .mesh import (
    RANK_AXIS,
    hyperslice_axes,
    make_grid_mesh,
    mode_axis,
    validate_tucker_grid,
)
from .mttkrp_parallel import factor_spec, gather_factors, tensor_spec
from .ring import ring_all_gather


def _engine_multi_ttm(ctx) -> Callable:
    """The per-shard Multi-TTM through the engine (same separation of
    concerns as ``engine_local_fn``: the programs here own the
    collectives; inside each shard the problem is exactly sequential)."""
    from ..engine import execute as engine_execute  # call-time: layer cycle
    from ..engine.context import ExecutionContext

    if ctx is None:
        ctx = ExecutionContext.default()
    local_ctx = ctx.local()

    def fn(x_loc, mats, keep):
        return engine_execute.multi_ttm(x_loc, mats, keep, ctx=local_ctx)

    return fn


# --------------------------------------------------------------------------
# One full-core Multi-TTM (matrices in the Alg-3 factor layout)
# --------------------------------------------------------------------------

def _multi_ttm_local(
    x_loc: jax.Array,
    m_locs: tuple[jax.Array, ...],
    *,
    ndim: int,
    local_fn: Callable,
) -> jax.Array:
    """Per-processor body: gather every matrix's block-rows over its mode
    hyperslice (exactly Alg 3 line 4), contract locally, all-reduce the
    partial core over the whole grid."""
    gathered = gather_factors(list(m_locs), ndim)
    core_part = local_fn(x_loc, gathered, None)
    return jax.lax.psum(
        core_part, tuple(mode_axis(k) for k in range(ndim))
    )


def multi_ttm_stationary(
    mesh: jax.sharding.Mesh,
    ndim: int,
    *,
    ctx=None,
):
    """Build the stationary-tensor full-core Multi-TTM shard_map callable
    ``f(x, *matrices) -> core`` (core replicated on every processor).

    X is block-distributed and never moves; matrices use the CP factor
    layout (:func:`repro.distributed.mttkrp_parallel.factor_spec`), so
    the gather terms are the Eq-12-shaped ones of
    :func:`repro.core.bounds.par_multi_ttm_cost`, plus one all-reduce of
    the ``prod R_k`` partial core.
    """
    local_fn = _engine_multi_ttm(ctx)
    in_specs = (tensor_spec(ndim),) + tuple(
        factor_spec(ndim, k) for k in range(ndim)
    )
    fn = functools.partial(_multi_ttm_local, ndim=ndim, local_fn=local_fn)

    def wrapper(x, *m_locs):
        return fn(x, m_locs)

    return jax.jit(
        shard_map(
            wrapper,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P(*([None] * ndim)),
            check_rep=False,
        )
    )


def place_multi_ttm_inputs(
    mesh: jax.sharding.Mesh,
    x: jax.Array,
    matrices: Sequence[jax.Array],
):
    """Device-put X and the matrices into the stationary distribution."""
    ndim = x.ndim
    xs = jax.device_put(x, NamedSharding(mesh, tensor_spec(ndim)))
    ms = tuple(
        jax.device_put(m, NamedSharding(mesh, factor_spec(ndim, k)))
        for k, m in enumerate(matrices)
    )
    return xs, ms


# --------------------------------------------------------------------------
# The HOOI sweep (one shard_map program per sweep)
# --------------------------------------------------------------------------

def _local_rows(f_full: jax.Array, j: int, pj: int) -> jax.Array:
    """This processor's block-rows of the replicated factor j."""
    rows = f_full.shape[0] // pj
    start = jax.lax.axis_index(mode_axis(j)) * rows
    return jax.lax.dynamic_slice_in_dim(f_full, start, rows, axis=0)


def _tucker_sweep_local(
    x_loc: jax.Array,
    factors: tuple[jax.Array, ...],
    normx: jax.Array,
    *,
    ndim: int,
    ranks: tuple[int, ...],
    grid: tuple[int, ...],
    local_fn: Callable,
    compute_fit: bool,
    overlap: str = "none",
):
    """One full HOOI sweep (all N mode updates) under shard_map; factors
    are replicated, X stays put, and the only collectives are one
    hyperslice all-reduce + one fiber all-gather of the partial Y^(k)
    per mode (see :func:`multi_ttm_sweep_words`).

    ``overlap="ring"`` spells the fiber all-gather as a ``ppermute`` ring
    (:func:`repro.distributed.ring.ring_all_gather`) — same result, same
    ring bytes, but the transfer is exposed as ``P_k - 1`` chunk hops the
    scheduler can interleave with the eigendecomposition's Gram build.
    """
    factors = list(factors)
    dtype = x_loc.dtype
    zm = None
    for k in range(ndim):
        mats = [
            None if j == k else _local_rows(factors[j], j, grid[j])
            for j in range(ndim)
        ]
        z_part = local_fn(x_loc, mats, k)
        z_rows = jax.lax.psum(z_part, hyperslice_axes(ndim, k))
        zm_rows = _unfold_rows(z_rows, k)
        if overlap == "ring":
            zm = ring_all_gather(zm_rows, (mode_axis(k),))
        else:
            zm = jax.lax.all_gather(
                zm_rows, (mode_axis(k),), axis=0, tiled=True
            )
        factors[k] = _leading_eigvecs(zm @ zm.T, ranks[k]).astype(dtype)
    # the core falls out of the last mode update (mode N-1 rows of zm):
    # (R_{N-1}, prod_{j<N-1} R_j) -> (R_0, ..., R_{N-1})
    core_mat = factors[ndim - 1].T.astype(jnp.float32) @ zm.astype(jnp.float32)
    core = jnp.moveaxis(
        core_mat.reshape((ranks[ndim - 1],) + ranks[: ndim - 1]), 0,
        ndim - 1,
    ).astype(dtype)
    if compute_fit:
        err_sq = jnp.maximum(normx**2 - frob_norm(core) ** 2, 0.0)
        fit = 1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)
    else:
        fit = jnp.zeros((), dtype)
    return tuple(factors), core, fit


def build_tucker_sweep(
    mesh: jax.sharding.Mesh,
    ndim: int,
    ranks: Sequence[int],
    *,
    ctx=None,
    compute_fit: bool = True,
) -> Callable:
    """Compile-ready HOOI sweep: ``f(x, factors, normx) -> (factors,
    core, fit)`` with X block-distributed (:func:`place_tucker_state`)
    and the factors/core replicated."""
    ranks = tuple(int(r) for r in ranks)
    grid = tuple(
        mesh.shape[mode_axis(k)] for k in range(ndim)
    )
    local_fn = _engine_multi_ttm(ctx)
    overlap = "none"
    if ctx is not None and getattr(ctx, "distribution", None) is not None:
        overlap = ctx.distribution.overlap
    in_specs = (
        tensor_spec(ndim),
        tuple(P(None, None) for _ in range(ndim)),
        P(),
    )
    out_specs = (
        in_specs[1],
        P(*([None] * ndim)),
        P(),
    )
    body = functools.partial(
        _tucker_sweep_local, ndim=ndim, ranks=ranks, grid=grid,
        local_fn=local_fn, compute_fit=compute_fit, overlap=overlap,
    )
    # check_rep=False: the replicated factors and fit are not provable by
    # the manual-axes check, and under backend="pallas"/"auto" the body
    # holds pallas_call
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_rep=False,
        )
    )


def place_tucker_state(
    mesh: jax.sharding.Mesh,
    x: jax.Array,
    factors: Sequence[jax.Array],
):
    """Device-put the sweep's carried state: X block-distributed (it
    never moves again) and the factors replicated."""
    ndim = x.ndim
    xs = jax.device_put(x, NamedSharding(mesh, tensor_spec(ndim)))
    fs = tuple(
        jax.device_put(f, NamedSharding(mesh, P(None, None)))
        for f in factors
    )
    return xs, fs


# --------------------------------------------------------------------------
# The driver
# --------------------------------------------------------------------------

def tucker_hooi_parallel(
    x: jax.Array,
    ranks: Sequence[int],
    n_iters: int = 10,
    *,
    ctx=None,
    init_factors: Sequence[jax.Array] | None = None,
    grid: Sequence[int] | None = None,
    mesh: jax.sharding.Mesh | None = None,
    procs: int | None = None,
    tol: float = 0.0,
    compute_fit: bool = True,
) -> TuckerResult:
    """Distributed Tucker/HOOI with automatic grid selection.

    Grid resolution (all read from ``ctx.distribution``; explicit
    ``grid``/``mesh``/``procs`` arguments override): an explicit ``mesh``
    wins; else an explicit ``grid`` is validated against the tensor
    extents; else
    :func:`repro.distributed.grid_select.choose_tucker_grid` picks the
    Multi-TTM-sweep-optimal evenly-sharding grid for ``procs`` (default:
    every available device).  Factors are returned orthonormal, the core
    replicated — the same convention as
    :func:`repro.core.tucker.tucker_hooi`.
    """
    from dataclasses import replace

    from ..engine.context import Distribution, ExecutionContext

    if ctx is None:
        ctx = ExecutionContext.default()
    if ctx.distribution is None:
        # this driver IS the distributed path; a plain context means
        # "select everything automatically" (re-validates, so tune=True
        # still fails loudly here)
        ctx = replace(ctx, distribution=Distribution())
    if ctx.distribution.p0 != 1:
        raise ValueError(
            "the Tucker sweep keeps X stationary on an N-way grid; "
            "rank-axis (p0>1) contexts are for single-mode mttkrp_general"
        )
    ndim = x.ndim
    ranks = _check_ranks(x.shape, ranks)
    dist = ctx.distribution
    mesh = mesh if mesh is not None else dist.mesh
    grid = tuple(grid) if grid is not None else dist.grid
    procs = procs if procs is not None else dist.procs
    choice: GridChoice | None = None
    if mesh is None:
        if grid is None:
            procs = procs if procs is not None else len(jax.devices())
            choice = choose_tucker_grid(x.shape, ranks, procs)
            grid = choice.grid
        validate_tucker_grid(grid, dims=x.shape)
        mesh = make_grid_mesh(grid)
    else:
        if RANK_AXIS in mesh.axis_names:
            raise ValueError(
                "tucker_hooi_parallel keeps X stationary; pass a p0=1 "
                "grid mesh"
            )
        grid = tuple(
            mesh.shape[mode_axis(k)] for k in range(len(mesh.axis_names))
        )
        validate_tucker_grid(grid, dims=x.shape)
    if len(grid) != ndim:
        raise ValueError(f"grid {grid} is not {ndim}-way")
    if math.prod(grid) > 1 and any(
        x.shape[k] % g for k, g in enumerate(grid)
    ):  # pragma: no cover - validate_tucker_grid already rejects
        raise ValueError(f"grid {grid} does not shard {x.shape} evenly")

    if init_factors is not None:
        factors = [jnp.asarray(f) for f in init_factors]
    else:
        factors = hosvd_init(x, ranks)
    if n_iters < 1:  # HOSVD only: no sweep program to run
        from ..core.tucker import tucker_hooi

        return tucker_hooi(
            x, ranks, 0, ctx=ctx.local(), init_factors=factors
        )
    normx = frob_norm(x)

    sweep = build_tucker_sweep(
        mesh, ndim, ranks, ctx=ctx, compute_fit=compute_fit or tol > 0,
    )
    xs, fs = place_tucker_state(mesh, x, factors)
    normx_dev = jax.device_put(normx, NamedSharding(mesh, P()))

    from ..observe import trace as _otrace

    if _otrace.should_record(ctx.observe):
        # Driver level: lower the sweep once more and walk its HLO for the
        # actual collective bytes next to the Multi-TTM sweep model.
        from .grid_select import multi_ttm_sweep_words
        from .hlo import parse_collectives

        nproc = int(math.prod(grid))
        text = sweep.lower(xs, fs, normx_dev).compile().as_text()
        summ = parse_collectives(text)
        itemsize = int(x.dtype.itemsize)
        modeled = int(multi_ttm_sweep_words(x.shape, ranks, grid))
        _otrace.record_event(
            "tucker_sweep_collectives",
            shape=list(x.shape),
            ranks=list(ranks),
            grid=list(grid),
            procs=nproc,
            itemsize=itemsize,
            measured_collective_bytes=int(summ.ring_bytes),
            modeled_words=modeled,
            modeled_bytes=modeled * itemsize,
            collectives_by_kind={
                k: v for k, v in summ.by_kind().items()
            },
        )

    fits: list[float] = []
    core = None
    for it in range(n_iters):
        fs, core, fit = sweep(xs, fs, normx_dev)
        if compute_fit or tol > 0:
            fits.append(float(fit))
        if tol and it > 0 and abs(fits[-1] - fits[-2]) < tol:
            break
    out_factors = [jnp.asarray(np.asarray(f)) for f in fs]
    return TuckerResult(jnp.asarray(np.asarray(core)), out_factors, fits)
