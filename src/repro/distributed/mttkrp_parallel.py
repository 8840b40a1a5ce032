"""Parallel MTTKRP: Algorithm 3 (stationary tensor) and Algorithm 4
(general, rank-partitioned) as shard_map programs.

Collective mapping (paper -> JAX):
  All-Gather over a hyperslice   -> lax.all_gather(axis_names, tiled=True)
  Reduce-Scatter over hyperslice -> lax.psum_scatter(axis_names, tiled=True)

Data distributions follow §V-C1 / §V-D1 exactly:
  X          : block-distributed over the N-way grid, P('m0', ..., 'm{N-1}')
               (Alg 4 additionally splits mode 0 across the rank axis:
               P(('r','m0'), 'm1', ...))
  A^(k)      : rows split by m{k} into the paper's S^{(k)}_{p_k} block-rows,
               each block-row spread across its hyperslice,
               P(('m{k}', *hyperslice), ) — and columns split by 'r' for
               Alg 4, P((...), 'r').
  B^(n) (out): same layout as A^(n).

The per-processor communication volumes of these programs are *measured*
from compiled HLO (distributed/hlo.py) and checked against Eq (12)/Eq (16)
in tests/test_parallel_cost_match.py — that is the reproduction of the
paper's cost analysis, and the optimality tests compare them against the
§IV lower bounds.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import shard_map
from .mesh import RANK_AXIS, hyperslice_axes, mode_axis, row_sharding_axes

LocalFn = Callable[[jax.Array, Sequence[jax.Array], int], jax.Array]


def engine_local_fn(*, ctx=None) -> LocalFn:
    """Per-processor MTTKRP through the engine's dispatch layer.

    This is the paper's separation of concerns made literal: Algorithms 3/4
    own the collectives; the *local* MTTKRP inside each shard is exactly the
    sequential problem, so it runs through the same engine (and, with
    ``ctx.backend == 'pallas'``, the same blocked VMEM kernels) as the
    single-device path. ``backend='auto'`` resolves against the autotuner's
    plan cache keyed by the *local shard* shape — tuned local plans apply
    inside shard_map because resolution is pure Python over static shapes
    (it happens once, at trace time; no measurement is attempted there).

    ``ctx`` is an :class:`~repro.engine.context.ExecutionContext` (its
    ``local()`` view is used — the collectives here are owned by the
    algorithms, not the engine); without one, the process default.
    """
    from ..engine import execute as engine_execute  # call-time: layer cycle
    from ..engine.context import ExecutionContext

    ctx = ctx if ctx is not None else ExecutionContext.default()
    local_ctx = ctx.local()

    def fn(x, factors, mode):
        return engine_execute.mttkrp(x, factors, mode, ctx=local_ctx)

    return fn


def gather_factor(f_loc: jax.Array, ndim: int, k: int) -> jax.Array:
    """Line 4 of Alg 3/4: all-gather factor k's block-rows over the mode-k
    hyperslice, reconstructing S^{(k)}_{p_k} on every processor of it."""
    return jax.lax.all_gather(
        f_loc, hyperslice_axes(ndim, k), axis=0, tiled=True
    )


def gather_factors(
    f_locs: Sequence[jax.Array | None], ndim: int, skip: int | None = None
) -> list[jax.Array | None]:
    """Batched factor gathers: one :func:`gather_factor` per non-``skip``
    mode (``f_locs`` is indexed by mode; ``None`` entries pass through).
    The CP-ALS sweep driver and Alg 3/4 share this so every consumer emits
    identical collectives (the HLO byte accounting depends on it)."""
    return [
        None if (k == skip or f is None) else gather_factor(f, ndim, k)
        for k, f in enumerate(f_locs)
    ]


# --------------------------------------------------------------------------
# Shardings (the paper's initial/terminal data distributions)
# --------------------------------------------------------------------------

def tensor_spec(ndim: int, rank_split_mode: int | None = None) -> P:
    """X's PartitionSpec on the grid mesh (optionally splitting one mode
    across the rank axis too, for Alg 4's across-p0 partition of X)."""
    parts = []
    for k in range(ndim):
        if k == rank_split_mode:
            # m-axis major, r minor: the rank-axis all-gather then
            # reconstructs the contiguous block S^{(k)}_{p_k}
            parts.append((mode_axis(k), RANK_AXIS))
        else:
            parts.append(mode_axis(k))
    return P(*parts)


def factor_spec(ndim: int, k: int, rank_axis: bool = False) -> P:
    """A^(k)'s PartitionSpec: rows over (m{k}, hyperslice), cols over r."""
    return P(row_sharding_axes(ndim, k), RANK_AXIS if rank_axis else None)


def output_spec(ndim: int, mode: int, rank_axis: bool = False) -> P:
    return factor_spec(ndim, mode, rank_axis)


# --------------------------------------------------------------------------
# Algorithm 3: stationary-tensor MTTKRP
# --------------------------------------------------------------------------

def _stationary_local(
    x_loc: jax.Array,
    f_locs: tuple[jax.Array, ...],
    *,
    ndim: int,
    mode: int,
    local_fn: LocalFn,
) -> jax.Array:
    """Per-processor body of Algorithm 3 (runs under shard_map)."""
    by_mode: list[jax.Array | None] = [None] * ndim
    fi = 0
    for k in range(ndim):
        if k != mode:
            by_mode[k] = f_locs[fi]
            fi += 1
    # Line 4: A^(k)_{p_k} = All-Gather over the mode-k hyperslice
    gathered = gather_factors(by_mode, ndim, skip=mode)
    # Line 6: local MTTKRP
    c = local_fn(x_loc, gathered, mode)
    # Line 7: Reduce-Scatter over the mode-n hyperslice
    return jax.lax.psum_scatter(
        c, hyperslice_axes(ndim, mode), scatter_dimension=0, tiled=True
    )


def _resolve_parallel_ctx(ctx):
    """The Alg 3/4 builders' context (the process default without one)
    and their replication-check policy: a ``pallas_call`` output carries
    no manual-axes type, so the (purely diagnostic) check is skipped when
    the local body may contain a kernel ("auto" can resolve to pallas at
    trace time)."""
    from ..engine.context import ExecutionContext

    ctx = ctx if ctx is not None else ExecutionContext.default()
    return ctx, ctx.backend not in ("pallas", "auto")


def mttkrp_stationary(
    mesh: jax.sharding.Mesh,
    mode: int,
    ndim: int,
    local_fn: LocalFn | None = None,
    *,
    ctx=None,
):
    """Build the Alg-3 shard_map callable ``f(x, *factors_except_mode)``.

    The tensor never moves (stationary); only factor blocks are gathered and
    partial outputs reduce-scattered — per-processor volume Eq (12). The
    local MTTKRP goes through the engine under ``ctx`` (the backend selects
    einsum / blocked_host / pallas); an explicit ``local_fn`` overrides it.
    """
    ctx, check_rep = _resolve_parallel_ctx(ctx)
    if local_fn is None:
        local_fn = engine_local_fn(ctx=ctx)
    in_specs = (tensor_spec(ndim),) + tuple(
        factor_spec(ndim, k) for k in range(ndim) if k != mode
    )
    fn = functools.partial(
        _stationary_local, ndim=ndim, mode=mode, local_fn=local_fn
    )

    def wrapper(x, *f_locs):
        return fn(x, f_locs)

    return jax.jit(
        shard_map(
            wrapper,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=output_spec(ndim, mode),
            check_rep=check_rep,
        )
    )


# --------------------------------------------------------------------------
# Algorithm 4: general MTTKRP (rank-partitioned)
# --------------------------------------------------------------------------

def _general_local(
    x_loc: jax.Array,
    f_locs: tuple[jax.Array, ...],
    *,
    ndim: int,
    mode: int,
    local_fn: LocalFn,
) -> jax.Array:
    """Per-processor body of Algorithm 4 (runs under shard_map)."""
    # Line 3: All-Gather the subtensor across the rank-axis fiber
    x_full = jax.lax.all_gather(x_loc, (RANK_AXIS,), axis=0, tiled=True)
    by_mode: list[jax.Array | None] = [None] * ndim
    fi = 0
    for k in range(ndim):
        if k != mode:
            by_mode[k] = f_locs[fi]
            fi += 1
    # Line 5: gather factor block-rows over the mode-k hyperslices
    # (never across r: each rank-slice keeps its own T_{p_0} columns)
    gathered = gather_factors(by_mode, ndim, skip=mode)
    # Line 7: local MTTKRP on the gathered subtensor and factor columns
    c = local_fn(x_full, gathered, mode)
    # Line 8: Reduce-Scatter over the mode-n hyperslice
    return jax.lax.psum_scatter(
        c, hyperslice_axes(ndim, mode), scatter_dimension=0, tiled=True
    )


def mttkrp_general(
    mesh: jax.sharding.Mesh,
    mode: int,
    ndim: int,
    local_fn: LocalFn | None = None,
    *,
    ctx=None,
):
    """Build the Alg-4 shard_map callable ``f(x, *factors_except_mode)``.

    Requires a mesh with a leading 'r' axis (make_grid_mesh(grid, p0)).
    Alg 3 is the special case p0 == 1 (the 'r' collectives degenerate).
    The local MTTKRP goes through the engine like :func:`mttkrp_stationary`.
    """
    ctx, check_rep = _resolve_parallel_ctx(ctx)
    if local_fn is None:
        local_fn = engine_local_fn(ctx=ctx)
    in_specs = (tensor_spec(ndim, rank_split_mode=0),) + tuple(
        factor_spec(ndim, k, rank_axis=True)
        for k in range(ndim)
        if k != mode
    )
    fn = functools.partial(
        _general_local, ndim=ndim, mode=mode, local_fn=local_fn
    )

    def wrapper(x, *f_locs):
        return fn(x, f_locs)

    return jax.jit(
        shard_map(
            wrapper,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=output_spec(ndim, mode, rank_axis=True),
            check_rep=check_rep,
        )
    )


# --------------------------------------------------------------------------
# Convenience: place global arrays per the paper's distributions
# --------------------------------------------------------------------------

def place_inputs(
    mesh: jax.sharding.Mesh,
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    rank_axis: bool = False,
):
    """Device-put X and the non-mode factors into their §V distributions."""
    ndim = x.ndim
    xs = jax.device_put(
        x,
        NamedSharding(
            mesh, tensor_spec(ndim, rank_split_mode=0 if rank_axis else None)
        ),
    )
    fs = tuple(
        jax.device_put(
            factors[k], NamedSharding(mesh, factor_spec(ndim, k, rank_axis))
        )
        for k in range(ndim)
        if k != mode
    )
    return xs, fs
