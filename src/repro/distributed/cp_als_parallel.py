"""Distributed CP-ALS: the stationary-tensor sweep driver.

The paper's parallel story (§V) analyzes one MTTKRP at a time; the workload
that matters (§II-A) is the CP-ALS sweep, where the tensor is needed in
*every* mode each iteration.  Ballard–Hayashi–Kannan (arXiv:1806.07985)
show the right organization: X stays stationary in the Alg-3 block
distribution for the whole decomposition, and factor communication
amortizes across the N per-mode updates.  This module implements that as
ONE shard_map program per sweep:

* X is block-distributed over the N-way grid and never moves.
* Each factor's gathered block-rows (the Alg-3 ``S^{(k)}_{p_k}``) are part
  of the carried state: they are produced by the all-gather right after
  that factor's update and *reused* by every subsequent mode update in this
  sweep and the next — so per sweep each factor is all-gathered exactly
  once and each MTTKRP output reduce-scattered exactly once (2 collectives
  per factor vs. N for independent per-mode Eq (12) calls).
* The Gram/Hadamard normal-equations solve runs on the sharded factors:
  Γ_n is the Hadamard product of carried R×R Grams (replicated), each
  processor solves its own block of rows, and the updated Gram is rebuilt
  from the gathered block-rows with a single R×R all-reduce over the
  P_n-processor mode-n fiber.  Column norms λ come from the Gram diagonal —
  no extra collective.
* The local MTTKRP inside each shard goes through
  :func:`repro.distributed.mttkrp_parallel.engine_local_fn`, so
  ``backend="pallas"`` runs the blocked VMEM kernels per shard and
  ``backend="auto"`` resolves the tune cache keyed by the *local shard*
  shapes.

Per-sweep communication is measured from compiled HLO in
``tests/dist_worker.py`` and checked to beat N independent
``mttkrp_stationary`` calls (the Eq (12) sum).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import shard_map
from ..core.cp_als import CPResult
from ..core.tensor import frob_norm, random_factors
from .grid_select import choose_cp_grid
from .mesh import (
    RANK_AXIS,
    hyperslice_axes,
    make_grid_mesh,
    mode_axis,
    validate_grid,
)
from .mttkrp_parallel import (
    LocalFn,
    engine_local_fn,
    factor_spec,
    gather_factor,
    tensor_spec,
)
from .ring import (
    arrival_source,
    ring_all_gather_parts,
    ring_assemble,
    ring_index,
    ring_reduce_scatter,
    ring_size,
)


def gathered_block_spec(k: int) -> P:
    """Spec of factor k's gathered block-rows: sharded by m{k}, replicated
    over the hyperslice (every processor of it holds S^{(k)}_{p_k})."""
    return P(mode_axis(k), None)


# --------------------------------------------------------------------------
# The per-processor sweep body
# --------------------------------------------------------------------------

def _sweep_local(
    x_loc: jax.Array,
    f_locs: tuple[jax.Array, ...],
    blocks: tuple[jax.Array, ...],
    grams: tuple[jax.Array, ...],
    normx: jax.Array,
    *,
    ndim: int,
    local_fn: LocalFn,
    compute_fit: bool,
    overlap: str = "none",
):
    """One full ALS sweep (all N mode updates) under shard_map.

    Carried state per factor k: the row shard (I_k/P rows), the gathered
    block S^{(k)}_{p_k} (I_k/P_k rows, replicated over the hyperslice), and
    the replicated Gram G_k = A_k^T A_k.  Mirrors ``core.cp_als.update``
    arithmetic exactly (same solve dtype, ridge, λ floor) so the
    distributed fits track the sequential driver to fp32 tolerance.

    ``overlap="ring"`` spells the two per-factor collectives as
    ``ppermute`` rings (:mod:`repro.distributed.ring`) and consumes factor
    ``mode-1``'s ring arrivals chunk-by-chunk inside mode ``mode``'s local
    MTTKRP: chunk t (from ring source ``(me - t) mod q``) multiplies the
    matching slice of ``x_loc`` along axis ``mode-1`` as soon as it lands,
    so each ring hop's transfer can hide behind one slice of compute.  The
    arrivals are pre-normalization (λ is not known until the Gram
    all-reduce completes, and waiting for it would re-serialize the ring),
    so the chunked MTTKRP runs on raw blocks and the result is rescaled by
    ``1/λ`` per column at the end — exact up to rounding, since the MTTKRP
    is linear in each factor column.  Total bytes are unchanged: same
    2-collectives-per-factor model, verified against compiled HLO in
    ``tests/dist_worker.py``.
    """
    ring = overlap == "ring"
    f_locs, blocks, grams = list(f_locs), list(blocks), list(grams)
    rank = f_locs[0].shape[-1]
    dtype = x_loc.dtype
    solve_dtype = jnp.float32 if dtype != jnp.float64 else dtype
    weights = jnp.ones((rank,), dtype)
    b_last = a_last = None
    pending = None  # ring arrivals of factor mode-1, consumed chunk-wise
    for mode in range(ndim):
        gamma = jnp.ones((rank, rank), grams[0].dtype)
        for k in range(ndim):
            if k != mode:
                gamma = gamma * grams[k]
        # MTTKRP: reuse the carried gathered blocks (no gathers here —
        # each was produced by the all-gather after its factor's update)
        if pending is not None:
            parts, lam_prev, q_prev, me_prev = pending
            pending = None
            prev = mode - 1
            w = x_loc.shape[prev] // q_prev
            c = None
            for t, part in enumerate(parts):
                src = arrival_source(me_prev, t, q_prev)
                x_sl = jax.lax.dynamic_slice_in_dim(
                    x_loc, src * w, w, axis=prev
                )
                mats = [
                    blocks[k] if k != mode else None for k in range(ndim)
                ]
                mats[prev] = part
                ct = local_fn(x_sl, mats, mode)
                c = ct if c is None else c + ct
            c = c / lam_prev
        else:
            c = local_fn(
                x_loc,
                [blocks[k] if k != mode else None for k in range(ndim)],
                mode,
            )
        if ring:
            b_loc = ring_reduce_scatter(c, hyperslice_axes(ndim, mode))
        else:
            b_loc = jax.lax.psum_scatter(
                c, hyperslice_axes(ndim, mode),
                scatter_dimension=0, tiled=True,
            )
        # normal-equations solve, rows local (Γ is replicated)
        gamma32 = gamma.astype(solve_dtype)
        ridge = 1e-5 * jnp.trace(gamma32) / rank + 1e-12
        a_loc = jnp.linalg.solve(
            gamma32 + ridge * jnp.eye(rank, dtype=solve_dtype),
            b_loc.astype(solve_dtype).T,
        ).T.astype(dtype)
        # the one all-gather of this factor for the sweep
        if ring:
            axes_g = hyperslice_axes(ndim, mode)
            parts = ring_all_gather_parts(a_loc, axes_g)
            blk = ring_assemble(parts, axes_g)
        else:
            blk = gather_factor(a_loc, ndim, mode)
        # full Gram from the gathered block-rows: one R x R all-reduce over
        # the mode-n fiber (q = P_n), the sweep's only solve collective
        g_raw = jax.lax.psum(blk.T @ blk, (mode_axis(mode),))
        lam = jnp.maximum(
            jnp.sqrt(jnp.maximum(jnp.diagonal(g_raw), 0.0)), 1e-30
        ).astype(dtype)
        a_loc = a_loc / lam
        blk = blk / lam
        grams[mode] = g_raw / (lam[:, None] * lam[None, :])
        f_locs[mode] = a_loc
        blocks[mode] = blk
        if ring and mode < ndim - 1:
            # hand the raw arrivals to mode+1's chunked MTTKRP; λ rides
            # along so the consumer can rescale without a ring barrier
            pending = (parts, lam, ring_size(axes_g), ring_index(axes_g))
        weights = lam
        b_last, a_last = b_loc, a_loc * lam
    if compute_fit:
        inner = jax.lax.psum(
            jnp.sum(b_last * a_last),
            tuple(mode_axis(k) for k in range(ndim)),
        )
        gram_full = jnp.ones((rank, rank), grams[0].dtype)
        for g in grams:
            gram_full = gram_full * g
        gram_full = gram_full * (weights[:, None] * weights[None, :])
        err_sq = jnp.maximum(
            normx**2 - 2 * inner + jnp.sum(gram_full), 0.0
        )
        fit = 1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)
    else:
        fit = jnp.zeros((), dtype)
    return tuple(f_locs), tuple(blocks), tuple(grams), weights, fit


# --------------------------------------------------------------------------
# Program construction and state placement
# --------------------------------------------------------------------------

def build_cp_sweep(
    mesh: jax.sharding.Mesh,
    ndim: int,
    *,
    ctx=None,
    local_fn: LocalFn | None = None,
    compute_fit: bool = True,
) -> Callable:
    """Compile-ready sweep: ``f(x, factors, blocks, grams, normx) ->
    (factors, blocks, grams, weights, fit)`` with every operand in the
    carried distributed state layout (see :func:`place_cp_state`)."""
    from ..engine.context import ExecutionContext

    ctx = ctx if ctx is not None else ExecutionContext.default()
    if RANK_AXIS in mesh.axis_names:
        raise ValueError(
            "the CP-ALS sweep keeps X stationary (Algorithm 3); rank-axis "
            "(p0>1) meshes are for single-mode mttkrp_general"
        )
    if local_fn is None:
        local_fn = engine_local_fn(ctx=ctx)
    overlap = (
        ctx.distribution.overlap if ctx.distribution is not None else "none"
    )
    in_specs = (
        tensor_spec(ndim),
        tuple(factor_spec(ndim, k) for k in range(ndim)),
        tuple(gathered_block_spec(k) for k in range(ndim)),
        tuple(P(None, None) for _ in range(ndim)),
        P(),
    )
    out_specs = (
        in_specs[1],
        in_specs[2],
        in_specs[3],
        P(None),
        P(),
    )
    body = functools.partial(
        _sweep_local, ndim=ndim, local_fn=local_fn,
        compute_fit=compute_fit, overlap=overlap,
    )
    # check_rep=False: the replicated outputs (Grams, weights, fit) are
    # not provable by the manual-axes check, and under
    # backend="pallas"/"auto" the body holds pallas_call
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_rep=False,
        )
    )


def place_cp_state(
    mesh: jax.sharding.Mesh,
    x: jax.Array,
    factors: Sequence[jax.Array],
):
    """Device-put the sweep's carried state: X block-distributed (it never
    moves again; an X already in that layout, as
    :meth:`ExecutionContext.tensor_sharding` gives it, is used as it is),
    factor row shards, gathered block-rows (globally these are just the
    factors, sharded by m{k} only), and replicated Grams."""
    ndim = x.ndim
    target = NamedSharding(mesh, tensor_spec(ndim))
    # an X already in the sweep's layout (jit may write the spec without
    # its unsplit trailing axis) stays where it is: its shards are the
    # blocks the sweep reads, so it is neither copied nor gathered
    placed = isinstance(x, jax.Array) and x.sharding.is_equivalent_to(
        target, ndim)
    xs = x if placed else jax.device_put(x, target)
    fs = tuple(
        jax.device_put(f, NamedSharding(mesh, factor_spec(ndim, k)))
        for k, f in enumerate(factors)
    )
    blocks = tuple(
        jax.device_put(f, NamedSharding(mesh, gathered_block_spec(k)))
        for k, f in enumerate(factors)
    )
    grams = tuple(
        jax.device_put(f.T @ f, NamedSharding(mesh, P(None, None)))
        for f in factors
    )
    return xs, fs, blocks, grams


# --------------------------------------------------------------------------
# The driver
# --------------------------------------------------------------------------

def resolve_cp_mesh(ctx, shape: Sequence[int], rank: int):
    """The distributed context, the grid mesh and the grid the sweep runs
    on for a tensor of ``shape`` at ``rank``: an explicit mesh wins; else
    an explicit (or resolved) grid, validated against the extents; else
    :func:`repro.distributed.grid_select.choose_cp_grid` picks the
    Eq (12) sweep-optimal evenly-sharding grid for ``procs`` (default:
    every available device).  A plain context means "select everything
    automatically".  The driver and
    :meth:`repro.ExecutionContext.tensor_sharding` both resolve here."""
    from dataclasses import replace

    from ..engine.context import Distribution

    if ctx.distribution is None:
        # this driver IS the distributed path; a plain context means
        # "select everything automatically" (re-validates, so tune=True
        # still fails loudly here)
        ctx = replace(ctx, distribution=Distribution())
    if ctx.distribution.p0 != 1:
        raise ValueError(
            "the CP-ALS sweep keeps X stationary (Algorithm 3); rank-axis "
            "(p0>1) contexts are for single-mode mttkrp_general"
        )
    shape = tuple(int(s) for s in shape)
    ndim = len(shape)
    dist = ctx.distribution
    mesh, grid, procs = dist.mesh, dist.grid, dist.procs
    if mesh is None:
        if grid is None:
            procs = procs if procs is not None else len(jax.devices())
            grid = choose_cp_grid(shape, rank, procs).grid
        mesh = make_grid_mesh(grid, dims=shape, rank=rank)
    else:
        if RANK_AXIS in mesh.axis_names:
            raise ValueError(
                "cp_als_parallel keeps X stationary; pass a p0=1 grid mesh"
            )
        grid = tuple(
            mesh.shape[mode_axis(k)]
            for k in range(len([n for n in mesh.axis_names if n != RANK_AXIS]))
        )
        validate_grid(grid, dims=shape, rank=rank)
    if len(grid) != ndim:
        raise ValueError(f"grid {grid} is not {ndim}-way")
    return ctx, mesh, tuple(grid)


def cp_als_parallel(
    x: jax.Array,
    rank: int,
    n_iters: int = 20,
    *,
    key: jax.Array | None = None,
    init_factors: Sequence[jax.Array] | None = None,
    ctx=None,
    tol: float = 0.0,
    compute_fit: bool = True,
) -> CPResult:
    """Distributed CP-ALS with automatic grid selection.

    Grid resolution (all read from ``ctx.distribution``) is
    :func:`resolve_cp_mesh`'s.  An X already in the sweep's layout
    (``ctx.tensor_sharding(x.shape, rank)``) is used in place; any other
    X is block-distributed first.  Factors are
    returned in the same convention as :func:`repro.core.cp_als.cp_als` —
    column-normalized, with the scales in ``CPResult.weights`` (never
    folded in as well).

    Spans (``repro.observe.trace.annotated``): ``repro.cp_als_parallel``
    around the call, ``.place`` (state placement and ``frob_norm``),
    ``.sweep`` (one per sweep, ``step_num`` = sweep), ``.fit`` (the
    fit's host read) and ``.collect`` (the final factor reads).  Host
    counters: ``distributed.sweeps`` and
    ``distributed.modeled_collective_bytes`` (the Eq (12) sweep model,
    :func:`stationary_sweep_words` times the itemsize, per sweep).
    """
    from ..engine.context import ExecutionContext
    from ..observe import trace as _otrace

    ctx = ctx if ctx is not None else ExecutionContext.default()
    with _otrace.annotated("repro.cp_als_parallel"):
        return _cp_als_parallel(
            x, rank, n_iters, key, init_factors, ctx, tol,
            compute_fit or tol > 0,
        )


def _cp_als_parallel(
    x, rank, n_iters, key, init_factors, ctx, tol, with_fit,
) -> CPResult:
    """The host loop of :func:`cp_als_parallel` (context resolved)."""
    from ..observe import trace as _otrace
    from ..observe.metrics import (
        DISTRIBUTED_MODELED_COLLECTIVE_BYTES,
        DISTRIBUTED_SWEEPS,
        registry,
    )
    from .grid_select import stationary_sweep_words

    ctx, mesh, grid = resolve_cp_mesh(ctx, x.shape, rank)
    ndim = x.ndim
    itemsize = int(x.dtype.itemsize)
    modeled = int(stationary_sweep_words(x.shape, rank, grid))

    with _otrace.annotated("repro.cp_als_parallel.place"):
        if init_factors is not None:
            factors = [jnp.asarray(f) for f in init_factors]
        else:
            key = key if key is not None else jax.random.PRNGKey(0)
            factors = random_factors(key, x.shape, rank, x.dtype)
        normx = frob_norm(x)
        sweep = build_cp_sweep(mesh, ndim, ctx=ctx, compute_fit=with_fit)
        xs, fs, blocks, grams = place_cp_state(mesh, x, factors)
        normx_dev = jax.device_put(normx, NamedSharding(mesh, P()))

    if _otrace.should_record(ctx.observe):
        nproc = int(np.prod(grid))
        measured = {}
        if ctx.observe:
            # the context's opt-in: lower the sweep once more and walk
            # its HLO for the actual collective bytes, so the trace
            # carries a measured/modeled pair per the §V-C3 model
            from .hlo import parse_collectives

            text = (
                sweep.lower(xs, fs, blocks, grams, normx_dev)
                .compile().as_text()
            )
            summ = parse_collectives(text)
            measured = {
                "measured_collective_bytes": int(summ.ring_bytes),
                "collectives_by_kind": dict(summ.by_kind()),
            }
        _otrace.record_event(
            "cp_sweep_collectives",
            shape=list(x.shape),
            rank=int(rank),
            grid=list(grid),
            procs=nproc,
            itemsize=itemsize,
            overlap=ctx.distribution.overlap,
            modeled_words=modeled,
            modeled_bytes=modeled * itemsize,
            fit_allreduce_bytes=(
                int(2 * (nproc - 1) / nproc * itemsize) if with_fit else 0
            ),
            **measured,
        )

    fits: list[float] = []
    weights = jnp.ones((rank,), x.dtype)
    reg = registry()
    for it in range(n_iters):
        with _otrace.annotated("repro.cp_als_parallel.sweep", step=it):
            fs, blocks, grams, weights, fit = sweep(
                xs, fs, blocks, grams, normx_dev
            )
            reg.inc(DISTRIBUTED_SWEEPS)
            reg.inc(DISTRIBUTED_MODELED_COLLECTIVE_BYTES, modeled * itemsize)
            if with_fit:
                with _otrace.annotated("repro.cp_als_parallel.fit"):
                    fits.append(float(fit))
        if tol and it > 0 and abs(fits[-1] - fits[-2]) < tol:
            break
    with _otrace.annotated("repro.cp_als_parallel.collect"):
        out_factors = [jnp.asarray(np.asarray(f)) for f in fs]
        out_weights = jnp.asarray(np.asarray(weights))
    return CPResult(out_factors, out_weights, fits)
