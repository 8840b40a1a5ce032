"""Dimension-tree multi-mode MTTKRP (paper §VII outlook; Phan et al. [13]).

CP-ALS needs the MTTKRP in *every* mode each sweep. Computing them
independently costs N separate O(N*I*R) contractions; a dimension tree
shares partial contractions: split the mode set in half, contract the
tensor once with each half's factors, and recurse. Asymptotically ~2
tensor-sized contractions per sweep instead of N, with the same
communication pattern per contraction (each partial contraction is itself
MTTKRP-like and is blocked / distributed by the same machinery).

The tree execution lives in :mod:`repro.engine.tree` — each partial
contraction is planned and dispatched through the engine's backends
(einsum or the blocked Pallas kernels). This module keeps the historical
entry points plus the analytic flop models.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Sequence

import jax

if TYPE_CHECKING:  # engine imports stay call-time-only (core <-> engine cycle)
    from ..engine.context import ExecutionContext


def all_mode_mttkrp_dimtree(
    x: jax.Array,
    factors: Sequence[jax.Array],
    *,
    ctx: "ExecutionContext | None" = None,
) -> list[jax.Array]:
    """All-mode MTTKRP via a binary dimension tree.

    Returns ``[B^(0), ..., B^(N-1)]`` identical (up to roundoff) to
    ``[mttkrp(x, factors, n) for n in range(N)]`` with ~half the flops for
    N=3,4 and asymptotically fewer for larger N. ``ctx.backend='pallas'``
    runs every partial contraction on the blocked kernels.
    """
    from ..engine.tree import all_mode_mttkrp

    return all_mode_mttkrp(x, factors, method="dimtree", ctx=ctx)


def dimtree_als_sweep(
    x: jax.Array,
    factors: list[jax.Array],
    update_fn,
    *,
    ctx: "ExecutionContext | None" = None,
) -> None:
    """One ALS sweep with dimension-tree reuse, *exactly* matching the
    Gauss-Seidel order of plain ALS (see :mod:`repro.engine.tree` for the
    ordering argument). ``factors`` is updated in place."""
    from ..engine.tree import dimtree_als_sweep as engine_sweep

    engine_sweep(x, factors, update_fn, ctx=ctx)


def dimtree_flops(dims: Sequence[int], rank: int) -> int:
    """Exact multiply-add count of one dimension-tree sweep.

    Each einsum contraction pairs the dropped factors one at a time; a
    pairing that drops mode ``m`` from a node with remaining mode sizes
    ``cur`` costs ``prod(cur) * R`` multiply-adds (every surviving
    element-and-rank pair sums over ``m``) — whether the rank axis is
    already materialized on the node (elementwise along r) or appears with
    this first pairing. Volumes shrink *exactly* per the dims dropped, not
    by a geometric-mean model. Compare against naive all-mode MTTKRP:
    ``N * (N-1) * I * R`` multiply-adds.
    """
    total = 0

    def contract_cost(sizes: tuple[int, ...], drop: tuple[int, ...]) -> int:
        # `sizes` are the node's mode sizes in order; `drop` indexes into
        # it. Each pairing costs prod(remaining)*R multiply-adds regardless
        # of whether the rank axis is already materialized; the drop ORDER
        # does matter, and einsum's 'optimal' path drops the largest mode
        # first (shrinking the node fastest minimizes the rest).
        cost = 0
        cur = list(sizes)
        for s in sorted((sizes[m] for m in drop), reverse=True):
            vol = 1
            for c in cur:
                vol *= c
            cost += vol * rank
            cur.remove(s)
        return cost

    def rec(sizes: tuple[int, ...]):
        nonlocal total
        if len(sizes) == 1:
            return
        half = max(1, len(sizes) // 2)
        total += contract_cost(sizes, tuple(range(half, len(sizes))))
        total += contract_cost(sizes, tuple(range(half)))
        rec(sizes[:half])
        rec(sizes[half:])

    rec(tuple(dims))
    return total


def dimtree_intermediate_words(dims: Sequence[int], rank: int) -> int:
    """Total words of every internal tree node (the reuse working set).

    Rank-augmented nodes hold ``prod(dims) * R`` words — the quantity the
    old geometric-mean model under-counted; the root holds ``prod(dims)``.
    """
    total = 0

    def rec(sizes: tuple[int, ...], has_rank: bool):
        nonlocal total
        vol = 1
        for s in sizes:
            vol *= s
        total += vol * (rank if has_rank else 1)
        if len(sizes) == 1:
            return
        half = max(1, len(sizes) // 2)
        rec(sizes[:half], True)
        rec(sizes[half:], True)

    rec(tuple(dims), False)
    return total


def naive_all_mode_flops(dims: Sequence[int], rank: int) -> int:
    """N independent MTTKRPs, each N-1 pairwise contractions of I*R."""
    n = len(dims)
    vol = 1
    for d in dims:
        vol *= d
    return n * (n - 1) * vol * rank
