"""Kernel-backed dimension trees: all-mode MTTKRP and ALS sweeps.

CP-ALS needs the MTTKRP in *every* mode each sweep. Computing them
independently costs N separate O(N*I*R) contractions; a binary dimension
tree (Phan et al. [13]; the actual CP-ALS bottleneck per Hayashi et al.,
arXiv:1708.08976) shares partial contractions: split the mode set in half,
contract the tensor once with each half's factors, and recurse.

Every tree edge is MTTKRP-shaped (tensor x a subset of the factors'
Khatri-Rao structure), so each one is planned and dispatched through
:func:`repro.engine.execute.contract_partial` under ONE
:class:`~repro.engine.context.ExecutionContext` — with
``ctx.backend == 'pallas'`` the whole sweep runs on the blocked VMEM/MXU
kernels instead of einsum, with the same blocking discipline per partial
contraction.
"""

from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax

from ..observe import trace as _otrace
from .context import ExecutionContext
from .execute import contract_partial, mttkrp


def _solve_tree(
    x: jax.Array,
    factors: Sequence[jax.Array],
    leaf_fn: Callable[[int, jax.Array], None],
    ctx: ExecutionContext,
) -> None:
    """Walk the binary dimension tree, calling ``leaf_fn(mode, b)`` at each
    leaf with that mode's MTTKRP result.

    Ordering is load-bearing for Gauss-Seidel sweeps: a node's *left*
    child partial is contracted (with not-yet-updated right-half factors)
    and fully solved before the *right* child partial is formed, and
    ``contract_partial`` reads ``factors`` at call time — so if ``leaf_fn``
    updates ``factors`` in place, every leaf sees exactly the factors
    plain sequential ALS would use.
    """

    def solve(node, modes, has_rank):
        if len(modes) == 1:
            leaf_fn(modes[0], node)
            return
        half = max(1, len(modes) // 2)
        left, right = modes[:half], modes[half:]
        for child, drop in ((left, right), (right, left)):
            solve(
                contract_partial(
                    node, factors, modes, drop, has_rank, ctx=ctx
                ),
                child, True,
            )

    solve(x, tuple(range(x.ndim)), False)


def all_mode_mttkrp(
    x: jax.Array,
    factors: Sequence[jax.Array],
    *,
    method: str = "dimtree",
    ctx: ExecutionContext | None = None,
) -> list[jax.Array]:
    """MTTKRP in every mode: ``[B^(0), ..., B^(N-1)]``.

    ``method='independent'`` runs N separate MTTKRPs (no reuse);
    ``method='dimtree'`` shares the upper-tree partial contractions
    (~2 tensor-sized contractions per sweep instead of N). Either way each
    contraction goes through the requested engine backend —
    ``ctx.backend == "auto"`` resolves every edge through the autotuner's
    plan cache (see :mod:`repro.tune`).
    """
    ctx = ctx if ctx is not None else ExecutionContext.default()
    n = x.ndim
    if method == "independent":
        return [mttkrp(x, factors, m, ctx=ctx) for m in range(n)]
    if method != "dimtree":
        raise ValueError(
            f"unknown method {method!r}; expected 'dimtree' or "
            f"'independent'"
        )
    if _otrace.should_record(ctx.observe, x, *factors):
        _otrace.record_event(
            "dimtree_sweep",
            shape=list(x.shape),
            rank=int(factors[0].shape[1]),
            backend=ctx.backend,
            n_modes=n,
        )
    results: Dict[int, jax.Array] = {}
    _solve_tree(
        x, factors, lambda mode, b: results.__setitem__(mode, b), ctx
    )
    return [results[m] for m in range(n)]


def dimtree_als_sweep(
    x: jax.Array,
    factors: list[jax.Array],
    update_fn: Callable[[int, jax.Array], jax.Array],
    *,
    ctx: ExecutionContext | None = None,
) -> None:
    """One ALS sweep with dimension-tree reuse, *exactly* matching the
    Gauss-Seidel order of plain ALS.

    ``update_fn(mode, b)`` receives the MTTKRP result for ``mode`` computed
    with all modes < mode already updated (see :func:`_solve_tree` for the
    ordering argument), must return the new factor, and may maintain its
    own side state (grams, weights). ``factors`` is updated in place.
    """
    ctx = ctx if ctx is not None else ExecutionContext.default()

    def leaf(mode: int, b: jax.Array) -> None:
        factors[mode] = update_fn(mode, b)

    _solve_tree(x, factors, leaf, ctx)
