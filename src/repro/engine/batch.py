"""Batched execution: decompose B tensors with ONE plan and ONE program.

The paper's blocked algorithms win because factor-matrix traffic is
amortized against tensor reads (Eq 9/10).  A *batch* of tensors sharing
one :class:`~repro.engine.plan.BlockPlan` amortizes everything above the
arithmetic the same way — the plan choice, the autotune-cache lookup,
and the XLA compilation are paid once per *bucket* of identically-shaped
problems instead of once per request.  This module is the engine half of
the serving story (:mod:`repro.launch.serve` is the queue half):

* :func:`batched_choose_blocks` — the batched planner entry: the block
  choice for a stack of B tensors IS the element plan.  The batch axis
  is vmapped over, never tiled, so the Eq-9 working set (and therefore
  the chosen blocks) is B-independent by construction.  The static
  verifier (``repro.verify`` rule ``batched-plan-divergence``) proves
  this over the plan lattice.
* :func:`cp_als_batched` / :func:`tucker_hooi_batched` — vmapped sweep
  drivers over stacks of tensors: every per-mode MTTKRP / Multi-TTM of
  a sweep is ONE batched engine dispatch (``jax.vmap`` over the shared
  resolved plan — one kernel launch for B requests on the pallas
  backend), the Gram/solve/eigh tails run batched, and a per-element
  convergence mask freezes early-converged entries (their factors stop
  changing, their iteration counters stop, and the whole loop exits as
  soon as every element has converged).

The batched engine *dispatch* itself (a leading B axis on
``repro.mttkrp`` / ``repro.multi_ttm`` / ``repro.contract_partial``)
lives in :mod:`repro.engine.execute`; the drivers here consume it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp

from .plan import BlockPlan, Memory, choose_blocks

if TYPE_CHECKING:  # core <-> engine cycle stays call-time-only
    from ..core.cp_als import CPResult
    from ..core.tucker import TuckerResult
    from .context import ExecutionContext


def batched_choose_blocks(
    batch: int,
    shape: Sequence[int],
    rank: int,
    itemsize: int,
    *,
    memory: Memory | None = None,
    x_has_rank: bool = False,
) -> BlockPlan:
    """The block plan a batched dispatch of B element-problems runs under.

    Batching is ``jax.vmap`` over the element contraction: the batch
    axis becomes a kernel *grid* dimension (one program instance per
    element), so no block ever spans two elements and the per-instance
    Eq-9 working set is exactly the element working set.  The correct
    plan for any ``batch >= 1`` is therefore the element plan,
    unchanged — this function documents (and the ``repro.verify``
    ``batched-plan-divergence`` rule enforces) that batching never
    changes the block choice.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    return choose_blocks(
        shape, rank, itemsize, memory=memory, x_has_rank=x_has_rank
    )


# ---------------------------------------------------------------------------
# Batched CP-ALS
# ---------------------------------------------------------------------------

@dataclass
class BatchedCPResult:
    """B Kruskal-form decompositions from one batched run.

    ``factors[k]`` is ``(B, I_k, R)`` (column-normalized per element),
    ``weights`` is ``(B, R)`` (λ per element), ``fits`` is ``(B,)``
    (final fit per element), ``n_iters`` is ``(B,)`` (sweeps each
    element actually *updated* — a converged element's counter freezes),
    and ``converged`` is ``(B,)`` bool.  ``result(b)`` crops element
    ``b`` back out as a plain :class:`~repro.core.cp_als.CPResult`.
    """

    factors: list[jax.Array]
    weights: jax.Array
    fits: jax.Array
    n_iters: jax.Array
    converged: jax.Array
    fit_history: list[jax.Array] = field(default_factory=list)

    @property
    def batch(self) -> int:
        return int(self.weights.shape[0])

    def result(self, b: int) -> "CPResult":
        """Element ``b`` as a plain :class:`CPResult` (fit history
        truncated to the sweeps that ran before the whole batch
        stopped)."""
        from ..core.cp_als import CPResult

        return CPResult(
            [f[b] for f in self.factors],
            self.weights[b],
            [float(h[b]) for h in self.fit_history],
        )


def _batched_grams(factors: Sequence[jax.Array]) -> list[jax.Array]:
    return [jnp.einsum("bir,bis->brs", f, f) for f in factors]


def _batched_hadamard_except(
    grams: Sequence[jax.Array], skip: int
) -> jax.Array:
    rank = grams[0].shape[-1]
    out = jnp.ones((grams[0].shape[0], rank, rank), grams[0].dtype)
    for k, g in enumerate(grams):
        if k != skip:
            out = out * g
    return out


def _batched_fit(normx, b_last, a_last, gram_had_all):
    """Per-element fit via the inner-product identity (no reconstruction):
    ``1 - ||X_b - recon_b|| / ||X_b||`` for every element at once."""
    inner = jnp.sum(b_last * a_last, axis=(1, 2))
    norm_recon_sq = jnp.sum(gram_had_all, axis=(1, 2))
    err_sq = jnp.maximum(normx**2 - 2 * inner + norm_recon_sq, 0.0)
    return 1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)


def cp_als_batched(
    x: jax.Array,
    rank: int,
    n_iters: int = 20,
    key: jax.Array | None = None,
    init_factors: Sequence[jax.Array] | None = None,
    tol: float = 0.0,
    *,
    ctx: "ExecutionContext | None" = None,
) -> BatchedCPResult:
    """CP-ALS over a stack of B same-shaped tensors, one plan for all.

    ``x`` is ``(B, I_0, ..., I_{N-1})``.  Each sweep's per-mode MTTKRP
    is ONE batched engine dispatch (``repro.mttkrp`` with the leading
    batch axis: the ``backend="auto"`` resolution, the plan choice, and
    — on the pallas backend — the kernel launch happen once per call,
    not once per element); the Gram/solve/normalize tail runs batched
    through ``jnp.linalg``.  ``init_factors[k]`` may be ``(B, I_k, R)``
    (per-element inits) and overrides ``key``.

    ``tol`` enables per-element convergence: an element whose fit
    improvement falls below ``tol`` is *frozen* — its factors, weights,
    and fit stop changing and its ``n_iters`` counter stops — while the
    rest of the batch keeps iterating; the loop exits as soon as every
    element has converged.  Numerics match a Python loop of
    single-tensor :func:`repro.cp_als` calls with the same inits (the
    property suite in ``tests/test_batched.py`` pins this
    differentially).
    """
    from ..engine.context import ExecutionContext

    if ctx is None:
        ctx = ExecutionContext.default()
    if x.ndim < 3:
        raise ValueError(
            f"cp_als_batched needs a batch of >=2-way tensors "
            f"(B, I_0, ..., I_N-1); got shape {tuple(x.shape)}"
        )
    if ctx.is_distributed:
        raise ValueError(
            "cp_als_batched is the single-process batched driver; "
            "distributed contexts run repro.cp_als per tensor (the "
            "stationary sweep owns the collectives)"
        )
    from ..observe import trace as _otrace

    with _otrace.annotated("repro.cp_als_batched"):
        return _cp_als_batched(x, rank, n_iters, key, init_factors, tol, ctx)


def _cp_als_batched(x, rank, n_iters, key, init_factors, tol, ctx):
    """The batched ALS loop of :func:`cp_als_batched` (checked)."""
    from ..observe import trace as _otrace
    from . import execute as engine_execute

    batch, dims = x.shape[0], x.shape[1:]
    n = len(dims)
    if init_factors is not None:
        factors = [jnp.asarray(f) for f in init_factors]
        for k, f in enumerate(factors):
            if f.shape != (batch, dims[k], rank):
                raise ValueError(
                    f"init_factors[{k}] must be (B, I_k, R) = "
                    f"({batch}, {dims[k]}, {rank}), got {tuple(f.shape)}"
                )
    else:
        from ..core.tensor import random_factors

        key = key if key is not None else jax.random.PRNGKey(0)
        keys = jax.random.split(key, batch)
        factors = [
            jnp.stack(f) for f in zip(*[
                random_factors(k, dims, rank, x.dtype) for k in keys
            ])
        ]

    normx = jnp.sqrt(
        jnp.sum(jnp.square(x.astype(jnp.float32)), axis=tuple(range(1, n + 1)))
    )
    grams = _batched_grams(factors)
    weights = jnp.ones((batch, rank), x.dtype)
    converged = jnp.zeros((batch,), bool)
    iters_run = jnp.zeros((batch,), jnp.int32)
    fits = jnp.zeros((batch,), jnp.float32)
    fit_history: list[jax.Array] = []
    solve_dtype = jnp.float32 if x.dtype != jnp.float64 else x.dtype
    eye = jnp.eye(rank, dtype=solve_dtype)
    state: dict = {}

    def update(mode: int, b: jax.Array, active: jax.Array):
        """One batched mode update, frozen where ``active`` is False."""
        nonlocal weights
        with _otrace.annotated("repro.cp_als_batched.update"):
            gamma = _batched_hadamard_except(grams, mode).astype(solve_dtype)
            ridge = (
                1e-5 * jnp.trace(gamma, axis1=1, axis2=2) / rank + 1e-12
            )[:, None, None]
            a_new = jnp.linalg.solve(
                gamma + ridge * eye,
                jnp.swapaxes(b.astype(solve_dtype), 1, 2),
            )
            a_new = jnp.swapaxes(a_new, 1, 2).astype(x.dtype)
            lam = jnp.maximum(jnp.linalg.norm(a_new, axis=1), 1e-30)
            a_new = a_new / lam[:, None, :]
            # the convergence mask: frozen elements keep their old
            # factors, weights, and Grams bit-for-bit
            a_new = jnp.where(active[:, None, None], a_new, factors[mode])
            weights = jnp.where(
                active[:, None], lam.astype(x.dtype), weights
            )
            grams[mode] = jnp.einsum("bir,bis->brs", a_new, a_new)
            state.update(
                b_last=b, a_last=a_new * weights[:, None, :], mode=mode
            )
            return a_new

    for it in range(n_iters):
        t_sweep = _otrace.now_ns()
        with _otrace.annotated("repro.cp_als_batched.sweep", step=it):
            active = ~converged
            for mode in range(n):
                # ONE batched engine dispatch for all B elements
                b = engine_execute.mttkrp(x, factors, mode, ctx=ctx)
                factors[mode] = update(mode, b, active)
            with _otrace.annotated("repro.cp_als_batched.fit"):
                gram_full = _batched_hadamard_except(grams, -1) * jnp.einsum(
                    "br,bs->brs", weights, weights
                )
                new_fits = _batched_fit(
                    normx, state["b_last"], state["a_last"], gram_full
                )
                new_fits = jnp.where(active, new_fits, fits)
                delta = jnp.abs(new_fits - fits)
                fits = new_fits
                fit_history.append(fits)
                iters_run = iters_run + active.astype(jnp.int32)
                if tol and it > 0:
                    converged = converged | (active & (delta < tol))
            if _otrace.should_record(ctx.observe):
                # arrays, read when the trace is: recording never syncs
                _otrace.record_event(
                    "cp_als_batched_iter",
                    start_ns=t_sweep,
                    batch=int(batch),
                    shape=list(dims),
                    rank=int(rank),
                    it=it,
                    fits=fits,
                    converged=converged,
                )
            if tol and bool(converged.all()):
                break
    return BatchedCPResult(
        factors, weights, fits, iters_run, converged, fit_history
    )


# ---------------------------------------------------------------------------
# Batched Tucker/HOOI
# ---------------------------------------------------------------------------

@dataclass
class BatchedTuckerResult:
    """B Tucker decompositions from one batched HOOI run: ``core`` is
    ``(B, R_1, ..., R_N)``, ``factors[k]`` is ``(B, I_k, R_k)``
    (orthonormal columns per element), ``fits``/``n_iters``/
    ``converged`` are per-element as in :class:`BatchedCPResult`."""

    core: jax.Array
    factors: list[jax.Array]
    fits: jax.Array
    n_iters: jax.Array
    converged: jax.Array

    @property
    def batch(self) -> int:
        return int(self.core.shape[0])

    @property
    def ranks(self) -> tuple[int, ...]:
        return tuple(self.core.shape[1:])

    def result(self, b: int) -> "TuckerResult":
        """Element ``b`` as a plain
        :class:`~repro.core.tucker.TuckerResult`."""
        from ..core.tucker import TuckerResult

        return TuckerResult(
            self.core[b], [f[b] for f in self.factors], [float(self.fits[b])]
        )


def tucker_hooi_batched(
    x: jax.Array,
    ranks: Sequence[int],
    n_iters: int = 10,
    *,
    ctx: "ExecutionContext | None" = None,
    init_factors: Sequence[jax.Array] | None = None,
    tol: float = 0.0,
) -> BatchedTuckerResult:
    """Tucker/HOOI over a stack of B same-shaped tensors, one plan for
    all.  ``x`` is ``(B, I_1, ..., I_N)``; each HOOI mode update is ONE
    batched Multi-TTM dispatch (``repro.multi_ttm`` with the leading
    batch axis) followed by a batched Gram eigendecomposition;
    initialization is per-element HOSVD (``init_factors[k]`` of shape
    ``(B, I_k, R_k)`` overrides).  ``tol`` freezes converged elements
    exactly as in :func:`cp_als_batched`.  Numerics match a loop of
    single-tensor :func:`repro.tucker_hooi` calls (pinned
    differentially in ``tests/test_batched.py``)."""
    from ..core.tucker import _check_ranks, _leading_eigvecs, hosvd_init
    from ..engine.context import ExecutionContext
    from ..observe import trace as _otrace
    from . import execute as engine_execute

    if ctx is None:
        ctx = ExecutionContext.default()
    if x.ndim < 3:
        raise ValueError(
            f"tucker_hooi_batched needs a batch of >=2-way tensors "
            f"(B, I_1, ..., I_N); got shape {tuple(x.shape)}"
        )
    if ctx.is_distributed:
        raise ValueError(
            "tucker_hooi_batched is the single-process batched driver; "
            "distributed contexts run repro.tucker_hooi per tensor"
        )
    batch, dims = x.shape[0], x.shape[1:]
    n = len(dims)
    ranks = _check_ranks(dims, ranks)
    if init_factors is not None:
        factors = [jnp.asarray(f) for f in init_factors]
        for k, f in enumerate(factors):
            if f.shape != (batch, dims[k], ranks[k]):
                raise ValueError(
                    f"init_factors[{k}] must be (B, I_k, R_k) = "
                    f"({batch}, {dims[k]}, {ranks[k]}), got {tuple(f.shape)}"
                )
    else:
        factors = jax.vmap(lambda xb: hosvd_init(xb, ranks))(x)
    normx = jnp.sqrt(
        jnp.sum(jnp.square(x.astype(jnp.float32)), axis=tuple(range(1, n + 1)))
    )
    converged = jnp.zeros((batch,), bool)
    iters_run = jnp.zeros((batch,), jnp.int32)
    fits = jnp.zeros((batch,), jnp.float32)
    core = None

    def _batched_eigvecs(ym: jax.Array, r: int) -> jax.Array:
        gram = jnp.einsum("bij,bkj->bik", ym, ym)
        return jax.vmap(lambda g: _leading_eigvecs(g, r))(gram)

    for it in range(n_iters):
        active = ~converged
        y = x
        for k in range(n):
            # ONE batched Multi-TTM dispatch for all B elements
            y = engine_execute.multi_ttm(
                x, [None if j == k else factors[j] for j in range(n)],
                keep=k, ctx=ctx,
            )
            ym = jnp.moveaxis(y, k + 1, 1).reshape(batch, dims[k], -1)
            a_new = _batched_eigvecs(ym, ranks[k]).astype(x.dtype)
            factors[k] = jnp.where(active[:, None, None], a_new, factors[k])
        # the core falls out of the last mode update (batched ttm)
        new_core = jnp.moveaxis(
            jnp.einsum("b...i,bir->b...r", jnp.moveaxis(y, n, x.ndim - 1),
                       factors[n - 1]),
            x.ndim - 1, n,
        )
        core = new_core if core is None else jnp.where(
            active.reshape((batch,) + (1,) * n), new_core, core
        )
        core_norm = jnp.sqrt(jnp.sum(
            jnp.square(core.astype(jnp.float32)),
            axis=tuple(range(1, n + 1)),
        ))
        err_sq = jnp.maximum(normx**2 - core_norm**2, 0.0)
        new_fits = 1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)
        new_fits = jnp.where(active, new_fits, fits)
        delta = jnp.abs(new_fits - fits)
        fits = new_fits
        iters_run = iters_run + active.astype(jnp.int32)
        if tol and it > 0:
            converged = converged | (active & (delta < tol))
        if _otrace.should_record(ctx.observe):
            _otrace.record_event(
                "tucker_batched_iter",
                batch=int(batch),
                shape=list(dims),
                ranks=list(ranks),
                it=it,
                fits=fits,
                converged=converged,
            )
        if tol and bool(converged.all()):
            break
    return BatchedTuckerResult(core, factors, fits, iters_run, converged)
