"""CP decomposition drivers: ALS and gradient-based (the paper's §II-A
application context — both are bottlenecked by MTTKRP).

``cp_als``  — alternating least squares with the standard Gram/Hadamard
normal-equations solve; the per-mode MTTKRP may run through any backend
(naive / einsum / blocked / Pallas kernel / distributed Alg 3/4), selected
by the :class:`~repro.engine.context.ExecutionContext` (or injected via
``mttkrp_fn``).

``cp_gradient`` — full-gradient descent (Adam) on 0.5*||X - [[A]]||_F^2 with
the analytic gradient  dL/dA_n = A_n * Γ_n - MTTKRP(X, A, n), Γ_n the
Hadamard product of the other Grams — again MTTKRP-bottlenecked.

Both use the efficient-fit identity
    ||X - recon||^2 = ||X||^2 - 2<B^(N-1), A^(N-1)> + 1^T (Γ ∘ A_N^T A_N) 1
so the full tensor is reconstructed only implicitly.

Configuration: both drivers take ``ctx: ExecutionContext`` — one object
carrying backend/memory/interpret/tune and the Distribution sub-config
(mesh/grid/procs); without one, the process default. Option validation
(backend names, tune x distributed, ...) lives in
:mod:`repro.engine.context`, not here.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, Sequence

import jax
import jax.numpy as jnp

from .tensor import frob_norm, random_factors

if TYPE_CHECKING:  # engine imports stay call-time-only (core <-> engine cycle)
    from ..engine.context import ExecutionContext

MttkrpFn = Callable[[jax.Array, Sequence[jax.Array], int], jax.Array]


@dataclass
class CPResult:
    """A Kruskal-form decomposition: column-normalized ``factors`` plus the
    column scales ``weights`` (λ).  The scales live ONLY here — they are
    never also folded into a factor, so reconstruction applies λ exactly
    once: ``tensor_from_factors(factors, weights)`` (or
    :meth:`reconstruct`)."""

    factors: list[jax.Array]
    weights: jax.Array
    fits: list[float] = field(default_factory=list)

    @property
    def final_fit(self) -> float:
        return self.fits[-1] if self.fits else float("nan")

    def reconstruct(self) -> jax.Array:
        from .tensor import tensor_from_factors

        return tensor_from_factors(self.factors, self.weights)


def _grams(factors: Sequence[jax.Array]) -> list[jax.Array]:
    return [f.T @ f for f in factors]


def _hadamard_except(grams: Sequence[jax.Array], skip: int) -> jax.Array:
    rank = grams[0].shape[0]
    out = jnp.ones((rank, rank), grams[0].dtype)
    for k, g in enumerate(grams):
        if k != skip:
            out = out * g
    return out


def _fit(normx: jax.Array, b_last: jax.Array, a_last: jax.Array,
         gram_had_all: jax.Array) -> jax.Array:
    """1 - ||X - recon|| / ||X|| via the inner-product identity."""
    inner = jnp.sum(b_last * a_last)
    norm_recon_sq = jnp.sum(gram_had_all)
    err_sq = jnp.maximum(normx**2 - 2 * inner + norm_recon_sq, 0.0)
    return 1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)


def cp_als(
    x: jax.Array,
    rank: int,
    n_iters: int = 20,
    key: jax.Array | None = None,
    init_factors: Sequence[jax.Array] | None = None,
    mttkrp_fn: MttkrpFn | None = None,
    *,
    tol: float = 0.0,
    sweep: str = "per_mode",
    ctx: "ExecutionContext | None" = None,
) -> CPResult:
    """CP-ALS. One sweep = for each mode n: B = MTTKRP; solve the normal
    equations A_n = B (Γ_n)^+; column-normalize into weights λ.

    Every MTTKRP goes through the engine under ``ctx``: the backend
    selects einsum / blocked_host / pallas — or ``"auto"`` to resolve
    each contraction through the autotuner's plan cache (``ctx.tune``
    searches and persists on the first sweep's misses; later sweeps and
    runs replay the tuned plans). A custom ``mttkrp_fn`` (e.g. a
    distributed Alg 3/4 shard_map callable) overrides the engine for the
    plain path.

    ``sweep`` selects the sweep schedule: ``"per_mode"`` (the plain N-pass
    Gauss-Seidel chain, the default), ``"dimtree"`` (binary
    dimension-tree reuse), ``"fused"`` (the arXiv:1708.08976
    mode-reuse schedule — 2 tensor passes per sweep, single-dispatch
    (B0, P') pair on the pallas backend; see
    :func:`repro.engine.sweep.fused_als_sweep`), or ``"auto"`` (resolve
    fused-vs-per-mode through the tune cache under ``kind="sweep"`` keys;
    ``ctx.tune`` measures both on the first call and persists the
    winner). All schedules are Gauss-Seidel exact.

    ``ctx.distribution`` runs the stationary-tensor sweep driver instead
    (:func:`repro.distributed.cp_als_parallel.cp_als_parallel`): X is
    block-distributed over an automatically selected Eq (12)-optimal
    processor grid and each sweep is one shard_map program whose local
    MTTKRPs still go through the engine backend. Only ``sweep="per_mode"``
    runs there, and ``mttkrp_fn`` cannot be combined with it."""
    from ..engine.context import ExecutionContext

    ctx = ctx if ctx is not None else ExecutionContext.default()
    if sweep not in ("per_mode", "dimtree", "fused", "auto"):
        raise ValueError(
            f"unknown sweep {sweep!r}; expected 'per_mode', 'dimtree', "
            f"'fused', or 'auto'"
        )
    if ctx.is_distributed:
        if mttkrp_fn is not None:
            raise ValueError(
                "mttkrp_fn cannot be combined with the distributed path "
                "(the sweep driver owns the collectives); drop mttkrp_fn "
                "or the context's distribution"
            )
        if sweep != "per_mode":
            raise ValueError(
                f"sweep={sweep!r} is not supported on the distributed path "
                f"(the stationary sweep already amortizes factor gathers; "
                f"overlap='ring' is its comm/compute-overlap knob)"
            )
        from ..distributed.cp_als_parallel import cp_als_parallel

        return cp_als_parallel(
            x, rank, n_iters, key=key, init_factors=init_factors,
            ctx=ctx, tol=tol,
        )
    from ..observe import trace as _otrace

    with _otrace.annotated("repro.cp_als"):
        return _cp_als_local(
            x, rank, n_iters, key, init_factors, mttkrp_fn, tol, sweep, ctx,
        )


def _cp_als_local(
    x, rank, n_iters, key, init_factors, mttkrp_fn, tol, schedule, ctx,
) -> CPResult:
    """The single-device ALS loop of :func:`cp_als` (options checked)."""
    from ..observe import trace as _otrace

    n = x.ndim
    if init_factors is not None:
        factors = [jnp.asarray(f) for f in init_factors]
    else:
        key = key if key is not None else jax.random.PRNGKey(0)
        factors = random_factors(key, x.shape, rank, x.dtype)
    normx = frob_norm(x)
    grams = _grams(factors)
    fits: list[float] = []
    weights = jnp.ones((rank,), x.dtype)
    state: dict = {}

    def update(mode: int, b: jax.Array) -> jax.Array:
        nonlocal weights
        with _otrace.annotated("repro.cp_als.update"):
            gamma = _hadamard_except(grams, mode)
            # solve A_n Γ = B  (Γ is PSD; ridge for rank-deficiency safety)
            solve_dtype = jnp.float32 if x.dtype != jnp.float64 else x.dtype
            gamma32 = gamma.astype(solve_dtype)
            # ridge scaled to f32 conditioning; essential when rank exceeds
            # the true tensor rank (Γ singular)
            ridge = 1e-5 * jnp.trace(gamma32) / rank + 1e-12
            a_new = jnp.linalg.solve(
                gamma32 + ridge * jnp.eye(rank, dtype=solve_dtype),
                b.astype(solve_dtype).T,
            ).T.astype(x.dtype)
            # column normalization
            lam = jnp.maximum(jnp.linalg.norm(a_new, axis=0), 1e-30)
            a_new = a_new / lam
            weights = lam.astype(x.dtype)
            grams[mode] = a_new.T @ a_new
            state.update(b_last=b, a_last=a_new * weights, g_last=mode)
            return a_new

    from ..engine import execute as engine_execute
    from ..engine.sweep import fused_als_sweep
    from ..engine.tree import dimtree_als_sweep

    if mttkrp_fn is None:
        def mttkrp_fn(t, fs, mode):
            return engine_execute.mttkrp(t, fs, mode, ctx=ctx)

    if schedule == "auto":
        from ..tune.search import _is_concrete, resolve_sweep, tune_sweep

        if ctx.tune and _is_concrete(x):
            tune_sweep(
                x, rank, ctx=ctx, memory=ctx.memory,
                interpret=ctx.interpret, cache=ctx.plan_cache(),
            )
        schedule = resolve_sweep(
            x.shape, rank, x.dtype, ctx.memory, cache=ctx.plan_cache()
        ).variant

    for it in range(n_iters):
        t_sweep = _otrace.now_ns()
        with _otrace.annotated("repro.cp_als.sweep", step=it):
            if schedule == "dimtree":
                dimtree_als_sweep(x, factors, update, ctx=ctx)
            elif schedule == "fused":
                fused_als_sweep(x, factors, update, ctx=ctx)
            else:
                for mode in range(n):
                    factors[mode] = update(mode, mttkrp_fn(x, factors, mode))
            with _otrace.annotated("repro.cp_als.fit"):
                gram_full = _hadamard_except(grams, -1) * jnp.outer(
                    weights, weights
                )
                b_last, a_last = state["b_last"], state["a_last"]
                fit = float(_fit(normx, b_last, a_last, gram_full))
            fits.append(fit)
            delta = abs(fits[-1] - fits[-2]) if it > 0 else None
            converged = bool(tol and it > 0 and delta < tol)
            # float(_fit) above forces concreteness, so this loop never
            # runs under a jax trace — no tracer guard needed here.
            if _otrace.should_record(ctx.observe):
                _otrace.record_event(
                    "cp_als_iter",
                    start_ns=t_sweep,
                    shape=list(x.shape),
                    rank=int(rank),
                    schedule=schedule,
                    it=it,
                    fit=fit,
                    fit_delta=delta,
                    weights=weights,
                    converged=converged,
                )
        if converged:
            break
    # Kruskal form: factors stay column-normalized, λ is returned ONLY in
    # CPResult.weights.  (It used to be folded into the last-updated factor
    # *and* returned, so reconstructing with weights scaled by λ twice.)
    return CPResult(factors, weights, fits)


def cp_gradient(
    x: jax.Array,
    rank: int,
    n_iters: int = 200,
    lr: float = 0.05,
    key: jax.Array | None = None,
    mttkrp_fn: MttkrpFn | None = None,
    *,
    ctx: "ExecutionContext | None" = None,
) -> CPResult:
    """Gradient-based CP (Adam on the analytic MTTKRP gradient).

    Engine parity with :func:`cp_als`: every MTTKRP goes through
    ``engine.execute.mttkrp`` under the same ``ctx``
    (backend/memory/interpret/tune). An explicit ``mttkrp_fn`` still
    overrides."""
    n = x.ndim
    if mttkrp_fn is None:
        from ..engine import execute as engine_execute

        def mttkrp_fn(t, fs, mode):
            return engine_execute.mttkrp(t, fs, mode, ctx=ctx)
    key = key if key is not None else jax.random.PRNGKey(0)
    factors = random_factors(key, x.shape, rank, x.dtype)
    normx = frob_norm(x)
    m = [jnp.zeros_like(f) for f in factors]
    v = [jnp.zeros_like(f) for f in factors]
    b1, b2, eps = 0.9, 0.999, 1e-8
    fits: list[float] = []
    for it in range(1, n_iters + 1):
        grams = _grams(factors)
        grads = []
        for mode in range(n):
            b = mttkrp_fn(x, factors, mode)
            gamma = _hadamard_except(grams, mode)
            grads.append(factors[mode] @ gamma - b)
        for k in range(n):
            m[k] = b1 * m[k] + (1 - b1) * grads[k]
            v[k] = b2 * v[k] + (1 - b2) * jnp.square(grads[k])
            mhat = m[k] / (1 - b1**it)
            vhat = v[k] / (1 - b2**it)
            factors[k] = factors[k] - lr * mhat / (jnp.sqrt(vhat) + eps)
        if it % 10 == 0 or it == n_iters:
            grams = _grams(factors)
            b = mttkrp_fn(x, factors, n - 1)
            gram_full = _hadamard_except(grams, -1)
            fits.append(float(_fit(normx, b, factors[n - 1], gram_full)))
    return CPResult(factors, jnp.ones((rank,), x.dtype), fits)
