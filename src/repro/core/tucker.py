"""Tucker decomposition drivers: HOSVD initialization and HOOI sweeps.

The Tucker/HOSVD workload is the second MTTKRP-class kernel the engine
serves (arXiv:2207.10437): every HOOI mode update is a Multi-TTM

    Y^(k) = X x_{j != k} A_j^T        (the kept-mode partial contraction)

followed by a small eigendecomposition of the unfolding Gram, and the
core is the full contraction ``G = X x_1 A_1^T ... x_N A_N^T``.  Both
run through :func:`repro.engine.execute.multi_ttm` under one
:class:`~repro.engine.context.ExecutionContext`, so the backend
(einsum / blocked_host / the Pallas Kronecker kernel / ``"auto"``) and
memory budget are chosen exactly once — the same contract the CP drivers
follow.

Fit uses the orthonormal-factor identity
``||X - [[G; A_1..A_N]]||^2 = ||X||^2 - ||G||^2``, so the full tensor is
never reconstructed during iteration.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp

from .tensor import frob_norm

if TYPE_CHECKING:  # engine imports stay call-time-only (core <-> engine cycle)
    from ..engine.context import ExecutionContext


@dataclass
class TuckerResult:
    """A Tucker decomposition: ``core`` of shape ``(R_1, ..., R_N)`` and
    orthonormal ``factors`` (``A_k`` of shape ``(I_k, R_k)``, columns
    orthonormal), plus the per-sweep ``fits``."""

    core: jax.Array
    factors: list[jax.Array]
    fits: list[float] = field(default_factory=list)

    @property
    def final_fit(self) -> float:
        return self.fits[-1] if self.fits else float("nan")

    @property
    def ranks(self) -> tuple[int, ...]:
        return tuple(self.core.shape)

    def reconstruct(self) -> jax.Array:
        """Full tensor ``G x_1 A_1 ... x_N A_N``."""
        out = self.core
        for k, a in enumerate(self.factors):
            out = ttm(out, a, k, transpose=False)
        return out


def ttm(
    x: jax.Array, a: jax.Array, mode: int, transpose: bool = True
) -> jax.Array:
    """Single tensor-times-matrix: contract tensor mode ``mode`` with
    ``a`` — ``A^T`` applied (``transpose=True``, extent ``I_k -> R_k``,
    the Multi-TTM building block) or ``A`` applied (``transpose=False``,
    ``R_k -> I_k``, reconstruction direction)."""
    axes = ((mode,), (0,) if transpose else (1,))
    out = jnp.tensordot(x, a, axes=axes)
    # tensordot appends the matrix's free axis; rotate it back into place
    return jnp.moveaxis(out, -1, mode)


def _fix_signs(v: jax.Array) -> jax.Array:
    """Deterministic eigenvector sign convention: the largest-magnitude
    entry of every column is made positive (eigh's signs are arbitrary;
    pinning them keeps sequential and distributed sweeps bit-comparable)."""
    idx = jnp.argmax(jnp.abs(v), axis=0)
    signs = jnp.sign(v[idx, jnp.arange(v.shape[1])])
    return v * jnp.where(signs == 0, 1.0, signs)


def _leading_eigvecs(gram: jax.Array, r: int) -> jax.Array:
    """Top-``r`` eigenvectors of a PSD Gram (ascending eigh, reversed),
    with the deterministic sign convention."""
    _, v = jnp.linalg.eigh(gram.astype(jnp.float32))
    return _fix_signs(v[:, ::-1][:, :r])


def _unfold_rows(z: jax.Array, mode: int) -> jax.Array:
    """Mode-``mode``-rows unfolding ``(I_mode, prod rest)`` (row-Gram
    ordering is irrelevant as long as it is consistent)."""
    return jnp.moveaxis(z, mode, 0).reshape(z.shape[mode], -1)


def hosvd_init(
    x: jax.Array, ranks: Sequence[int], dtype=jnp.float32
) -> list[jax.Array]:
    """HOSVD factors: the top-``R_k`` left singular vectors of every
    unfolding ``X_(k)``, via the ``I_k x I_k`` Gram eigendecomposition.

    Each Gram ``X_(k) X_(k)^T`` is one contraction over the other modes;
    no unfolding is materialized, so the device holds at most one
    compiler-made copy of ``x`` beside it."""
    factors = []
    for k, r in enumerate(ranks):
        others = tuple(a for a in range(x.ndim) if a != k)
        gram = jnp.tensordot(x, x, axes=(others, others))
        factors.append(_leading_eigvecs(gram, int(r)).astype(x.dtype))
    return factors


def _check_ranks(shape: Sequence[int], ranks: Sequence[int]) -> tuple[int, ...]:
    ranks = tuple(int(r) for r in ranks)
    if len(ranks) != len(shape):
        raise ValueError(
            f"Tucker ranks {ranks} must give one rank per tensor mode "
            f"({len(shape)} for shape {tuple(shape)})"
        )
    for k, (r, d) in enumerate(zip(ranks, shape)):
        if not 1 <= r <= d:
            raise ValueError(
                f"Tucker rank R_{k}={r} out of range [1, I_{k}={d}]"
            )
    return ranks


def tucker_hooi(
    x: jax.Array,
    ranks: Sequence[int],
    n_iters: int = 10,
    *,
    ctx: "ExecutionContext | None" = None,
    init_factors: Sequence[jax.Array] | None = None,
    tol: float = 0.0,
) -> TuckerResult:
    """Tucker decomposition by HOOI (higher-order orthogonal iteration).

    One sweep = for each mode k: ``Y = multi_ttm(x, factors, keep=k)``,
    then ``A_k`` = top-``R_k`` eigenvectors of ``Y_(k) Y_(k)^T``.  Every
    Multi-TTM goes through the engine under ``ctx`` (einsum /
    blocked_host / the Pallas Kronecker kernel, or ``"auto"`` to resolve
    each contraction through the tune cache's ``kind="multi_ttm"``
    entries — a context pinned via
    ``ExecutionContext.for_problem(shape, ranks)`` replays its stored
    decisions).  A distributed context routes to the stationary-tensor
    sweep driver
    (:func:`repro.distributed.tucker_parallel.tucker_hooi_parallel`): X
    is block-distributed over a Multi-TTM-sweep-optimal processor grid
    and each sweep is one shard_map program.

    Initialization is HOSVD (``init_factors`` overrides).  ``tol`` stops
    early when the fit improvement between sweeps falls below it.
    Returns a :class:`TuckerResult` (orthonormal factors, core, fits).
    """
    from ..engine.context import ExecutionContext

    if ctx is None:
        ctx = ExecutionContext.default()
    ranks = _check_ranks(x.shape, ranks)
    if ctx.is_distributed:
        from ..distributed.tucker_parallel import tucker_hooi_parallel

        return tucker_hooi_parallel(
            x, ranks, n_iters, ctx=ctx, init_factors=init_factors, tol=tol
        )
    from ..observe import trace as _otrace

    with _otrace.annotated("repro.tucker_hooi"):
        return _tucker_hooi_local(x, ranks, n_iters, init_factors, tol, ctx)


def _tucker_hooi_local(x, ranks, n_iters, init_factors, tol, ctx):
    """The single-device HOOI loop of :func:`tucker_hooi` (checked)."""
    from ..engine import execute as engine_execute
    from ..observe import trace as _otrace

    n = x.ndim
    if init_factors is not None:
        factors = [jnp.asarray(f) for f in init_factors]
    else:
        with _otrace.annotated("repro.tucker.hosvd_init"):
            factors = hosvd_init(x, ranks)
    normx = frob_norm(x)
    fits: list[float] = []
    if n_iters < 1:  # HOSVD only: just project onto the initial factors
        core = engine_execute.multi_ttm(x, factors, keep=None, ctx=ctx)
        err_sq = jnp.maximum(normx**2 - frob_norm(core) ** 2, 0.0)
        fits.append(
            float(1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30))
        )
        return TuckerResult(core, factors, fits)

    for it in range(n_iters):
        t_sweep = _otrace.now_ns()
        with _otrace.annotated("repro.tucker.sweep", step=it):
            for k in range(n):
                y = engine_execute.multi_ttm(x, factors, keep=k, ctx=ctx)
                with _otrace.annotated("repro.tucker.eigh"):
                    ym = _unfold_rows(y, k)
                    factors[k] = _leading_eigvecs(
                        ym @ ym.T, ranks[k]
                    ).astype(x.dtype)
            with _otrace.annotated("repro.tucker.fit"):
                # the core falls out of the last mode update: contract
                # mode N-1 of its Y with the fresh A_{N-1} (no extra pass
                # over X)
                core = ttm(y, factors[n - 1], n - 1)
                err_sq = jnp.maximum(normx**2 - frob_norm(core) ** 2, 0.0)
                fit = float(
                    1.0 - jnp.sqrt(err_sq) / jnp.maximum(normx, 1e-30)
                )
            fits.append(fit)
            delta = abs(fits[-1] - fits[-2]) if it > 0 else None
            converged = bool(tol and it > 0 and delta < tol)
            # float(...) above forces concreteness: never inside a trace.
            if _otrace.should_record(ctx.observe):
                _otrace.record_event(
                    "tucker_iter",
                    start_ns=t_sweep,
                    shape=list(x.shape),
                    ranks=list(ranks),
                    it=it,
                    fit=fit,
                    fit_delta=delta,
                    converged=converged,
                )
        if converged:
            break
    return TuckerResult(core, factors, fits)
