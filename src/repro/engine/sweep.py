"""Fused ALS sweeps: the arXiv:1708.08976 mode-reuse schedule on the
engine's dispatch layer.

Plain Gauss-Seidel ALS re-reads the tensor once per mode (N passes per
sweep). The fused schedule reuses the contraction ``P' = X x_{N-1}
A_{N-1}`` — computed with *pre-sweep* factors — for every mode but the
last:

    P'  = X  x_{N-1} A_{N-1}       pre-sweep factors (1st tensor pass)
    B0  = P' x_{1..N-2} A_d        every dropped factor pre-sweep
    ... solve mode 0 ...; then for m = 1 .. N-2:
    B_m = P' x_{d != m} A_d        A_0..A_{m-1} updated, rest pre-sweep
    ... solve mode m ...; finally
    B_{N-1} = full MTTKRP          all factors updated (2nd tensor pass)

Two tensor passes per sweep instead of N, and every mode's update consumes
exactly the factor values plain sequential ALS would use — the sweep is
Gauss-Seidel *exact*, not an approximation (results differ only by
floating-point summation order).

On the ``pallas`` backend the opening ``(B0, P')`` pair is ONE two-output
``pallas_call`` (:mod:`repro.kernels.sweep`) that reads each X tile once —
a single dispatch replacing the first two launches of the per-mode chain,
with both accumulators VMEM-resident (the mode-reuse working set,
:func:`repro.engine.plan.fused_pair_working_set_words`). Other backends
compute the same two nodes as two ``contract_partial`` calls (still two
tensor passes total).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..observe import trace as _otrace
from .context import ExecutionContext
from .execute import _count_pallas, _span_plan, contract_partial, mttkrp


def _fused_pair(x: jax.Array, factors, ctx: ExecutionContext):
    """The sweep's opening ``(B0, P')`` pair. One pallas dispatch on the
    pallas backend; two ``contract_partial`` calls elsewhere (``auto``
    resolves each edge through the tune cache as usual)."""
    n = x.ndim
    modes = tuple(range(n))
    inner = tuple(range(n - 1))
    if ctx.backend == "pallas":
        from ..kernels.sweep import fused_pair_canonical_pallas
        from .plan import choose_sweep_blocks

        orig_dtype = x.dtype
        fs = [f for f in factors[1:]]
        if ctx.compute_dtype is not None:
            cd = jnp.dtype(ctx.compute_dtype)
            x = x.astype(cd)
            fs = [f.astype(cd) for f in fs]
        plan = None
        if ctx.memory is not None:
            mem = ctx.memory.with_itemsize(x.dtype.itemsize)
            with _otrace.annotated("repro.engine.resolve"):
                plan = choose_sweep_blocks(
                    x.shape, fs[0].shape[1], x.dtype.itemsize, memory=mem
                )
        _count_pallas()
        if not _otrace.should_record(ctx.observe, x, *fs):
            return fused_pair_canonical_pallas(
                x, fs, plan=plan, interpret=ctx.interpret,
                out_dtype=orig_dtype,
            )
        t0 = _otrace.now_ns()
        with _otrace.annotated("repro.fused_pair"):
            out = fused_pair_canonical_pallas(
                x, fs, plan=plan, interpret=ctx.interpret,
                out_dtype=orig_dtype,
            )
        _otrace.record_event(
            "fused_pair",
            start_ns=t0,
            shape=list(x.shape),
            rank=int(fs[0].shape[1]),
            backend="pallas",
            plan=_span_plan(plan),
            itemsize=int(x.dtype.itemsize),
            compute_dtype=ctx.compute_dtype,
            out_dtype=ctx.out_dtype,
        )
        return out
    p = contract_partial(x, factors, modes, (n - 1,), False, ctx=ctx)
    b0 = contract_partial(
        p, factors, inner, tuple(range(1, n - 1)), True, ctx=ctx
    )
    return b0, p


def fused_als_sweep(
    x: jax.Array,
    factors: list[jax.Array],
    update_fn: Callable[[int, jax.Array], jax.Array],
    *,
    ctx: ExecutionContext | None = None,
) -> None:
    """One Gauss-Seidel ALS sweep under the mode-reuse schedule.

    Same contract as :func:`repro.engine.tree.dimtree_als_sweep`:
    ``update_fn(mode, b)`` receives mode ``mode``'s MTTKRP computed with
    all modes < mode already updated, returns the new factor, and may keep
    its own side state; ``factors`` is updated in place. Tensors with
    fewer than 3 modes fall back to the per-mode chain (nothing to reuse).
    """
    if ctx is None:
        ctx = ExecutionContext.default()
    n = x.ndim
    if n < 3:
        for mode in range(n):
            factors[mode] = update_fn(mode, mttkrp(x, factors, mode, ctx=ctx))
        return
    inner = tuple(range(n - 1))
    b0, p = _fused_pair(x, factors, ctx)
    factors[0] = update_fn(0, b0)
    for m in range(1, n - 1):
        drop = tuple(d for d in inner if d != m)
        bm = contract_partial(p, factors, inner, drop, True, ctx=ctx)
        factors[m] = update_fn(m, bm)
    factors[n - 1] = update_fn(n - 1, mttkrp(x, factors, n - 1, ctx=ctx))
