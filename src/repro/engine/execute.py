"""The dispatch layer: one ``mttkrp`` entry point over three backends.

Backends
--------
``einsum``        — XLA's contraction (production default off-TPU).
``blocked_host``  — Algorithm 2's blocked schedule expressed as a host-level
                    reshape-einsum (:mod:`repro.core.blocked`); the
                    mid-level oracle for the kernels.
``pallas``        — the blocked VMEM/MXU kernels (Algorithm 2 on TPU),
                    planned by :mod:`repro.engine.plan`.
``auto``          — resolved through the autotuner (:mod:`repro.tune`):
                    plan-cache hit replays the tuned backend/plan exactly;
                    miss falls back to the analytic model-best
                    configuration. ``tune=True`` searches empirically on a
                    miss and persists the winner.

Configuration comes in as ONE :class:`~repro.engine.context.ExecutionContext`
(``ctx=``): backend, Memory, dtype policy, interpret, tuning policy;
without one, the process default. Per-problem *overrides* (``plan``,
``block``, ``kernel_variant``, ``out_dtype``) stay explicit arguments:
they pin one contraction's execution details, not the machine.

:func:`contract_partial` is the engine's generalized contraction: any
dimension-tree node (tensor x a subset of factors, optionally carrying the
rank axis) is flattened to canonical form, planned, and dispatched through
the same backends — this is what lets the all-mode sweep run kernel-backed.

:func:`multi_ttm` is the second workload class on the same dispatch
skeleton (arXiv:2207.10437): the Tucker/HOSVD contraction of every mode
(or every mode but one) with its own small-rank matrix.  The weight is a
Kronecker product instead of a Khatri-Rao product, so the pallas path
runs the dedicated :mod:`repro.kernels.multi_ttm` kernel under a
:class:`~repro.engine.plan.MultiTTMPlan`, and ``backend="auto"``
resolves ``kind="multi_ttm"`` tune-cache keys.

The kernel imports are lazy: ``kernels.ops`` imports the planner from this
package, so importing kernels first must not re-enter ``engine``.
"""

from __future__ import annotations

import math
from dataclasses import replace
from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.blocked import mttkrp_blocked
from ..core.mttkrp import mttkrp as _einsum_mttkrp
from ..observe import trace as _otrace
from ..observe.metrics import PALLAS_DISPATCHES, registry
from .context import ExecutionContext, check_backend
from .plan import (
    BlockPlan,
    Memory,
    MultiTTMPlan,
    best_uniform_block,
    choose_blocks,
    choose_multi_ttm_blocks,
    mttkrp_lane_pos,
)
from .plan import mode_first as _mode_first

BACKENDS = ("einsum", "blocked_host", "pallas")

_L = "abcdefghijklmnopqrstuvw"
_RANK = "z"
_RANKS = "ABCDEFGHIJ"  # per-mode Tucker rank letters (Multi-TTM einsum)


def _count_pallas() -> None:
    # instrumentation: how many contractions were dispatched to the Pallas
    # kernels (tests assert the kernel path is actually taken)
    registry().inc(PALLAS_DISPATCHES)


def _span_plan(plan) -> dict | None:
    """Serialize a plan for a span event (the tune cache's codec, so
    span plans and cached plans never drift apart)."""
    if plan is None:
        return None
    from ..tune.cache import plan_to_dict  # lazy: engine <-> tune cycle

    return plan_to_dict(plan)


def _dtype_policy(ctx: ExecutionContext) -> dict:
    return {"compute_dtype": ctx.compute_dtype, "out_dtype": ctx.out_dtype}


def _cast_compute(ctx: ExecutionContext, x, arrays, out_dtype):
    """Apply the context's mixed-precision policy: cast the tensor and the
    factor/matrix operands to ``ctx.compute_dtype`` (the bandwidth win) and
    default the output dtype to the ORIGINAL input dtype, so the policy is
    transparent end to end (bf16 streams, fp32 results). Accumulation
    stays fp32 on every backend: the pallas kernels accumulate in
    ``acc_dtype=float32`` already, and the einsum paths get
    ``preferred_element_type=float32`` when a policy is active.

    Returns ``(x, arrays, out_dtype, active)``."""
    if ctx.compute_dtype is None:
        return x, arrays, out_dtype, False
    cd = jnp.dtype(ctx.compute_dtype)
    if out_dtype is None:
        out_dtype = x.dtype
    x = x.astype(cd)
    arrays = [a.astype(cd) if a is not None else None for a in arrays]
    return x, arrays, out_dtype, True


def _einsum_mttkrp_f32acc(x, factors, mode):
    """The einsum backend under a compute-dtype policy: same contraction as
    ``core.mttkrp.mttkrp`` but with fp32 accumulation forced."""
    from ..core.mttkrp import _einsum_spec

    ins = [f for k, f in enumerate(factors) if k != mode]
    return jnp.einsum(
        _einsum_spec(x.ndim, mode), x, *ins, optimize="optimal",
        preferred_element_type=jnp.float32,
    )


def mttkrp(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    ctx: ExecutionContext | None = None,
    plan: BlockPlan | None = None,
    block: int | None = None,
    out_dtype=None,
    kernel_variant: str | None = None,
) -> jax.Array:
    """MTTKRP through the engine: ``B^(mode)(i, r)``.

    ``ctx`` is the execution environment (see
    :class:`~repro.engine.context.ExecutionContext`); ``plan`` pins
    explicit block sizes for the ``pallas`` backend; ``block`` sets the
    uniform host-blocking size for ``blocked_host`` (defaults to the Eq-9
    optimum for an abstract VMEM-word memory); ``kernel_variant`` forces
    the 3-way specialized vs N-way generic kernel for ``pallas``.

    ``ctx.backend == "auto"`` consults the autotuner: a context pinned via
    ``ExecutionContext.for_problem`` replays its stored decision; else a
    plan-cache hit replays the tuned configuration exactly (no re-search)
    and a miss uses the analytic model-best. ``ctx.tune`` additionally
    runs the empirical search on a miss and persists the winner (skipped
    under tracing, where nothing can be timed — resolution itself is
    trace-safe).
    """
    ctx = ctx if ctx is not None else ExecutionContext.default()
    if x.ndim == len(factors) + 1:
        # leading batch axis: B independent MTTKRPs under ONE resolved plan
        return _mttkrp_batched(
            x, factors, mode, ctx, plan, block, out_dtype, kernel_variant,
        )
    if not _otrace.should_record(ctx.observe, x, *factors):
        return _mttkrp_impl(
            x, factors, mode, ctx, plan, block, out_dtype, kernel_variant,
        )
    span: dict = {}
    t0 = _otrace.now_ns()
    with _otrace.annotated(f"repro.mttkrp.mode{mode}"):
        out = _mttkrp_impl(
            x, factors, mode, ctx, plan, block, out_dtype, kernel_variant,
            _span=span,
        )
    rank = next(f.shape[-1] for k, f in enumerate(factors) if k != mode)
    _record_mttkrp_span(
        "mttkrp", ctx, tuple(x.shape), rank, mode, x.dtype.itemsize,
        span, t0,
    )
    return out


def _record_mttkrp_span(
    kind: str, ctx, shape, rank, mode, itemsize, span, t0, **extra
) -> None:
    """Emit one MTTKRP-shaped dispatch event from ``t0`` (ns, the
    profiler's clock) to now: resolved backend/plan (as filled in by the
    impl), the Eq-10 modeled words for the plan (the model plan against
    the resolver's default memory when the backend carried none), and the
    Thm-4.1 lower bound, clamped at 0."""
    from ..core.bounds import seq_lb_memory

    mem = ctx.memory or Memory.tpu_vmem(itemsize=itemsize)
    mode_first = _mode_first(shape, mode) if kind == "mttkrp" else shape
    plan = span.get("plan")
    if not isinstance(plan, BlockPlan):
        plan = choose_blocks(
            mode_first, rank, itemsize, memory=mem,
            x_has_rank=bool(span.get("x_has_rank", False)),
            lane_pos=mttkrp_lane_pos(len(shape), mode)
            if kind == "mttkrp" else -1,
        )
    if kind == "mttkrp":
        extra.setdefault("relayout", span.get("relayout", "none"))
    event = {
        "shape": list(shape),
        "rank": int(rank),
        "mode": int(mode),
        "backend": span.get("backend"),
        "plan": _span_plan(span.get("plan")),
        "modeled_words": int(plan.eq10_words(mode_first, rank)),
        "lower_bound_words": max(
            seq_lb_memory(shape, rank, mem.budget_words), 0.0
        ),
        "memory_words": mem.budget_words,
        "itemsize": int(itemsize),
        **_dtype_policy(ctx),
        **extra,
    }
    _otrace.record_event(kind, start_ns=t0, **event)


def _mttkrp_impl(
    x, factors, mode, ctx, plan, block, out_dtype, kernel_variant,
    _span: dict | None = None,
):
    backend = ctx.backend
    memory = ctx.memory
    interpret = ctx.interpret
    if out_dtype is None:
        out_dtype = ctx.out_dtype
    x, factors, out_dtype, mixed = _cast_compute(ctx, x, factors, out_dtype)
    if backend == "auto":
        with _otrace.annotated("repro.engine.resolve"):
            rank = next(
                f.shape[1] for k, f in enumerate(factors) if k != mode
            )
            decision = ctx.decision_for(x.shape, rank, mode, x.dtype)
            if decision is None:
                # lazy import: engine <-> tune layer cycle
                from ..tune.search import _is_concrete, resolve, tune_mttkrp

                if ctx.tune and _is_concrete(x):
                    tune_mttkrp(
                        x, factors, mode, memory=memory,
                        interpret=interpret, cache=ctx.plan_cache(),
                    )
                decision = resolve(
                    _mode_first(x.shape, mode), rank, mode, x.dtype, memory,
                    cache=ctx.plan_cache(),
                )
        backend = decision.backend
        plan = plan if plan is not None else decision.plan
        block = block if block is not None else decision.block
        kernel_variant = kernel_variant or decision.variant
    check_backend(backend)
    if _span is not None:
        _span["backend"] = backend
    if backend == "einsum":
        out = _einsum_mttkrp_f32acc(x, factors, mode) if mixed \
            else _einsum_mttkrp(x, factors, mode)
        return out.astype(out_dtype) if out_dtype is not None else out
    if backend == "blocked_host":
        if block is None:
            mem = memory or Memory.abstract(2 ** 20)
            block = best_uniform_block(x.shape, mem)
        if _span is not None:
            _span["block"] = block
        out = mttkrp_blocked(x, factors, mode, block, f32_acc=mixed)
        return out.astype(out_dtype) if out_dtype is not None else out
    # pallas
    if x.ndim < 3:  # the kernels need >= 2 contraction dims
        out = _einsum_mttkrp_f32acc(x, factors, mode) if mixed \
            else _einsum_mttkrp(x, factors, mode)
        return out.astype(out_dtype) if out_dtype is not None else out
    from ..kernels import ops as kernel_ops  # lazy: avoids import cycle

    if plan is None:
        rank = next(
            f.shape[1] for k, f in enumerate(factors) if k != mode
        )
        if mixed and memory is not None:
            # dtype-aware planning: same physical budget, narrower items
            memory = memory.with_itemsize(x.dtype.itemsize)
        with _otrace.annotated("repro.engine.resolve"):
            plan = kernel_ops.mttkrp_plan(
                x.shape, rank, mode, x.dtype.itemsize, memory=memory,
                variant=kernel_variant,
            )
    if _span is not None:
        _span["plan"] = plan
        _span["variant"] = kernel_variant
        _span["relayout"] = kernel_ops.tensor_relayout(
            x.shape, mode, plan, kernel_variant
        )
    _count_pallas()
    return kernel_ops.mttkrp_pallas(
        x, factors, mode, plan=plan, interpret=interpret,
        out_dtype=out_dtype, variant=kernel_variant,
    )


# ---------------------------------------------------------------------------
# Batched dispatch: a leading B axis, ONE plan, ONE program
# ---------------------------------------------------------------------------

def _concrete_ctx(ctx: ExecutionContext, backend: str) -> ExecutionContext:
    """The context the vmapped element dispatch runs under: the backend
    the bucket resolved to, pinned (no per-element re-resolution, no
    empirical tuning, no stale problem pinning inside the trace)."""
    if ctx.backend == backend and not ctx.tune and ctx.problem is None:
        return ctx
    return replace(
        ctx, backend=backend, tune=False, problem=None, decisions=(),
    )


def _batch_axes(
    api: str, arrays: Sequence[jax.Array | None], batch: int,
    elem_dims: Sequence[int], ranks: Sequence[object], what: str,
) -> list[int | None]:
    """vmap ``in_axes`` for the per-mode operands of a batched call:
    axis 0 for per-element ``(B, I_k, R)`` stacks, ``None`` for shared
    ``(I_k, R)`` operands (and for the ``None`` slot at a kept mode).
    ``ranks[k]`` may be ``None`` to skip the rank-extent check."""
    axes: list[int | None] = []
    for k, a in enumerate(arrays):
        if a is None:
            axes.append(None)
            continue
        want = (elem_dims[k],) if ranks[k] is None \
            else (elem_dims[k], ranks[k])
        if a.ndim == len(want) + 1 and tuple(a.shape) == (batch,) + want:
            axes.append(0)
        elif a.ndim == len(want) and tuple(a.shape) == want:
            axes.append(None)
        else:
            raise ValueError(
                f"{api}: batched call (B={batch}) needs {what} {k} of "
                f"shape {(batch,) + want} (per-element) or {want} "
                f"(shared), got {tuple(a.shape)}"
            )
    return axes


def _mttkrp_batched(
    x, factors, mode, ctx, plan, block, out_dtype, kernel_variant,
):
    """B MTTKRPs as one dispatch: ``x`` is ``(B, I_0, ..., I_{N-1})``,
    ``factors[k]`` is ``(B, I_k, R)`` (per-element) or ``(I_k, R)``
    (shared). The ``auto`` decision is resolved ONCE against the element
    shape — the same tune-cache key the unbatched call uses, so a bucket
    of B requests costs one cache lookup — and ``jax.vmap`` maps the
    element dispatch over the batch axis: the pallas backend launches
    ONE kernel (the batch axis becomes a grid dimension), not B."""
    batch = int(x.shape[0])
    elem_shape = tuple(x.shape[1:])
    rank = next(
        int(f.shape[-1]) for k, f in enumerate(factors) if k != mode
    )
    axes = _batch_axes(
        "repro.mttkrp", factors, batch, elem_shape,
        [rank] * len(factors), "factor",
    )
    backend = ctx.backend
    if backend == "auto":
        with _otrace.annotated("repro.engine.resolve"):
            decision = ctx.decision_for(elem_shape, rank, mode, x.dtype)
            if decision is None:
                from ..tune.search import resolve  # lazy: engine <-> tune

                decision = resolve(
                    _mode_first(elem_shape, mode), rank, mode, x.dtype,
                    ctx.memory, cache=ctx.plan_cache(),
                )
        backend = decision.backend
        plan = plan if plan is not None else decision.plan
        block = block if block is not None else decision.block
        kernel_variant = kernel_variant or decision.variant
    ectx = _concrete_ctx(ctx, backend)
    span: dict = {}

    def one(xb, *fbs):
        return _mttkrp_impl(
            xb, list(fbs), mode, ectx, plan, block, out_dtype,
            kernel_variant, _span=span,
        )

    vmapped = jax.vmap(one, in_axes=(0, *axes))
    if not _otrace.should_record(ctx.observe, x, *factors):
        return vmapped(x, *factors)
    t0 = _otrace.now_ns()
    with _otrace.annotated(f"repro.mttkrp.batched.mode{mode}"):
        out = vmapped(x, *factors)
    _record_mttkrp_span(
        "mttkrp", ectx, elem_shape, rank, mode, x.dtype.itemsize, span,
        t0, batch=batch,
    )
    return out


def contract_partial(
    node: jax.Array,
    factors: Sequence[jax.Array],
    modes: Sequence[int],
    drop: Sequence[int],
    has_rank: bool,
    *,
    ctx: ExecutionContext | None = None,
    plan: BlockPlan | None = None,
) -> jax.Array:
    """Contract the factors for ``drop`` out of a dimension-tree ``node``.

    ``node`` carries tensor modes ``modes`` (in axis order) plus a trailing
    rank axis when ``has_rank``; ``factors`` is the full factor list indexed
    by mode. Returns the node for ``keep = modes - drop`` (rank axis last).

    Every such contraction is MTTKRP-shaped: kept modes flatten into the
    output axis, dropped modes are the contraction dims, and the dropped
    factors' Khatri-Rao structure is the weight. The ``pallas`` backend
    plans each one against ``ctx.memory`` and dispatches the blocked
    kernels (the N-way generic kernel when the node has no rank axis yet,
    the rank-augmented partial kernel otherwise). ``plan`` pins explicit
    block sizes for ``pallas``. ``ctx.backend == "auto"`` resolves each
    edge through the autotuner's plan cache (kind ``"partial"``), falling
    back to the model-best configuration on a miss; ``ctx.tune`` searches
    the edge empirically on a miss and persists the winner (skipped under
    tracing — resolution itself is trace-safe, so dimension-tree sweeps
    inside jit still work).
    """
    ctx = ctx if ctx is not None else ExecutionContext.default()
    if node.ndim == len(modes) + int(has_rank) + 1:
        # leading batch axis: B tree-node contractions under ONE plan
        return _contract_partial_batched(
            node, factors, modes, drop, has_rank, ctx, plan,
        )
    if not _otrace.should_record(ctx.observe, node, *factors):
        return _contract_partial_impl(
            node, factors, modes, drop, has_rank, ctx, plan
        )
    span: dict = {}
    t0 = _otrace.now_ns()
    with _otrace.annotated("repro.contract_partial"):
        out = _contract_partial_impl(
            node, factors, modes, drop, has_rank, ctx, plan, _span=span,
        )
    modes_t, drop_t = tuple(modes), tuple(drop)
    keep = tuple(m for m in modes_t if m not in drop_t)
    pos = {m: i for i, m in enumerate(modes_t)}
    canon = (
        math.prod(node.shape[pos[m]] for m in keep) if keep else 1,
    ) + tuple(node.shape[pos[m]] for m in drop_t)
    span["x_has_rank"] = has_rank
    _record_mttkrp_span(
        "contract_partial", ctx, canon, factors[drop_t[0]].shape[1], 0,
        node.dtype.itemsize, span, t0,
        modes=list(modes_t), drop=list(drop_t), has_rank=bool(has_rank),
    )
    return out


def _contract_partial_impl(
    node, factors, modes, drop, has_rank, ctx, plan,
    _span: dict | None = None,
):
    backend = ctx.backend
    memory = ctx.memory
    interpret = ctx.interpret
    out_dtype = ctx.out_dtype  # same dtype policy as the plain path
    node, factors, out_dtype, mixed = _cast_compute(
        ctx, node, factors, out_dtype
    )
    modes = tuple(modes)
    drop = tuple(drop)
    keep = tuple(m for m in modes if m not in drop)
    auto_plan: BlockPlan | None = plan
    if backend == "auto":
        # lazy import: engine <-> tune layer cycle
        from ..tune.search import _is_concrete, resolve, tune_partial

        with _otrace.annotated("repro.engine.resolve"):
            if ctx.tune and _is_concrete(node):
                tune_partial(
                    node, factors, modes, drop, has_rank, memory=memory,
                    interpret=interpret, cache=ctx.plan_cache(),
                )
            pos0 = {m: i for i, m in enumerate(modes)}
            canon_shape = (
                math.prod(node.shape[pos0[m]] for m in keep) if keep else 1,
            ) + tuple(node.shape[pos0[m]] for m in drop)
            resolved = resolve(
                canon_shape, factors[drop[0]].shape[1], 0, node.dtype,
                memory, kind="partial", x_has_rank=has_rank,
                cache=ctx.plan_cache(),
            )
        backend = resolved.backend
        if auto_plan is None:
            auto_plan = resolved.plan
    check_backend(backend)
    if _span is not None:
        _span["backend"] = backend
    if backend != "pallas":
        # Algorithm 2's schedule matters only below the einsum boundary
        # here; blocked_host partials fall back to einsum (the host-blocked
        # oracle exists for the full MTTKRP path).
        sub_in = "".join(_L[m] for m in modes) + (_RANK if has_rank else "")
        ops = [node]
        subs = [sub_in]
        for m in drop:
            ops.append(factors[m])
            subs.append(_L[m] + _RANK)
        sub_out = "".join(_L[m] for m in keep) + _RANK
        kw = {"preferred_element_type": jnp.float32} if mixed else {}
        out = jnp.einsum(
            ",".join(subs) + "->" + sub_out, *ops, optimize="optimal", **kw
        )
        return out.astype(out_dtype) if out_dtype is not None else out

    from ..kernels import ops as kernel_ops  # lazy: avoids import cycle

    rank = factors[drop[0]].shape[1]
    pos = {m: i for i, m in enumerate(modes)}
    keep_sizes = tuple(node.shape[pos[m]] for m in keep)
    drop_sizes = tuple(node.shape[pos[m]] for m in drop)
    # canonicalize: kept modes first (flattened), dropped modes next,
    # rank axis last
    perm = tuple(pos[m] for m in keep) + tuple(pos[m] for m in drop)
    if has_rank:
        perm = perm + (node.ndim - 1,)
    xp = kernel_ops.transpose_tensor(node, perm)
    i_rows = math.prod(keep_sizes) if keep_sizes else 1
    fs = [factors[m] for m in drop]
    itemsize = node.dtype.itemsize
    if mixed and memory is not None:
        memory = memory.with_itemsize(itemsize)  # dtype-aware planning
    _count_pallas()
    if has_rank:
        xp = xp.reshape((i_rows,) + drop_sizes + (rank,))
        plan = auto_plan if auto_plan is not None else (
            choose_blocks(
                (i_rows,) + drop_sizes, rank, itemsize, memory=memory,
                x_has_rank=True,
            ) if memory is not None else None
        )
        if _span is not None:
            _span["plan"] = plan
        out = kernel_ops.mttkrp_partial_canonical_pallas(
            xp, fs, plan=plan, interpret=interpret,
            out_dtype=out_dtype if mixed else node.dtype,
        )
    else:
        xp = xp.reshape((i_rows,) + drop_sizes)
        plan = auto_plan if auto_plan is not None else (
            choose_blocks(
                xp.shape, rank, itemsize, memory=memory
            ) if memory is not None else None
        )
        if _span is not None:
            _span["plan"] = plan
        out = kernel_ops.mttkrp_canonical_pallas(
            xp, fs, plan=plan, interpret=interpret,
            out_dtype=out_dtype if mixed else node.dtype,
        )
    out = out.reshape(keep_sizes + (rank,))
    return out.astype(out_dtype) if out_dtype is not None else out


def _contract_partial_batched(
    node, factors, modes, drop, has_rank, ctx, plan,
):
    """B dimension-tree contractions as one dispatch: ``node`` carries a
    leading batch axis ahead of its tensor modes (and trailing rank axis
    when ``has_rank``); ``factors[m]`` for each dropped mode is
    ``(B, I_m, R)`` or shared ``(I_m, R)``. The ``auto`` resolution runs
    once against the element's canonical shape (``kind="partial"`` key),
    then the element contraction is vmapped — one pallas launch."""
    modes_t = tuple(modes)
    drop_t = tuple(drop)
    keep = tuple(m for m in modes_t if m not in drop_t)
    batch = int(node.shape[0])
    elem_shape = tuple(node.shape[1:])
    rank = int(factors[drop_t[0]].shape[-1])
    # factor list is indexed by mode; only dropped modes' factors are
    # touched, so slots for kept/absent modes batch-check only if present
    pos = {m: i for i, m in enumerate(modes_t)}
    dims, ranks = [], []
    for k, f in enumerate(factors):
        if k in pos:
            dims.append(elem_shape[pos[k]])
        else:
            dims.append(None if f is None else int(f.shape[-2]))
        ranks.append(rank)
    axes = _batch_axes(
        "repro.contract_partial", factors, batch, dims, ranks, "factor",
    )
    backend = ctx.backend
    if backend == "auto":
        from ..tune.search import resolve  # lazy: engine <-> tune

        canon_shape = (
            math.prod(elem_shape[pos[m]] for m in keep) if keep else 1,
        ) + tuple(elem_shape[pos[m]] for m in drop_t)
        with _otrace.annotated("repro.engine.resolve"):
            resolved = resolve(
                canon_shape, rank, 0, node.dtype, ctx.memory,
                kind="partial", x_has_rank=has_rank, cache=ctx.plan_cache(),
            )
        backend = resolved.backend
        plan = plan if plan is not None else resolved.plan
    ectx = _concrete_ctx(ctx, backend)

    def one(nb, *fbs):
        return _contract_partial_impl(
            nb, list(fbs), modes_t, drop_t, has_rank, ectx, plan,
        )

    vmapped = jax.vmap(one, in_axes=(0, *axes))
    if not _otrace.should_record(ctx.observe, node, *factors):
        return vmapped(node, *factors)
    t0 = _otrace.now_ns()
    with _otrace.annotated("repro.contract_partial.batched"):
        out = vmapped(node, *factors)
    canon = (
        math.prod(elem_shape[pos[m]] for m in keep) if keep else 1,
    ) + tuple(elem_shape[pos[m]] for m in drop_t)
    span = {"backend": backend, "plan": plan, "x_has_rank": has_rank}
    _record_mttkrp_span(
        "contract_partial", ectx, canon, rank, 0, node.dtype.itemsize,
        span, t0, modes=list(modes_t), drop=list(drop_t),
        has_rank=bool(has_rank), batch=batch,
    )
    return out


# ---------------------------------------------------------------------------
# Multi-TTM (the Tucker/HOSVD kernel, arXiv:2207.10437)
# ---------------------------------------------------------------------------

def _multi_ttm_einsum(x, matrices, keep, f32_acc=False):
    subs, ops, out = [_L[: x.ndim]], [x], ""
    for k in range(x.ndim):
        if k == keep:
            out += _L[k]
            continue
        ops.append(matrices[k])
        subs.append(_L[k] + _RANKS[k])
        out += _RANKS[k]
    kw = {"preferred_element_type": jnp.float32} if f32_acc else {}
    return jnp.einsum(
        ",".join(subs) + "->" + out, *ops, optimize="optimal", **kw
    )


def _keep_first(shape: Sequence[int], keep: int) -> tuple[int, ...]:
    """Canonical Multi-TTM problem shape: kept mode first (mode 0 when
    the full core is computed — every mode is contracted either way)."""
    return (shape[keep],) + tuple(
        s for k, s in enumerate(shape) if k != keep
    )


def multi_ttm(
    x: jax.Array,
    matrices: Sequence[jax.Array],
    keep: int | None = None,
    *,
    ctx: ExecutionContext | None = None,
    plan: MultiTTMPlan | None = None,
    block: int | None = None,
    out_dtype=None,
) -> jax.Array:
    """Multi-TTM through the engine: contract every tensor mode (or every
    mode but ``keep``) with its matrix — the Tucker/HOSVD workhorse
    (arXiv:2207.10437).

    ``matrices[k]`` is ``(I_k, R_k)``; ``matrices[keep]`` is ignored (may
    be ``None``).  ``keep=None`` computes the full core ``G = X x_1
    A_1^T ... x_N A_N^T`` of shape ``(R_1, ..., R_N)``; ``keep=k``
    computes the HOOI workhorse ``Y^(k) = X x_{j != k} A_j^T`` with the
    kept mode staying in place: ``(R_1, ..., I_k, ..., R_N)``.

    ``ctx`` is the same :class:`~repro.engine.context.ExecutionContext`
    that drives :func:`mttkrp`: the backend selects einsum /
    blocked_host (the uniform-b Algorithm-2 schedule; ``block``
    overrides the Eq-9 optimum) / pallas (the blocked Kronecker-weight
    kernel, planned against ``ctx.memory``; ``plan`` pins explicit
    :class:`~repro.engine.plan.MultiTTMPlan` blocks) — or ``"auto"`` to
    resolve through the autotuner's plan cache under ``kind=
    "multi_ttm"`` keys (a context pinned via
    ``ExecutionContext.for_problem(shape, ranks)`` replays its stored
    decision; ``ctx.tune`` searches empirically on a miss and persists
    the winner).
    """
    if ctx is None:
        ctx = ExecutionContext.default()
    if x.ndim == len(matrices) + 1 and _looks_batched_multi_ttm(
        x, matrices, keep
    ):
        # leading batch axis: B Multi-TTMs under ONE resolved plan
        return _multi_ttm_batched(
            x, matrices, keep, ctx, plan, block, out_dtype,
        )
    n = x.ndim
    if keep is not None and not 0 <= keep < n:
        raise ValueError(f"keep mode {keep} out of range for {n}-way tensor")
    if len(matrices) != n:
        raise ValueError(
            f"multi_ttm needs one matrix per tensor mode ({n}), got "
            f"{len(matrices)} (pass None at the kept mode)"
        )
    for k, m in enumerate(matrices):
        if k == keep:
            continue
        if m is None:
            raise ValueError(
                f"matrix {k} is None but mode {k} is contracted "
                f"(only matrices[keep] may be None; keep={keep})"
            )
        if m.shape[0] != x.shape[k]:
            raise ValueError(
                f"matrix {k} has {m.shape[0]} rows but tensor mode {k} "
                f"has extent {x.shape[k]}"
            )
    concrete_mats = [m for m in matrices if m is not None]
    if not _otrace.should_record(ctx.observe, x, *concrete_mats):
        return _multi_ttm_impl(x, matrices, keep, ctx, plan, block, out_dtype)
    span: dict = {}
    t0 = _otrace.now_ns()
    with _otrace.annotated(f"repro.multi_ttm.keep{keep}"):
        out = _multi_ttm_impl(
            x, matrices, keep, ctx, plan, block, out_dtype, _span=span,
        )
    _record_multi_ttm_span(
        ctx, tuple(x.shape),
        tuple(m.shape[1] for k, m in enumerate(matrices) if k != keep),
        keep, x.dtype.itemsize, span, t0,
    )
    return out


def _record_multi_ttm_span(
    ctx, shape, ranks, keep, itemsize, span, t0, **extra
) -> None:
    """Emit one Multi-TTM dispatch event from ``t0`` (ns) to now:
    resolved backend/plan, the blocked model words
    (``MultiTTMPlan.model_words``) and the HBL sequential lower bound,
    clamped at 0."""
    from ..core.bounds import multi_ttm_seq_lb_memory

    mem = ctx.memory or Memory.tpu_vmem(itemsize=itemsize)
    canon = _keep_first(shape, 0 if keep is None else keep)
    plan = span.get("plan")
    if not isinstance(plan, MultiTTMPlan):
        kernel_ranks = ranks[1:] if keep is None else ranks
        plan = choose_multi_ttm_blocks(
            canon, kernel_ranks, itemsize, memory=mem
        )
    _otrace.record_event(
        "multi_ttm",
        start_ns=t0,
        shape=list(shape),
        ranks=list(ranks),
        keep=keep,
        backend=span.get("backend"),
        plan=_span_plan(span.get("plan")),
        modeled_words=int(plan.model_words(canon)),
        lower_bound_words=max(
            multi_ttm_seq_lb_memory(shape, ranks, mem.budget_words), 0.0
        ),
        memory_words=mem.budget_words,
        itemsize=int(itemsize),
        **_dtype_policy(ctx),
        **extra,
    )


def _multi_ttm_impl(
    x, matrices, keep, ctx, plan, block, out_dtype,
    _span: dict | None = None,
):
    n = x.ndim
    backend = ctx.backend
    memory = ctx.memory
    interpret = ctx.interpret
    if out_dtype is None:
        out_dtype = ctx.out_dtype
    x, matrices, out_dtype, mixed = _cast_compute(
        ctx, x, matrices, out_dtype
    )
    ranks = tuple(
        m.shape[1] for k, m in enumerate(matrices) if k != keep
    )
    keep_key = -1 if keep is None else keep
    canon = _keep_first(x.shape, 0 if keep is None else keep)
    if backend == "auto":
        with _otrace.annotated("repro.engine.resolve"):
            decision = _resolve_multi_ttm_decision(
                ctx, x, matrices, keep, canon, ranks, keep_key,
            )
        backend = decision.backend
        plan = plan if plan is not None else decision.plan
        block = block if block is not None else decision.block
    check_backend(backend)
    if _span is not None:
        _span["backend"] = backend
    if backend == "einsum" or (backend == "pallas" and n < 3):
        out = _multi_ttm_einsum(x, matrices, keep, f32_acc=mixed)
        return out.astype(out_dtype) if out_dtype is not None else out
    if backend == "blocked_host":
        from ..core.blocked import multi_ttm_blocked

        if block is None:
            from ..core.bounds import multi_ttm_best_block_size

            mem = memory or Memory.abstract(2 ** 20)
            # the oracle's convention is kept-mode-first (N dims, N-1
            # contracted ranks); for the full core the lead mode plays
            # the kept role, matching the pallas path's kernel_ranks
            b_ranks = ranks[1:] if keep is None else ranks
            block = multi_ttm_best_block_size(
                canon, b_ranks, mem.budget_words
            )
        out = multi_ttm_blocked(x, matrices, keep, block, f32_acc=mixed)
        return out.astype(out_dtype) if out_dtype is not None else out
    # pallas: canonicalize kept mode first (mode 0 for the full core),
    # run the blocked Kronecker kernel, then restore the mode order
    from ..kernels import ops as kernel_ops  # lazy: avoids import cycle

    lead = 0 if keep is None else keep
    perm = (lead,) + tuple(k for k in range(n) if k != lead)
    xp = kernel_ops.transpose_tensor(x, perm)
    mats = [matrices[k] for k in perm[1:]]
    if plan is None and memory is not None:
        # the keep=None kernel contracts the trailing N-1 modes only (the
        # lead mode is contracted by the final small matmul)
        kernel_ranks = ranks[1:] if keep is None else ranks
        if mixed:
            memory = memory.with_itemsize(x.dtype.itemsize)
        with _otrace.annotated("repro.engine.resolve"):
            plan = choose_multi_ttm_blocks(
                canon, kernel_ranks, x.dtype.itemsize, memory=memory
            )
    if _span is not None:
        _span["plan"] = plan
    _count_pallas()
    out2d = kernel_ops.multi_ttm_canonical_pallas(
        xp, mats, plan=plan, interpret=interpret
    )
    rest_ranks = tuple(m.shape[1] for m in mats)
    if keep is None:
        # contract the lead mode too: one small matmul A_0^T @ Z
        out2d = jax.lax.dot_general(
            matrices[0].astype(out2d.dtype), out2d,
            dimension_numbers=(((0,), (0,)), ((), ())),
        )
        out = out2d.reshape((matrices[0].shape[1],) + rest_ranks)
        out = out.astype(x.dtype)
        return out.astype(out_dtype) if out_dtype is not None else out
    inv = [0] * n
    for pos, axis in enumerate(perm):
        inv[axis] = pos
    with _otrace.annotated("repro.engine.relayout"):
        out = out2d.reshape((x.shape[keep],) + rest_ranks)
        out = jnp.transpose(out, inv).astype(x.dtype)
        return out.astype(out_dtype) if out_dtype is not None else out


def _resolve_multi_ttm_decision(ctx, x, matrices, keep, canon, ranks,
                                keep_key):
    """The ``auto`` decision of one Multi-TTM: a pinned context's stored
    decision, else the tune cache (searched first under ``ctx.tune``)."""
    # pinned Tucker contexts key decisions by the FULL per-mode rank
    # tuple (the problem identity); a None matrix at the kept mode
    # hides R_keep, so such calls just resolve live instead
    decision = None
    if all(m is not None for m in matrices):
        full_ranks = tuple(m.shape[1] for m in matrices)
        decision = ctx.decision_for(x.shape, full_ranks, keep_key, x.dtype)
    if decision is not None:
        return decision
    # lazy import: engine <-> tune layer cycle
    from ..tune.search import _is_concrete, resolve_multi_ttm, tune_multi_ttm

    if ctx.tune and _is_concrete(x):
        tune_multi_ttm(
            x, matrices, keep, memory=ctx.memory, interpret=ctx.interpret,
            cache=ctx.plan_cache(),
        )
    return resolve_multi_ttm(
        canon, ranks, keep_key, x.dtype, ctx.memory, cache=ctx.plan_cache(),
    )


def _looks_batched_multi_ttm(x, matrices, keep) -> bool:
    """Disambiguate ``multi_ttm(x_{N+1-way}, N matrices)``: it is a
    batched call only when every matrix is consistent with the element
    problem ``x[b]`` — ``(B, I_k, R_k)`` per-element, ``(I_k, R_k)``
    shared, or ``None`` at the kept mode. Anything else falls through
    to the unbatched path so a short matrix list still raises the
    canonical one-matrix-per-mode error."""
    batch, elem_shape = int(x.shape[0]), tuple(x.shape[1:])
    for k, m in enumerate(matrices):
        if m is None:
            if k != keep:
                return False
            continue
        rows = (elem_shape[k],)
        if not (
            (m.ndim == 3 and tuple(m.shape[:2]) == (batch,) + rows)
            or (m.ndim == 2 and tuple(m.shape[:1]) == rows)
        ):
            return False
    return True


def _multi_ttm_batched(x, matrices, keep, ctx, plan, block, out_dtype):
    """B Multi-TTMs as one dispatch: ``x`` is ``(B, I_1, ..., I_N)``,
    ``matrices[k]`` is ``(B, I_k, R_k)`` (per-element), ``(I_k, R_k)``
    (shared), or ``None`` at the kept mode. The ``auto`` decision
    resolves ONCE against the element shape (``kind="multi_ttm"`` key)
    and the element contraction is vmapped over the batch — one pallas
    launch for all B elements."""
    n = x.ndim - 1
    batch = int(x.shape[0])
    elem_shape = tuple(x.shape[1:])
    if keep is not None and not 0 <= keep < n:
        raise ValueError(
            f"keep mode {keep} out of range for batched {n}-way tensor"
        )
    for k, m in enumerate(matrices):
        if m is None and k != keep:
            raise ValueError(
                f"matrix {k} is None but mode {k} is contracted "
                f"(only matrices[keep] may be None; keep={keep})"
            )
    axes = _batch_axes(
        "repro.multi_ttm", matrices, batch, elem_shape,
        [None if m is None else int(m.shape[-1]) for m in matrices],
        "matrix",
    )
    ranks = tuple(
        int(m.shape[-1]) for k, m in enumerate(matrices) if k != keep
    )
    keep_key = -1 if keep is None else keep
    canon = _keep_first(elem_shape, 0 if keep is None else keep)
    backend = ctx.backend
    if backend == "auto":
        with _otrace.annotated("repro.engine.resolve"):
            decision = None
            if all(m is not None for m in matrices):
                full_ranks = tuple(int(m.shape[-1]) for m in matrices)
                decision = ctx.decision_for(
                    elem_shape, full_ranks, keep_key, x.dtype
                )
            if decision is None:
                from ..tune.search import resolve_multi_ttm  # lazy cycle

                decision = resolve_multi_ttm(
                    canon, ranks, keep_key, x.dtype, ctx.memory,
                    cache=ctx.plan_cache(),
                )
        backend = decision.backend
        plan = plan if plan is not None else decision.plan
        block = block if block is not None else decision.block
    ectx = _concrete_ctx(ctx, backend)

    def one(xb, *ms):
        return _multi_ttm_impl(
            xb, list(ms), keep, ectx, plan, block, out_dtype,
        )

    vmapped = jax.vmap(one, in_axes=(0, *axes))
    concrete = [m for m in matrices if m is not None]
    if not _otrace.should_record(ctx.observe, x, *concrete):
        return vmapped(x, *matrices)
    t0 = _otrace.now_ns()
    with _otrace.annotated(f"repro.multi_ttm.batched.keep{keep}"):
        out = vmapped(x, *matrices)
    span = {"backend": backend, "plan": plan}
    _record_multi_ttm_span(
        ectx, elem_shape, ranks, keep, x.dtype.itemsize, span, t0,
        batch=batch,
    )
    return out
