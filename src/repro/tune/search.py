"""Empirical plan search: generate candidates, measure, pick a winner.

Candidate space (the knobs PR 1 unified behind the engine):

  * all three executors — ``einsum``, ``blocked_host`` (Eq-9 uniform
    blocking), ``pallas`` (the blocked VMEM/MXU kernels);
  * for ``pallas``, the analytic ``choose_blocks`` plan plus structured
    perturbations of it (each block dimension halved/doubled within the
    Eq-9 budget) and the paper's exact uniform-b plan;
  * for 3-way tensors, both kernel variants (the specialized
    ``mttkrp3`` schedule and the generic N-way kernel).

Measurement runs every candidate through the same
``engine.execute.mttkrp`` entry point the engine uses in production and
checks it against the einsum oracle, so a tuned winner is always a
correct configuration. Scoring:

  * ``metric="walltime"`` — min-of-reps wall time on the actual device
    (the TPU path).
  * ``metric="traffic"``  — the CPU fallback: interpret-mode wall time of
    a Pallas kernel says nothing about its TPU behavior, so kernel plans
    are ranked by their modeled HBM traffic (``BlockPlan.traffic_model``)
    and only the best-traffic plan is timed against the host executors.
  * ``metric="auto"``     — walltime on TPU, traffic elsewhere.

:func:`resolve` is the ``backend="auto"`` entry: cache hit returns the
persisted winner (exact :class:`BlockPlan` round-trip, no re-search);
miss returns the analytic model-best configuration. It is pure Python on
static shapes, so it also works at trace time (e.g. inside shard_map for
the distributed algorithms' local MTTKRPs).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp

from ..engine.context import ExecutionContext
from ..engine.plan import (
    BlockPlan,
    Memory,
    MultiTTMPlan,
    choose_blocks,
    choose_multi_ttm_blocks,
    mttkrp_lane_pos,
    uniform_plan,
)
from .cache import CacheEntry, PlanCache, cache_key, default_cache, plan_to_dict

KERNEL_VARIANTS = ("specialized", "generic")


def _is_concrete(x) -> bool:
    try:
        return not isinstance(x, jax.core.Tracer)
    except AttributeError:  # pragma: no cover - jax.core moved
        return hasattr(x, "addressable_data") or hasattr(x, "__array__")


@dataclass(frozen=True)
class Candidate:
    """One runnable configuration of the engine for a fixed problem."""

    backend: str
    plan: BlockPlan | None = None
    variant: str | None = None  # pallas 3-way kernel variant
    block: int | None = None  # blocked_host uniform block

    @property
    def label(self) -> str:
        if self.backend == "pallas" and self.plan is not None:
            p = self.plan
            v = f":{self.variant}" if self.variant else ""
            return (
                f"pallas{v}[{p.block_i}x"
                f"{'x'.join(map(str, p.block_contract))}xR{p.block_r}]"
            )
        if self.backend == "blocked_host" and self.block is not None:
            return f"blocked_host[b={self.block}]"
        return self.backend


@dataclass
class Measurement:
    candidate: Candidate
    walltime_us: float = float("nan")
    modeled_bytes: int | None = None
    score: float = float("inf")
    ok: bool = True
    error: str = ""


@dataclass
class TuneResult:
    key: str
    winner: Candidate
    measurements: list[Measurement] = field(default_factory=list)
    metric: str = "walltime"
    cache_hit: bool = False

    @property
    def best(self) -> Measurement:
        return next(
            m for m in self.measurements if m.candidate == self.winner
        )


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------

def _clamp_plan(plan: BlockPlan, shape: Sequence[int], rank: int,
                memory: Memory) -> BlockPlan | None:
    """Keep a perturbed plan only if it is feasible and non-degenerate."""
    if plan.block_i < 1 or plan.block_r < 1:
        return None
    if any(c < 1 for c in plan.block_contract):
        return None
    if not plan.fits(memory):
        return None
    return plan


def _perturbations(base: BlockPlan, shape: Sequence[int], rank: int,
                   memory: Memory) -> list[BlockPlan]:
    """Halve/double each block dimension of the analytic plan (one axis at
    a time), keeping Eq-9-feasible results — the empirical neighborhood
    Hayashi et al. search instead of trusting the model's constants."""
    out: list[BlockPlan] = []
    axes = 2 + len(base.block_contract)  # i, r, c_0..c_{k-1}
    for axis in range(axes):
        for factor_num, factor_den in ((1, 2), (2, 1)):
            bi, br = base.block_i, base.block_r
            bc = list(base.block_contract)
            if axis == 0:
                bi = max(1, bi * factor_num // factor_den)
            elif axis == 1:
                br = max(1, br * factor_num // factor_den)
            else:
                d = axis - 2
                bc[d] = max(1, bc[d] * factor_num // factor_den)
            cand = _clamp_plan(
                BlockPlan(bi, tuple(bc), br, base.x_has_rank),
                shape, rank, memory,
            )
            if cand is not None:
                out.append(cand)
    return out


def candidate_plans(
    shape: Sequence[int],
    rank: int,
    memory: Memory,
    itemsize: int = 4,
    *,
    x_has_rank: bool = False,
    max_plans: int = 8,
    lane_pos: int = -1,
) -> list[BlockPlan]:
    """The pallas plan candidates: analytic best (lane-aligned at
    ``lane_pos``, as :func:`~repro.engine.plan.choose_blocks`), its
    perturbations, and the paper's exact uniform-b plan."""
    base = choose_blocks(
        shape, rank, itemsize, memory=memory, x_has_rank=x_has_rank,
        lane_pos=lane_pos,
    )
    plans: list[BlockPlan] = [base]
    plans.extend(_perturbations(base, shape, rank, memory))
    up = uniform_plan(shape, rank, memory)
    up = BlockPlan(  # clamp the paper's uniform b to the actual extents
        min(up.block_i, shape[0]),
        tuple(min(b, s) for b, s in zip(up.block_contract, shape[1:])),
        min(up.block_r, rank),
        x_has_rank,
    )
    if _clamp_plan(up, shape, rank, memory) is not None:
        plans.append(up)
    seen: set[tuple] = set()
    unique: list[BlockPlan] = []
    for p in plans:
        sig = (p.block_i, p.block_contract, p.block_r, p.x_has_rank)
        if sig not in seen:
            seen.add(sig)
            unique.append(p)
    return unique[:max_plans]


def generate_candidates(
    shape: Sequence[int],
    rank: int,
    memory: Memory,
    itemsize: int = 4,
    *,
    backends: Sequence[str] = ("einsum", "blocked_host", "pallas"),
    max_plans: int = 8,
    lane_pos: int = -1,
) -> list[Candidate]:
    """All executors x all plan candidates x (3-way) both kernel variants.
    ``lane_pos`` aligns the analytic plan (:func:`candidate_plans`)."""
    out: list[Candidate] = []
    n = len(shape)
    if "einsum" in backends:
        out.append(Candidate("einsum"))
    if "blocked_host" in backends:
        abstract = Memory.abstract(memory.budget_words)
        b = uniform_plan(shape, rank, abstract).block_i
        out.append(Candidate("blocked_host", block=b))
    if "pallas" in backends and n >= 3:
        variants = KERNEL_VARIANTS if n == 3 else ("generic",)
        for plan in candidate_plans(
            shape, rank, memory, itemsize, max_plans=max_plans,
            lane_pos=lane_pos,
        ):
            for variant in variants:
                out.append(Candidate("pallas", plan=plan, variant=variant))
    return out


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _time_call(fn, warmup: int, reps: int) -> float:
    """Min-of-reps wall time in microseconds (device-synchronized)."""
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def _measure_one(
    cand: Candidate,
    call,
    *,
    reference: jax.Array | None = None,
    rtol: float = 5e-3,
    warmup: int = 1,
    reps: int = 3,
    modeled_bytes: int | None = None,
) -> Measurement:
    """Run, verify (against ``reference``), and time one candidate's
    ``call``. The shared core of full-MTTKRP and partial-contraction
    measurement; failures are recorded, never raised — a candidate that
    crashes or is wrong simply loses."""
    from ..observe.metrics import TUNE_CANDIDATES, registry

    registry().inc(TUNE_CANDIDATES)
    m = Measurement(cand, modeled_bytes=modeled_bytes)
    try:
        got = call()
        jax.block_until_ready(got)
        if reference is not None:
            err = float(jnp.max(jnp.abs(got - reference)))
            scale = float(jnp.max(jnp.abs(reference))) + 1e-30
            if not math.isfinite(err) or err > rtol * scale:
                m.ok = False
                m.error = f"maxerr={err:.3e} (scale {scale:.3e})"
                return m
        m.walltime_us = _time_call(call, warmup, reps)
    except Exception as e:  # noqa: BLE001 - any failing candidate loses
        m.ok = False
        m.error = f"{type(e).__name__}: {e}"
    return m


def _split_for_metric(
    cands: Sequence[Candidate], metric: str, tm_bytes
) -> tuple[list[Candidate], list[Candidate]]:
    """Under the traffic metric, pre-rank pallas candidates by their
    modeled bytes (``tm_bytes``) and time only the best of them against
    the non-pallas executors; returns (timed, modeled_only)."""
    if metric != "traffic":
        return list(cands), []
    pallas = sorted(
        (c for c in cands if c.backend == "pallas"), key=tm_bytes
    )
    rest = [c for c in cands if c.backend != "pallas"]
    return rest + pallas[:1], pallas[1:]


def measure_candidate(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    cand: Candidate,
    *,
    interpret: bool | None = None,
    warmup: int = 1,
    reps: int = 3,
    reference: jax.Array | None = None,
    rtol: float = 5e-3,
) -> Measurement:
    """Time one candidate through ``engine.execute.mttkrp`` and verify it
    against the einsum oracle."""
    from ..engine import execute as engine_execute  # call-time: layer cycle

    rank = next(f.shape[1] for k, f in enumerate(factors) if k != mode)
    perm_shape = (x.shape[mode],) + tuple(
        s for k, s in enumerate(x.shape) if k != mode
    )
    modeled = None
    if cand.plan is not None:
        modeled = int(
            cand.plan.traffic_model(
                perm_shape, rank, x.dtype.itemsize
            )["total_bytes"]
        )

    cand_ctx = ExecutionContext.create(
        backend=cand.backend, interpret=interpret
    )

    def call():
        return engine_execute.mttkrp(
            x, factors, mode, ctx=cand_ctx, plan=cand.plan,
            block=cand.block, kernel_variant=cand.variant,
        )

    return _measure_one(
        cand, call, reference=reference, rtol=rtol, warmup=warmup,
        reps=reps, modeled_bytes=modeled,
    )


def _resolve_metric(metric: str) -> str:
    if metric == "auto":
        return "walltime" if jax.default_backend() == "tpu" else "traffic"
    if metric not in ("walltime", "traffic"):
        raise ValueError(f"unknown metric {metric!r}")
    return metric


def search(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    ctx: ExecutionContext | None = None,
    memory: Memory | None = None,
    metric: str = "auto",
    interpret: bool | None = None,
    warmup: int = 1,
    reps: int = 3,
    max_plans: int = 8,
) -> TuneResult:
    """Measure the candidate space for one MTTKRP problem, return the winner.

    ``ctx`` supplies ``memory``/``interpret`` defaults (explicit arguments
    win). ``metric="traffic"`` (the CPU fallback) pre-ranks pallas plans
    by modeled traffic and times only the best one against the host
    executors; ``metric="walltime"`` times everything.
    """
    from ..observe import trace as _otrace

    _search_t0 = time.perf_counter()
    if ctx is not None:
        memory = memory if memory is not None else ctx.memory
        interpret = interpret if interpret is not None else ctx.interpret
    metric = _resolve_metric(metric)
    perm_shape = (x.shape[mode],) + tuple(
        s for k, s in enumerate(x.shape) if k != mode
    )
    rank = next(f.shape[1] for k, f in enumerate(factors) if k != mode)
    mem = memory or Memory.tpu_vmem(itemsize=x.dtype.itemsize)
    key = cache_key(perm_shape, rank, mode, x.dtype, mem)
    cands = generate_candidates(
        perm_shape, rank, mem, x.dtype.itemsize, max_plans=max_plans,
        lane_pos=mttkrp_lane_pos(x.ndim, mode),
    )
    def tm_bytes(c):
        return int(
            c.plan.traffic_model(
                perm_shape, rank, x.dtype.itemsize
            )["total_bytes"]
        )

    timed, modeled_only = _split_for_metric(cands, metric, tm_bytes)

    from ..core.mttkrp import mttkrp as einsum_oracle

    reference = einsum_oracle(x, factors, mode)
    jax.block_until_ready(reference)
    measurements = [
        measure_candidate(
            x, factors, mode, c, interpret=interpret, warmup=warmup,
            reps=reps, reference=reference,
        )
        for c in timed
    ]
    measurements += [  # recorded for the report, not timed
        Measurement(c, modeled_bytes=tm_bytes(c)) for c in modeled_only
    ]
    ok = [m for m in measurements if m.ok and math.isfinite(m.walltime_us)]
    if not ok:
        raise RuntimeError(
            f"no candidate survived measurement for {key}: "
            + "; ".join(f"{m.candidate.label}: {m.error}" for m in measurements)
        )
    _assign_scores(measurements, metric)
    winner = min(ok, key=lambda m: m.walltime_us).candidate
    search_us = (time.perf_counter() - _search_t0) * 1e6
    if _otrace.should_record(ctx.observe if ctx is not None else False):
        _otrace.record_event(
            "tune_search",
            shape=list(perm_shape),
            rank=int(rank),
            mode=int(mode),
            metric=metric,
            candidates=len(measurements),
            timed=len(timed),
            winner=winner.label,
            search_time_us=search_us,
        )
    return TuneResult(key, winner, measurements, metric)


def _assign_scores(measurements: list[Measurement], metric: str) -> None:
    """score = the quantity the ranking actually used for that candidate:
    modeled bytes for kernel plans under the traffic metric, wall time
    otherwise."""
    for m in measurements:
        if (
            metric == "traffic"
            and m.candidate.backend == "pallas"
            and m.modeled_bytes is not None
        ):
            m.score = float(m.modeled_bytes)
        else:
            m.score = m.walltime_us


def tune_mttkrp(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    ctx: ExecutionContext | None = None,
    memory: Memory | None = None,
    cache: PlanCache | None = None,
    metric: str = "auto",
    interpret: bool | None = None,
    force: bool = False,
    persist: bool = True,
    **search_kwargs,
) -> TuneResult:
    """Search (unless already cached) and persist the winner.

    ``ctx`` supplies ``memory``/``interpret``/cache-handle defaults
    (explicit arguments win). Idempotent: a warm cache short-circuits to
    the stored entry, so a ``backend="auto", tune=True`` context in a
    loop searches exactly once.
    """
    if ctx is not None:
        memory = memory if memory is not None else ctx.memory
        interpret = interpret if interpret is not None else ctx.interpret
        cache = cache if cache is not None else ctx.plan_cache()
    cache = cache if cache is not None else default_cache()
    mem = memory or Memory.tpu_vmem(itemsize=x.dtype.itemsize)
    perm_shape = (x.shape[mode],) + tuple(
        s for k, s in enumerate(x.shape) if k != mode
    )
    rank = next(f.shape[1] for k, f in enumerate(factors) if k != mode)
    key = cache_key(perm_shape, rank, mode, x.dtype, mem)
    if not force:
        entry = cache.get(key)
        if entry is not None:
            winner = Candidate(
                entry.backend, plan=entry.to_plan(), variant=entry.variant,
                block=entry.block,
            )
            best = Measurement(
                winner, walltime_us=entry.walltime_us,
                modeled_bytes=entry.modeled_bytes, score=entry.score,
            )
            return TuneResult(
                key, winner, [best], entry.metric, cache_hit=True
            )
    result = search(
        x, factors, mode, memory=mem, metric=metric, interpret=interpret,
        **search_kwargs,
    )
    best = result.best
    w = result.winner
    cache.put(
        key,
        CacheEntry(
            backend=w.backend,
            plan=plan_to_dict(w.plan) if w.plan is not None else None,
            variant=w.variant,
            block=w.block,
            metric=result.metric,
            score=best.score,
            walltime_us=best.walltime_us,
            modeled_bytes=best.modeled_bytes,
            meta={"candidates": len(result.measurements)},
        ),
        persist=persist,
    )
    return result


# ---------------------------------------------------------------------------
# Partial contractions (dimension-tree edges)
# ---------------------------------------------------------------------------

def tune_partial(
    node: jax.Array,
    factors: Sequence[jax.Array],
    modes: Sequence[int],
    drop: Sequence[int],
    has_rank: bool,
    *,
    ctx: ExecutionContext | None = None,
    memory: Memory | None = None,
    cache: PlanCache | None = None,
    metric: str = "auto",
    interpret: bool | None = None,
    force: bool = False,
    persist: bool = True,
    warmup: int = 1,
    reps: int = 3,
    max_plans: int = 8,
) -> TuneResult:
    """Search + persist the winner for one dimension-tree edge
    (``kind="partial"`` cache entries — what ``contract_partial`` with
    ``backend="auto"`` resolves against).

    ``ctx`` supplies ``memory``/``interpret``/cache-handle defaults
    (explicit arguments win). Candidates: einsum vs the pallas partial
    kernels with the analytic plan and its perturbations. Same metric
    semantics as :func:`search`; idempotent like :func:`tune_mttkrp`.
    """
    from ..engine import execute as engine_execute  # call-time: layer cycle

    if ctx is not None:
        memory = memory if memory is not None else ctx.memory
        interpret = interpret if interpret is not None else ctx.interpret
        cache = cache if cache is not None else ctx.plan_cache()
    metric = _resolve_metric(metric)
    cache = cache if cache is not None else default_cache()
    mem = memory or Memory.tpu_vmem(itemsize=node.dtype.itemsize)
    modes = tuple(modes)
    drop = tuple(drop)
    keep = tuple(m for m in modes if m not in drop)
    pos = {m: i for i, m in enumerate(modes)}
    canon_shape = (
        math.prod(node.shape[pos[m]] for m in keep) if keep else 1,
    ) + tuple(node.shape[pos[m]] for m in drop)
    rank = factors[drop[0]].shape[1]
    key = cache_key(
        canon_shape, rank, 0, node.dtype, mem, kind="partial"
    )
    if not force:
        entry = cache.get(key)
        if entry is not None:
            winner = Candidate(entry.backend, plan=entry.to_plan())
            best = Measurement(
                winner, walltime_us=entry.walltime_us,
                modeled_bytes=entry.modeled_bytes, score=entry.score,
            )
            return TuneResult(
                key, winner, [best], entry.metric, cache_hit=True
            )

    cands = [Candidate("einsum")]
    if len(canon_shape) >= 3:
        cands += [
            Candidate("pallas", plan=p)
            for p in candidate_plans(
                canon_shape, rank, mem, node.dtype.itemsize,
                x_has_rank=has_rank, max_plans=max_plans,
            )
        ]

    def tm_bytes(c):
        return int(
            c.plan.traffic_model(
                canon_shape, rank, node.dtype.itemsize
            )["total_bytes"]
        )

    timed, modeled_only = _split_for_metric(cands, metric, tm_bytes)

    reference = engine_execute.contract_partial(
        node, factors, modes, drop, has_rank,
        ctx=ExecutionContext.create(backend="einsum"),
    )
    jax.block_until_ready(reference)

    def call_for(c):
        c_ctx = ExecutionContext.create(
            backend=c.backend, interpret=interpret
        )

        def call():
            return engine_execute.contract_partial(
                node, factors, modes, drop, has_rank, ctx=c_ctx,
                plan=c.plan,
            )

        return call

    measurements = [
        _measure_one(
            c, call_for(c), reference=reference, warmup=warmup, reps=reps,
            modeled_bytes=tm_bytes(c) if c.plan is not None else None,
        )
        for c in timed
    ]
    measurements += [
        Measurement(c, modeled_bytes=tm_bytes(c)) for c in modeled_only
    ]
    ok = [m for m in measurements if m.ok and math.isfinite(m.walltime_us)]
    if not ok:
        raise RuntimeError(f"no candidate survived measurement for {key}")
    _assign_scores(measurements, metric)
    winner = min(ok, key=lambda m: m.walltime_us)
    cache.put(
        key,
        CacheEntry(
            backend=winner.candidate.backend,
            plan=(
                plan_to_dict(winner.candidate.plan)
                if winner.candidate.plan is not None else None
            ),
            metric=metric,
            score=winner.score,
            walltime_us=winner.walltime_us,
            modeled_bytes=winner.modeled_bytes,
            meta={"candidates": len(measurements)},
        ),
        persist=persist,
    )
    return TuneResult(key, winner.candidate, measurements, metric)


# ---------------------------------------------------------------------------
# Multi-TTM (kind="multi_ttm" cache entries; engine.execute.multi_ttm)
# ---------------------------------------------------------------------------

def _multi_ttm_plan_candidates(
    canon_shape: Sequence[int],
    kernel_ranks: Sequence[int],
    memory: Memory,
    itemsize: int = 4,
    *,
    max_plans: int = 8,
) -> list[MultiTTMPlan]:
    """Analytic plan + halved/doubled per-axis perturbations (Eq-9-feasible
    only) — the Multi-TTM counterpart of :func:`candidate_plans` (the
    Tucker ranks are structural, never perturbed)."""
    base = choose_multi_ttm_blocks(
        canon_shape, kernel_ranks, itemsize, memory=memory
    )
    plans = [base]
    axes = 1 + len(base.block_contract)
    for axis in range(axes):
        for num, den in ((1, 2), (2, 1)):
            bi = base.block_i
            bc = list(base.block_contract)
            if axis == 0:
                bi = max(1, bi * num // den)
            else:
                bc[axis - 1] = max(1, bc[axis - 1] * num // den)
            cand = MultiTTMPlan(bi, tuple(bc), base.ranks)
            if cand.fits(memory):
                plans.append(cand)
    seen: set[tuple] = set()
    unique: list[MultiTTMPlan] = []
    for p in plans:
        sig = (p.block_i, p.block_contract)
        if sig not in seen:
            seen.add(sig)
            unique.append(p)
    return unique[:max_plans]


def tune_multi_ttm(
    x: jax.Array,
    matrices: Sequence[jax.Array],
    keep: int | None,
    *,
    ctx: ExecutionContext | None = None,
    memory: Memory | None = None,
    cache: PlanCache | None = None,
    metric: str = "auto",
    interpret: bool | None = None,
    force: bool = False,
    persist: bool = True,
    warmup: int = 1,
    reps: int = 3,
    max_plans: int = 8,
) -> TuneResult:
    """Search + persist the winner for one Multi-TTM problem
    (``kind="multi_ttm"`` cache entries — what ``multi_ttm`` with
    ``backend="auto"`` resolves against).

    Candidates: einsum, the uniform-b blocked_host schedule, and the
    blocked Kronecker kernel with the analytic plan and its
    perturbations. Same metric semantics as :func:`search`; idempotent
    like :func:`tune_mttkrp`.
    """
    from ..engine import execute as engine_execute  # call-time: layer cycle
    from ..core.bounds import multi_ttm_best_block_size

    if ctx is not None:
        memory = memory if memory is not None else ctx.memory
        interpret = interpret if interpret is not None else ctx.interpret
        cache = cache if cache is not None else ctx.plan_cache()
    metric = _resolve_metric(metric)
    cache = cache if cache is not None else default_cache()
    mem = memory or Memory.tpu_vmem(itemsize=x.dtype.itemsize)
    keep_key = -1 if keep is None else keep
    lead = 0 if keep is None else keep
    canon = (x.shape[lead],) + tuple(
        s for k, s in enumerate(x.shape) if k != lead
    )
    ranks = tuple(
        m.shape[1] for k, m in enumerate(matrices) if k != keep
    )
    kernel_ranks = ranks[1:] if keep is None else ranks
    key = cache_key(canon, ranks, keep_key, x.dtype, mem, kind="multi_ttm")
    if not force:
        entry = cache.get(key)
        if entry is not None:
            winner = Candidate(
                entry.backend, plan=entry.to_plan(), block=entry.block
            )
            best = Measurement(
                winner, walltime_us=entry.walltime_us,
                modeled_bytes=entry.modeled_bytes, score=entry.score,
            )
            return TuneResult(
                key, winner, [best], entry.metric, cache_hit=True
            )

    cands = [Candidate("einsum")]
    # kept-mode-first oracle convention: N dims pair with N-1 contracted
    # ranks (the lead mode plays the kept role for the full core)
    abstract_b = multi_ttm_best_block_size(
        canon, kernel_ranks, Memory.abstract(mem.budget_words).budget_words
    )
    cands.append(Candidate("blocked_host", block=abstract_b))
    if len(canon) >= 3:
        cands += [
            Candidate("pallas", plan=p)
            for p in _multi_ttm_plan_candidates(
                canon, kernel_ranks, mem, x.dtype.itemsize,
                max_plans=max_plans,
            )
        ]

    def tm_bytes(c):
        return int(
            c.plan.traffic_model(canon, x.dtype.itemsize)["total_bytes"]
        )

    timed, modeled_only = _split_for_metric(cands, metric, tm_bytes)

    reference = engine_execute.multi_ttm(
        x, matrices, keep,
        ctx=ExecutionContext.create(backend="einsum"),
    )
    jax.block_until_ready(reference)

    def call_for(c):
        c_ctx = ExecutionContext.create(
            backend=c.backend, interpret=interpret
        )

        def call():
            return engine_execute.multi_ttm(
                x, matrices, keep, ctx=c_ctx, plan=c.plan, block=c.block
            )

        return call

    measurements = [
        _measure_one(
            c, call_for(c), reference=reference, warmup=warmup, reps=reps,
            modeled_bytes=tm_bytes(c) if c.plan is not None else None,
        )
        for c in timed
    ]
    measurements += [
        Measurement(c, modeled_bytes=tm_bytes(c)) for c in modeled_only
    ]
    ok = [m for m in measurements if m.ok and math.isfinite(m.walltime_us)]
    if not ok:
        raise RuntimeError(f"no candidate survived measurement for {key}")
    _assign_scores(measurements, metric)
    winner = min(ok, key=lambda m: m.walltime_us)
    cache.put(
        key,
        CacheEntry(
            backend=winner.candidate.backend,
            plan=(
                plan_to_dict(winner.candidate.plan)
                if winner.candidate.plan is not None else None
            ),
            block=winner.candidate.block,
            metric=metric,
            score=winner.score,
            walltime_us=winner.walltime_us,
            modeled_bytes=winner.modeled_bytes,
            meta={"candidates": len(measurements)},
        ),
        persist=persist,
    )
    return TuneResult(key, winner.candidate, measurements, metric)


# ---------------------------------------------------------------------------
# backend="auto" resolution (cache hit -> tuned; miss -> model-best)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Resolved:
    """What ``backend='auto'`` decided for one problem."""

    backend: str
    plan: BlockPlan | None
    variant: str | None
    block: int | None
    cache_hit: bool
    key: str


def _count_cache(entry) -> None:
    """Tune-cache hit/miss telemetry (always-on, like the dispatch
    counter — registry reads are bracketed with snapshot()/delta())."""
    from ..observe.metrics import (
        TUNE_CACHE_HITS,
        TUNE_CACHE_MISSES,
        registry,
    )

    registry().inc(
        TUNE_CACHE_HITS if entry is not None else TUNE_CACHE_MISSES
    )


def resolve(
    shape: Sequence[int],
    rank: int,
    mode: int,
    dtype,
    memory: Memory | None = None,
    *,
    kind: str = "mttkrp",
    x_has_rank: bool = False,
    cache: PlanCache | None = None,
) -> Resolved:
    """Cache hit → the tuned configuration, exactly as persisted. Miss →
    the analytic model-best: pallas + ``choose_blocks`` on TPU, einsum on
    hosts (where interpret-mode kernels are strictly slower).

    Pure Python over static shapes — safe at trace time.
    """
    itemsize = jnp.dtype(dtype).itemsize
    mem = memory or Memory.tpu_vmem(itemsize=itemsize)
    key = cache_key(shape, rank, mode, dtype, mem, kind=kind)
    cache = cache if cache is not None else default_cache()
    entry = cache.get(key)
    _count_cache(entry)
    if entry is not None:
        return Resolved(
            entry.backend, entry.to_plan(), entry.variant, entry.block,
            True, key,
        )
    if jax.default_backend() == "tpu" and len(shape) >= 3:
        plan = choose_blocks(
            shape, rank, itemsize, memory=mem, x_has_rank=x_has_rank,
            lane_pos=mttkrp_lane_pos(len(shape), mode)
            if kind == "mttkrp" else -1,
        )
        return Resolved("pallas", plan, None, None, False, key)
    return Resolved("einsum", None, None, None, False, key)


def resolve_multi_ttm(
    canon_shape: Sequence[int],
    ranks: Sequence[int],
    keep_key: int,
    dtype,
    memory: Memory | None = None,
    *,
    cache: PlanCache | None = None,
) -> Resolved:
    """``backend="auto"`` resolution for one Multi-TTM problem
    (``kind="multi_ttm"``): cache hit → the tuned configuration exactly;
    miss → pallas + the analytic :func:`choose_multi_ttm_blocks` plan on
    TPU, einsum on hosts.  ``canon_shape`` is kept-mode-first;
    ``ranks`` are *all* contracted ranks (the problem identity);
    ``keep_key`` is the kept mode, or ``-1`` for the full core (whose
    kernel contracts the trailing modes only, so its plan uses
    ``ranks[1:]``).  Pure Python over static shapes — trace-safe.
    """
    itemsize = jnp.dtype(dtype).itemsize
    mem = memory or Memory.tpu_vmem(itemsize=itemsize)
    key = cache_key(
        canon_shape, tuple(ranks), keep_key, dtype, mem, kind="multi_ttm"
    )
    cache = cache if cache is not None else default_cache()
    entry = cache.get(key)
    _count_cache(entry)
    if entry is not None:
        return Resolved(
            entry.backend, entry.to_plan(), entry.variant, entry.block,
            True, key,
        )
    if jax.default_backend() == "tpu" and len(canon_shape) >= 3:
        kernel_ranks = tuple(ranks)[1:] if keep_key == -1 else tuple(ranks)
        plan = choose_multi_ttm_blocks(
            canon_shape, kernel_ranks, itemsize, memory=mem
        )
        return Resolved("pallas", plan, None, None, False, key)
    return Resolved("einsum", None, None, None, False, key)


# ---------------------------------------------------------------------------
# Sweep schedule (kind="sweep" cache entries; core.cp_als sweep="auto")
# ---------------------------------------------------------------------------

def _sweep_pass_bytes(shape: Sequence[int], rank: int, itemsize: int,
                      schedule: str) -> int:
    """Modeled streaming traffic of one ALS sweep's MTTKRP chain.

    ``per_mode`` re-reads the tensor once per mode (N passes).  ``fused``
    reads it twice (P' + the final full MTTKRP) and instead streams the
    rank-augmented partial ``P'`` once to write it and once per middle
    mode to contract it — the arXiv:1708.08976 mode-reuse trade."""
    n = len(shape)
    x_words = math.prod(shape)
    if schedule == "per_mode":
        return n * x_words * itemsize
    p_words = math.prod(shape[:-1]) * rank
    # 2 tensor passes + P' written once + P' read for B0 and each middle mode
    return (2 * x_words + p_words * (n - 1)) * itemsize


def tune_sweep(
    x: jax.Array,
    rank: int,
    *,
    ctx: ExecutionContext | None = None,
    factors: Sequence[jax.Array] | None = None,
    memory: Memory | None = None,
    cache: PlanCache | None = None,
    metric: str = "auto",
    interpret: bool | None = None,
    force: bool = False,
    persist: bool = True,
    warmup: int = 1,
    reps: int = 3,
    rtol: float = 5e-3,
) -> TuneResult:
    """Measure one ALS sweep's MTTKRP chain under the fused (mode-reuse)
    vs the per-mode schedule, persist the winner (``kind="sweep"`` cache
    entries — what ``cp_als(sweep="auto")`` resolves against).

    The chain runs with *fixed* factors, under which every fused-schedule
    B equals the corresponding full MTTKRP — so the fused candidate is
    verified against the per-mode chain, and the timing compares exactly
    the work the schedule changes (the Gram/solve/normalize part is
    identical either way). ``metric="walltime"`` times both chains;
    ``metric="traffic"`` (the CPU default) ranks by the modeled pass
    bytes (:func:`_sweep_pass_bytes`). Idempotent like
    :func:`tune_mttkrp`.
    """
    from dataclasses import replace as dc_replace

    from ..engine import execute as engine_execute  # call-time: layer cycle
    from ..engine.sweep import fused_als_sweep

    if ctx is not None:
        memory = memory if memory is not None else ctx.memory
        interpret = interpret if interpret is not None else ctx.interpret
        cache = cache if cache is not None else ctx.plan_cache()
    metric = _resolve_metric(metric)
    cache = cache if cache is not None else default_cache()
    mem = memory or Memory.tpu_vmem(itemsize=x.dtype.itemsize)
    key = cache_key(x.shape, rank, -1, x.dtype, mem, kind="sweep")
    if not force:
        entry = cache.get(key)
        if entry is not None:
            winner = Candidate(entry.backend, variant=entry.variant)
            best = Measurement(
                winner, walltime_us=entry.walltime_us,
                modeled_bytes=entry.modeled_bytes, score=entry.score,
            )
            return TuneResult(
                key, winner, [best], entry.metric, cache_hit=True
            )

    if factors is None:
        ks = jax.random.split(jax.random.PRNGKey(0), x.ndim)
        factors = [
            jax.random.normal(k, (s, rank), x.dtype)
            for k, s in zip(ks, x.shape)
        ]
    factors = list(factors)
    if ctx is None:
        measure_ctx = ExecutionContext.create(
            backend="auto", interpret=interpret,
        )
    else:
        # the chains replay the already-cached per-contraction decisions;
        # tune=False stops the per-mode searches from re-entering here
        measure_ctx = dc_replace(ctx.local(), tune=False)
    n = x.ndim

    def per_mode_chain():
        return [
            engine_execute.mttkrp(x, factors, m, ctx=measure_ctx)
            for m in range(n)
        ]

    def fused_chain():
        out: list[jax.Array] = []

        def keep(mode, b):
            out.append(b)
            return factors[mode]

        fs = list(factors)
        fused_als_sweep(x, fs, keep, ctx=measure_ctx)
        return out

    backend_tag = ctx.backend if ctx is not None else "auto"
    cands = {
        "per_mode": (Candidate(backend_tag, variant="per_mode"),
                     per_mode_chain),
        "fused": (Candidate(backend_tag, variant="fused"), fused_chain),
    }
    reference = per_mode_chain()
    jax.block_until_ready(reference)
    measurements: list[Measurement] = []
    for schedule, (cand, chain) in cands.items():
        modeled = _sweep_pass_bytes(
            x.shape, rank, x.dtype.itemsize, schedule
        )
        m = Measurement(cand, modeled_bytes=modeled)
        try:
            got = chain()
            jax.block_until_ready(got)
            for g, r in zip(got, reference):
                err = float(jnp.max(jnp.abs(g - r)))
                scale = float(jnp.max(jnp.abs(r))) + 1e-30
                if not math.isfinite(err) or err > rtol * scale:
                    raise AssertionError(
                        f"maxerr={err:.3e} (scale {scale:.3e})"
                    )
            if metric == "walltime":
                m.walltime_us = _time_call(chain, warmup, reps)
                m.score = m.walltime_us
            else:
                m.score = float(modeled)
        except Exception as e:  # noqa: BLE001 - a failing schedule loses
            m.ok = False
            m.error = f"{type(e).__name__}: {e}"
        measurements.append(m)
    ok = [m for m in measurements if m.ok and math.isfinite(m.score)]
    if not ok:
        raise RuntimeError(f"no sweep schedule survived measurement for {key}")
    winner = min(ok, key=lambda m: m.score)
    cache.put(
        key,
        CacheEntry(
            backend=backend_tag,
            variant=winner.candidate.variant,
            metric=metric,
            score=winner.score,
            walltime_us=winner.walltime_us,
            modeled_bytes=winner.modeled_bytes,
            meta={"candidates": len(measurements)},
        ),
        persist=persist,
    )
    return TuneResult(key, winner.candidate, measurements, metric)


def resolve_sweep(
    shape: Sequence[int],
    rank: int,
    dtype,
    memory: Memory | None = None,
    *,
    cache: PlanCache | None = None,
) -> Resolved:
    """``sweep="auto"`` resolution: cache hit → the tuned schedule
    (``variant`` is ``"fused"`` or ``"per_mode"``); miss → ``"fused"``
    for 3-way-and-up tensors (2 tensor passes strictly beat N in the
    pass model), ``"per_mode"`` below that (nothing to reuse). Pure
    Python over static shapes — trace-safe."""
    itemsize = jnp.dtype(dtype).itemsize
    mem = memory or Memory.tpu_vmem(itemsize=itemsize)
    key = cache_key(shape, rank, -1, dtype, mem, kind="sweep")
    cache = cache if cache is not None else default_cache()
    entry = cache.get(key)
    _count_cache(entry)
    if entry is not None:
        return Resolved(
            entry.backend, entry.to_plan(), entry.variant, entry.block,
            True, key,
        )
    variant = "fused" if len(shape) >= 3 else "per_mode"
    return Resolved("auto", None, variant, None, False, key)
