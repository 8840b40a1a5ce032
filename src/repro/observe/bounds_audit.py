"""The optimality-ratio auditor: measured bytes vs model vs lower bound.

The paper's headline claim is a *triple* — what a blocked MTTKRP
actually moves, what the Eq (10) blocked model says it should move, and
what Theorem 4.1 says it *must* move.  This module renders that triple
as a runtime metric: for any jitted engine call it compiles the program,
walks the HLO with the existing analyzers
(:func:`repro.analysis.hlo_cost.analyze_module` for memory traffic,
:func:`repro.distributed.hlo.parse_collectives` for collectives) and
emits one :class:`AuditRow` per dispatch with

    measured_bytes   — HLO fusion-boundary bytes of the compiled program
    modeled_words    — ``BlockPlan.eq10_words`` (Eq 10) /
                       ``MultiTTMPlan.model_words``
    lower_bound_words— ``seq_lb_memory`` (Thm 4.1) /
                       ``multi_ttm_seq_lb_memory``, clamped at 0

plus the two ratios that summarize them (``measured / modeled`` — how
honest the model is; ``modeled / bound`` — how close to optimal the
schedule is).  Rows are also recorded into the active
:class:`~repro.observe.trace.Trace` (kind ``"bounds_audit"``), so the
report CLI can table them next to ordinary dispatch spans.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Sequence

from .trace import record_event


@dataclass(frozen=True)
class AuditRow:
    """One dispatch's measured / modeled / lower-bound triple (bytes are
    HLO-measured; words are dtype-free model counts)."""

    name: str
    itemsize: int
    measured_bytes: float
    modeled_words: float
    lower_bound_words: float

    @property
    def modeled_bytes(self) -> float:
        return self.modeled_words * self.itemsize

    @property
    def lower_bound_bytes(self) -> float:
        return self.lower_bound_words * self.itemsize

    @property
    def measured_over_model(self) -> float | None:
        """How far above the blocked model the compiled program runs
        (1.0 = the model is exact; None when the model is degenerate)."""
        if self.modeled_bytes <= 0:
            return None
        return self.measured_bytes / self.modeled_bytes

    @property
    def model_over_bound(self) -> float | None:
        """The optimality ratio: modeled traffic over the Thm-4.1 floor
        (None when the bound clamps to 0 — small problems fit in fast
        memory and the bound says nothing)."""
        if self.lower_bound_bytes <= 0:
            return None
        return self.modeled_bytes / self.lower_bound_bytes

    def to_dict(self) -> dict:
        d = asdict(self)
        d["modeled_bytes"] = self.modeled_bytes
        d["lower_bound_bytes"] = self.lower_bound_bytes
        d["measured_over_model"] = self.measured_over_model
        d["model_over_bound"] = self.model_over_bound
        return d


def _audit_compiled(
    compiled,
    *,
    name: str,
    itemsize: int,
    modeled_words: float,
    lower_bound_words: float,
) -> AuditRow:
    """Walk one compiled program's HLO and build (+record) the row."""
    from ..analysis.hlo_cost import analyze_compiled

    cost = analyze_compiled(compiled)
    row = AuditRow(
        name=name,
        itemsize=int(itemsize),
        measured_bytes=float(cost.bytes),
        modeled_words=float(modeled_words),
        lower_bound_words=float(lower_bound_words),
    )
    record_event(
        "bounds_audit",
        name=name,
        itemsize=row.itemsize,
        measured_bytes=row.measured_bytes,
        modeled_words=row.modeled_words,
        lower_bound_words=row.lower_bound_words,
        measured_over_model=row.measured_over_model,
        model_over_bound=row.model_over_bound,
        measured_collective_bytes=float(cost.collective_ring_bytes),
    )
    return row


def audit_mttkrp(
    x,
    factors: Sequence,
    mode: int,
    *,
    ctx=None,
) -> AuditRow:
    """Compile ``mttkrp(x, factors, mode, ctx=ctx)`` under jit and audit
    it: measured HLO bytes vs the Eq-10 blocked model vs the Thm-4.1
    memory-dependent lower bound (both evaluated against ``ctx.memory``,
    defaulting to the resolver's TPU-VMEM budget)."""
    import jax

    from ..core.bounds import seq_lb_memory
    from ..engine.context import ExecutionContext
    from ..engine.execute import _mode_first, mttkrp
    from ..engine.plan import Memory, choose_blocks, mttkrp_lane_pos

    if ctx is None:
        ctx = ExecutionContext.default()
    rank = next(f.shape[1] for k, f in enumerate(factors) if k != mode)
    itemsize = x.dtype.itemsize
    mem = ctx.memory or Memory.tpu_vmem(itemsize=itemsize)
    plan = choose_blocks(
        _mode_first(x.shape, mode), rank, itemsize, memory=mem,
        lane_pos=mttkrp_lane_pos(x.ndim, mode),
    )
    modeled = plan.eq10_words(_mode_first(x.shape, mode), rank)
    lb = max(seq_lb_memory(x.shape, rank, mem.budget_words), 0.0)

    def call(xx, *fs):
        return mttkrp(xx, list(fs), mode, ctx=ctx)

    compiled = jax.jit(call).lower(x, *factors).compile()
    return _audit_compiled(
        compiled,
        name=f"mttkrp[shape={tuple(x.shape)},rank={rank},mode={mode}]",
        itemsize=itemsize,
        modeled_words=modeled,
        lower_bound_words=lb,
    )


def audit_multi_ttm(
    x,
    matrices: Sequence,
    keep: int | None = None,
    *,
    ctx=None,
) -> AuditRow:
    """The Multi-TTM analog of :func:`audit_mttkrp`: measured HLO bytes
    vs ``MultiTTMPlan.model_words`` vs ``multi_ttm_seq_lb_memory``."""
    import jax

    from ..core.bounds import multi_ttm_seq_lb_memory
    from ..engine.context import ExecutionContext
    from ..engine.execute import _keep_first, multi_ttm
    from ..engine.plan import Memory, choose_multi_ttm_blocks

    if ctx is None:
        ctx = ExecutionContext.default()
    ranks = tuple(
        m.shape[1] for k, m in enumerate(matrices) if k != keep
    )
    itemsize = x.dtype.itemsize
    mem = ctx.memory or Memory.tpu_vmem(itemsize=itemsize)
    canon = _keep_first(x.shape, 0 if keep is None else keep)
    kernel_ranks = ranks[1:] if keep is None else ranks
    plan = choose_multi_ttm_blocks(canon, kernel_ranks, itemsize, memory=mem)
    modeled = plan.model_words(canon)
    lb = max(
        multi_ttm_seq_lb_memory(x.shape, ranks, mem.budget_words), 0.0
    )

    def call(xx, *ms):
        ms = list(ms)
        if keep is not None:
            ms.insert(keep, None)
        return multi_ttm(xx, ms, keep, ctx=ctx)

    concrete = [m for k, m in enumerate(matrices) if k != keep]
    compiled = jax.jit(call).lower(x, *concrete).compile()
    return _audit_compiled(
        compiled,
        name=(
            f"multi_ttm[shape={tuple(x.shape)},ranks={ranks},keep={keep}]"
        ),
        itemsize=itemsize,
        modeled_words=modeled,
        lower_bound_words=lb,
    )
