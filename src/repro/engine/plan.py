"""The planner: one source of truth for MTTKRP blocking and traffic models.

Everything the paper derives about *how to block* lives here:

  * :class:`Memory` — an explicit two-level-memory descriptor (capacity,
    lane/sublane alignment, itemsize). ``Memory.tpu_vmem()`` is the VMEM of
    the Pallas kernels; ``Memory.abstract(M)`` is the paper's §II-C abstract
    M-word fast memory (no alignment), used by the simulator.
  * :class:`BlockPlan` — block sizes for one contraction, with the Eq-9
    working-set check and the Eq-10 traffic model as *methods*, so the
    kernel wrapper, the simulator, and the benchmarks all quote the same
    numbers from the same object.
  * :func:`choose_blocks` — TPU-aligned block selection against a Memory
    budget (the paper's b ~ (alpha*M)^{1/N} with MXU/VPU alignment floors).
    ``x_has_rank=True`` plans the dimension tree's rank-augmented partial
    contractions, whose tensor tile carries an extra rank axis.
  * :func:`best_uniform_block` / :func:`uniform_block_feasible` — the
    paper's exact uniform-b selection (Eq 9), re-exported for the simulator
    so block selection has a single import path.

  * :class:`MultiTTMPlan` / :func:`choose_multi_ttm_blocks` /
    :func:`uniform_multi_ttm_plan` — the Multi-TTM (Tucker/HOSVD,
    arXiv:2207.10437) counterparts: kept-mode + contraction blocks with
    the small per-mode Tucker ranks structural (never tiled), the
    Kronecker weight block in the Eq-9-analog working set, and the
    Eq-10-analog traffic model pinned against
    ``core.bounds.multi_ttm_blocked_cost``.

Formula provenance stays in :mod:`repro.core.bounds` (the pure equation
library); this module is the only place that turns those equations into
decisions.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

from ..core.bounds import best_block_size, blocked_feasible_b, seq_blocked_cost

LANE = 128
SUBLANE = 8
VMEM_BYTES = 16 * 2 ** 20  # v5e per-core VMEM
VMEM_BUDGET = VMEM_BYTES // 2  # leave headroom for double-buffering


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class Memory:
    """Two-level fast-memory descriptor the planner blocks against."""

    budget_bytes: int
    lane: int = 1
    sublane: int = 1
    itemsize: int = 4

    @classmethod
    def tpu_vmem(cls, budget_bytes: int = VMEM_BUDGET, itemsize: int = 4) -> "Memory":
        """The Pallas kernels' fast memory: VMEM with MXU alignment."""
        return cls(budget_bytes, lane=LANE, sublane=SUBLANE, itemsize=itemsize)

    @classmethod
    def abstract(cls, words: int, itemsize: int = 1) -> "Memory":
        """The paper's abstract M-word fast memory (§II-C): no alignment."""
        return cls(words * itemsize, lane=1, sublane=1, itemsize=itemsize)

    @property
    def budget_words(self) -> int:
        return self.budget_bytes // self.itemsize

    def with_itemsize(self, itemsize: int) -> "Memory":
        """Same memory, re-described for a different element width — the
        dtype-aware planning hook: a bf16 compute dtype halves ``itemsize``
        so ``budget_words`` doubles and every Eq-9 fit admits larger
        blocks on the *same physical budget*."""
        if itemsize == self.itemsize:
            return self
        return Memory(self.budget_bytes, self.lane, self.sublane, itemsize)


@dataclass(frozen=True)
class BlockPlan:
    """Block sizes for one (possibly rank-augmented) MTTKRP-shaped
    contraction: output rows ``block_i``, contraction dims
    ``block_contract``, rank tile ``block_r``.

    ``x_has_rank`` marks dimension-tree partial contractions whose tensor
    operand already carries the rank axis (tile holds ``bi*prod(bc)*br``
    words instead of ``bi*prod(bc)``).
    """

    block_i: int
    block_contract: tuple[int, ...]
    block_r: int
    x_has_rank: bool = False

    # -- Eq 9: working set -------------------------------------------------
    def kernel_block_words(self) -> int:
        """VMEM words held by the kernel's BlockSpec operand tiles alone:
        X tile + factor tiles + output tile.  This is the part of the Eq-9
        working set that the Pallas ``BlockSpec`` machinery stages; the
        static kernel analyzer (:mod:`repro.verify.kernels`) recomputes it
        from the captured block shapes and pins the two against each other
        via ``working_set_words() == kernel_block_words() +
        weight_scratch_words()``."""
        prod_c = math.prod(self.block_contract)
        x_tile = self.block_i * prod_c * (self.block_r if self.x_has_rank else 1)
        f_tiles = sum(c * self.block_r for c in self.block_contract)
        out = self.block_i * self.block_r
        return x_tile + f_tiles + out

    def weight_scratch_words(self) -> int:
        """VMEM words of the Khatri-Rao weight block ``prod(bc) * br`` the
        kernel builds in registers/VMEM each grid step — part of Eq 9 but
        *not* a BlockSpec operand (it never touches HBM)."""
        return math.prod(self.block_contract) * self.block_r

    def working_set_words(self, itemsize: int = 4) -> int:
        """VMEM words held per grid step (Eq 9 analogue): X tile + factor
        tiles + KRP block + output tile."""
        del itemsize  # word count is itemsize-free; kept for API stability
        return self.kernel_block_words() + self.weight_scratch_words()

    def fits(self, memory: Memory) -> bool:
        """Eq-9 feasibility against an explicit memory descriptor."""
        return self.working_set_words() * memory.itemsize <= memory.budget_bytes

    # -- shapes ------------------------------------------------------------
    def blocks_per_mode(self) -> tuple[int, ...]:
        """Per-mode block sizes with the output mode first (paper's b_k)."""
        return (self.block_i,) + tuple(self.block_contract)

    def padded_shape(self, shape: Sequence[int]) -> tuple[int, ...]:
        """Input shape rounded up to block multiples (output mode first)."""
        blocks = self.blocks_per_mode()
        return tuple(_round_up(s, b) for s, b in zip(shape, blocks))

    def grid(self, shape: Sequence[int], rank: int) -> tuple[int, ...]:
        """Pallas grid (r, i, c_1..c_{N-1}) for the padded problem."""
        padded = self.padded_shape(shape)
        r_pad = _round_up(rank, self.block_r)
        return (r_pad // self.block_r, padded[0] // self.block_i) + tuple(
            padded[1 + d] // self.block_contract[d]
            for d in range(len(self.block_contract))
        )

    # -- Eq 10: traffic ----------------------------------------------------
    def eq10_words(self, shape: Sequence[int], rank: int) -> int:
        """The paper's Eq (10) bound generalized to per-mode block sizes.

        Per block (prod_k ceil(I_k/b_k) of them), each of the R rank
        columns loads the N-1 factor subvectors (sum of their b_k) and
        loads+stores the output subvector (2*b_out); plus one pass over the
        tensor. With a uniform block b this is exactly
        ``core.bounds.seq_blocked_cost``: I + prod ceil(I_k/b) * R*(N+1)*b.
        """
        blocks = self.blocks_per_mode()
        nblocks = math.prod(
            math.ceil(s / b) for s, b in zip(shape, blocks)
        )
        per_block = rank * (sum(blocks) + blocks[0])
        return math.prod(shape) + nblocks * per_block

    def traffic_model(
        self, shape: Sequence[int], rank: int, itemsize: int = 4
    ) -> dict[str, int]:
        """Modeled HBM<->VMEM traffic of the kernel (bytes), mirroring the
        BlockSpec fetch rules: a block is re-fetched when its mapped index
        changes between consecutive grid steps.

        Grid (3-way): (i, r, j, k), k innermost. X fetched every step;
        factor k every step; factor j once per k-sweep; O written once per
        (i, r). ``eq10_bytes`` is the paper-ideal Eq-10 cost for the same
        per-mode block sizes (see :meth:`eq10_words`).
        """
        n = len(shape)
        padded = self.padded_shape(shape)
        r_pad = _round_up(rank, self.block_r)
        gi = padded[0] // self.block_i
        gr = r_pad // self.block_r
        gc = [
            padded[1 + d] // self.block_contract[d] for d in range(n - 1)
        ]
        steps = gi * gr * math.prod(gc)
        x_words = self.block_i * math.prod(self.block_contract)
        if self.x_has_rank:
            x_words *= self.block_r
        x_bytes = steps * x_words * itemsize
        f_bytes = 0
        # factor d re-fetched when (c_d, r) changes; c_d sweeps with all
        # inner dims constant-free: fetches = gi*gr*prod(gc[:d+1])
        run = gi * gr
        for d in range(n - 1):
            run *= gc[d]
            f_bytes += run * self.block_contract[d] * self.block_r * itemsize
        o_bytes = gi * gr * self.block_i * self.block_r * itemsize
        total = x_bytes + f_bytes + o_bytes
        return {
            "x_bytes": x_bytes,
            "factor_bytes": f_bytes,
            "out_bytes": o_bytes,
            "total_bytes": total,
            "eq10_bytes": self.eq10_words(shape, rank) * itemsize,
            "steps": steps,
            "working_set_bytes": self.working_set_words() * itemsize,
        }


def mode_first(shape: Sequence[int], mode: int) -> tuple[int, ...]:
    """``shape`` reordered output mode first, the other modes after it in
    axis order: the order every :class:`BlockPlan` is written in."""
    return (shape[mode],) + tuple(s for k, s in enumerate(shape) if k != mode)


def mttkrp_lane_pos(ndim: int, mode: int, variant: str | None = None) -> int:
    """Position, in a :class:`BlockPlan`'s output-mode-first order, of the
    block the lane width aligns: the block of the tensor's minor axis as
    the kernel reads it.  The 3-way kernel reads X in its stored layout
    (unless ``variant="generic"``), whose lane axis (axis 2) comes first
    when it is the output mode; every other kernel reads a mode-first
    copy, whose lane axis is last."""
    if ndim == 3 and mode == 2 and variant != "generic":
        return 0
    return ndim - 1


def choose_blocks(
    shape: Sequence[int],
    rank: int,
    itemsize: int = 4,
    vmem_budget: int = VMEM_BUDGET,
    *,
    memory: Memory | None = None,
    x_has_rank: bool = False,
    lane_pos: int = -1,
) -> BlockPlan:
    """Pick TPU-aligned block sizes fitting the memory budget.

    Strategy (mirrors the paper's b ~ (alpha*M)^{1/N} with TPU alignment):
    output mode and rank tiles start at MXU-friendly 128; the minor
    contraction dim at 128, other contraction dims at 8; then shrink the
    largest contributor until the working set fits.  ``shape`` is in
    output-mode-first order; ``lane_pos`` is the position in it of the
    tensor's lane axis as the kernel reads it (:func:`mttkrp_lane_pos`;
    the last by default).  That block is a multiple of the lane width
    (128), every other block a multiple of the sublane count (8).

    Degenerate extents never over-pad: a dimension smaller than its
    alignment unit (a mode of size 1, a rank below the lane width) gets
    the *full extent* as its block — the arrays are then padded to their
    own size (no padding at all) rather than to a whole alignment tile,
    and the traffic model stops charging phantom bytes. If even the
    aligned-minimal plan exceeds the budget (only reachable for memories
    far below real VMEM, e.g. abstract/simulated budgets), alignment is
    relaxed rather than returning an Eq-9-infeasible plan.
    """
    if memory is None:
        memory = Memory.tpu_vmem(vmem_budget, itemsize)
    lane, sublane = memory.lane, memory.sublane
    n = len(shape)
    units = [lane if p == lane_pos % n else sublane for p in range(n)]

    def start(extent: int, unit: int, pref: int) -> int:
        if extent <= unit:  # sub-unit dim: full extent, zero padding
            return max(1, extent)
        return min(_round_up(extent, unit), pref)

    def floor(extent: int, unit: int) -> int:
        return max(1, extent) if extent <= unit else unit

    bi = start(shape[0], units[0], 128)
    br = start(rank, lane, 512)
    bc = [
        start(shape[d], units[d], 128 if d == n - 1 else max(sublane, 8))
        for d in range(1, n)
    ]
    fi = floor(shape[0], units[0])
    fr = floor(rank, lane)
    fc = [floor(shape[d], units[d]) for d in range(1, n)]
    plan = BlockPlan(bi, tuple(bc), br, x_has_rank)
    # shrink until it fits (keep alignment floors)
    while not plan.fits(memory):
        bi, br = plan.block_i, plan.block_r
        bc = list(plan.block_contract)
        if br > fr:
            br = max(fr, br // 2)
        elif bi > fi:
            bi = max(fi, bi // 2)
        else:
            shrunk = False
            for d in range(len(bc) - 1):  # shrink non-minor contraction dims
                if bc[d] > fc[d]:
                    bc[d] = max(fc[d], bc[d] // 2)
                    shrunk = True
                    break
            if not shrunk:
                if bc and bc[-1] > fc[-1]:
                    bc[-1] = max(fc[-1], bc[-1] // 2)
                else:
                    break  # aligned floors reached; relax below
        plan = BlockPlan(bi, tuple(bc), br, x_has_rank)
    # last resort: relax alignment (largest contributor first) so the
    # returned plan satisfies Eq 9 whenever any plan can
    while not plan.fits(memory):
        dims = [plan.block_i, *plan.block_contract, plan.block_r]
        j = max(range(len(dims)), key=lambda k: dims[k])
        if dims[j] <= 1:
            break  # all-1 blocks; nothing fits this memory
        dims[j] //= 2
        plan = BlockPlan(dims[0], tuple(dims[1:-1]), dims[-1], x_has_rank)
    return plan


# ---------------------------------------------------------------------------
# Fused-sweep planning (the arXiv:1708.08976 mode-reuse schedule)
# ---------------------------------------------------------------------------

def fused_pair_working_set_words(plan: BlockPlan) -> int:
    """Eq-9 analogue for the fused (B^(0), P) pair kernel
    (:mod:`repro.kernels.sweep`): the per-mode working set plus the
    rank-augmented partial tile ``bi * prod(bc[:-1]) * br`` that the second
    output keeps VMEM-resident across the innermost contraction sweep.

    X tile + factor tiles + KRP weight + B^(0) tile + P tile — the
    mode-reuse schedule pays one extra output tile to avoid re-streaming
    the tensor once per mode."""
    return fused_pair_kernel_block_words(plan) + plan.weight_scratch_words()


def fused_pair_kernel_block_words(plan: BlockPlan) -> int:
    """BlockSpec-operand share of :func:`fused_pair_working_set_words`:
    X tile + factor tiles + B^(0) tile + P tile, excluding the in-kernel
    KRP weight scratch (``plan.weight_scratch_words()``).  The static
    kernel analyzer pins the fused pair kernel's captured block shapes
    against this claim."""
    prod_c = math.prod(plan.block_contract)
    x_tile = plan.block_i * prod_c
    f_tiles = sum(c * plan.block_r for c in plan.block_contract)
    b0_tile = plan.block_i * plan.block_r
    p_tile = plan.block_i * math.prod(plan.block_contract[:-1]) * plan.block_r
    return x_tile + f_tiles + b0_tile + p_tile


def choose_sweep_blocks(
    shape: Sequence[int],
    rank: int,
    itemsize: int = 4,
    vmem_budget: int = VMEM_BUDGET,
    *,
    memory: Memory | None = None,
) -> BlockPlan:
    """Block selection for the fused pair kernel: start from the per-mode
    MTTKRP plan, then keep shrinking until the *fused* working set
    (:func:`fused_pair_working_set_words`) also fits — same shrink order
    as :func:`choose_blocks` (rank, then output rows, then non-minor
    contraction dims, then the minor dim, then relax alignment)."""
    if memory is None:
        memory = Memory.tpu_vmem(vmem_budget, itemsize)
    lane, sublane = memory.lane, memory.sublane
    n = len(shape)
    plan = choose_blocks(shape, rank, memory=memory)

    def fused_fits(p: BlockPlan) -> bool:
        return (
            fused_pair_working_set_words(p) * memory.itemsize
            <= memory.budget_bytes
        )

    def floor(extent: int, unit: int) -> int:
        return max(1, extent) if extent <= unit else unit

    fi = floor(shape[0], sublane)
    fr = floor(rank, lane)
    fc = [
        floor(shape[d], lane if d == n - 1 else sublane) for d in range(1, n)
    ]
    while not fused_fits(plan):
        bi, br = plan.block_i, plan.block_r
        bc = list(plan.block_contract)
        if br > fr:
            br = max(fr, br // 2)
        elif bi > fi:
            bi = max(fi, bi // 2)
        else:
            shrunk = False
            for d in range(len(bc) - 1):
                if bc[d] > fc[d]:
                    bc[d] = max(fc[d], bc[d] // 2)
                    shrunk = True
                    break
            if not shrunk:
                if bc and bc[-1] > fc[-1]:
                    bc[-1] = max(fc[-1], bc[-1] // 2)
                else:
                    break
        plan = BlockPlan(bi, tuple(bc), br)
    while not fused_fits(plan):
        dims = [plan.block_i, *plan.block_contract, plan.block_r]
        j = max(range(len(dims)), key=lambda k: dims[k])
        if dims[j] <= 1:
            break
        dims[j] //= 2
        plan = BlockPlan(dims[0], tuple(dims[1:-1]), dims[-1])
    return plan


# ---------------------------------------------------------------------------
# Multi-TTM planning (the Tucker/HOSVD kernel, arXiv:2207.10437)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MultiTTMPlan:
    """Block sizes for one canonical Multi-TTM contraction: kept mode first
    (``block_i`` rows), contracted tensor modes next (``block_contract``),
    each contracted mode paired with its small Tucker rank ``ranks[d]``.

    Unlike :class:`BlockPlan` there is no rank tile: the R_d are the
    *small* dimensions of the problem (Tucker ranks), so every tile keeps
    them whole and the Kronecker weight block
    ``W[(c_1..c_k), (r_1..r_k)] = prod_d A_d(c_d, r_d)`` is built in fast
    memory, never materialized in HBM — the Multi-TTM analog of the
    MTTKRP kernels' Khatri-Rao weight.
    """

    block_i: int
    block_contract: tuple[int, ...]
    ranks: tuple[int, ...]

    # -- Eq 9 analog: working set -----------------------------------------
    def kernel_block_words(self) -> int:
        """Fast-memory words of the kernel's BlockSpec operand tiles alone:
        tensor tile + matrix tiles + output tile.  The Kronecker weight
        block is in-kernel scratch (:meth:`weight_scratch_words`); the
        static kernel analyzer pins the captured block shapes against this
        claim."""
        prod_c = math.prod(self.block_contract)
        prod_r = math.prod(self.ranks)
        x_tile = self.block_i * prod_c
        m_tiles = sum(c * r for c, r in zip(self.block_contract, self.ranks))
        out = self.block_i * prod_r
        return x_tile + m_tiles + out

    def weight_scratch_words(self) -> int:
        """Fast-memory words the kernel builds in VMEM for one slab of
        the tile (never materialized in HBM): the slab's Kronecker block
        ``W[c_{k-1}, (r_1..r_{k-1})]`` and its copy broadcast over the
        ``bi`` rows for the batched contraction, plus the slab's product
        with the minor matrix, ``bi * c_{k-1} * R_k``.  The kernel applies
        the Kronecker weight one ``c_{k-1}`` slab at a time, so the full
        ``prod(bc) * prod(R_d)`` block is never resident."""
        c_sub = self.block_contract[-2] if len(self.block_contract) > 1 else 1
        r_lead = math.prod(self.ranks[:-1])
        return c_sub * (r_lead * (1 + self.block_i)
                        + self.block_i * self.ranks[-1])

    def working_set_words(self) -> int:
        """Fast-memory words per grid step: tensor tile + matrix tiles +
        output tile + the per-slab Kronecker scratch (the Multi-TTM Eq-9
        analog; uniform-b form in
        ``core.bounds.multi_ttm_blocked_feasible_b``)."""
        return self.kernel_block_words() + self.weight_scratch_words()

    def fits(self, memory: Memory) -> bool:
        return self.working_set_words() * memory.itemsize <= memory.budget_bytes

    # -- shapes ------------------------------------------------------------
    def blocks_per_mode(self) -> tuple[int, ...]:
        return (self.block_i,) + tuple(self.block_contract)

    def padded_shape(self, shape: Sequence[int]) -> tuple[int, ...]:
        blocks = self.blocks_per_mode()
        return tuple(_round_up(s, b) for s, b in zip(shape, blocks))

    def grid(self, shape: Sequence[int]) -> tuple[int, ...]:
        """Pallas grid (i, c_1..c_k) for the padded problem (no rank axis:
        the R_d stay whole per tile)."""
        padded = self.padded_shape(shape)
        return (padded[0] // self.block_i,) + tuple(
            padded[1 + d] // self.block_contract[d]
            for d in range(len(self.block_contract))
        )

    # -- Eq 10 analog: traffic --------------------------------------------
    def model_words(self, shape: Sequence[int]) -> int:
        """The blocked Multi-TTM cost generalized to per-mode block sizes:
        one pass over the tensor plus, per block, the matrix subblocks
        (sum_d b_d R_d) and one load+store of the output subblock
        (2 b_i prod R_d). With a uniform b this equals
        ``core.bounds.multi_ttm_blocked_cost`` exactly."""
        blocks = self.blocks_per_mode()
        nblocks = math.prod(
            math.ceil(s / b) for s, b in zip(shape, blocks)
        )
        per_block = sum(
            b * r for b, r in zip(self.block_contract, self.ranks)
        ) + 2 * self.block_i * math.prod(self.ranks)
        return math.prod(shape) + nblocks * per_block

    def traffic_model(
        self, shape: Sequence[int], itemsize: int = 4
    ) -> dict[str, int]:
        """Modeled HBM<->VMEM traffic (bytes) of the Multi-TTM kernel,
        mirroring its BlockSpec fetch rules: grid (i, c_1..c_k), c
        innermost; the tensor is streamed once; matrix d is re-fetched
        when c_d changes; the output tile is written once per i block
        (output-stationary). ``model_bytes`` is the paper-ideal cost for
        the same per-mode blocks (:meth:`model_words`)."""
        n = len(shape)
        padded = self.padded_shape(shape)
        gi = padded[0] // self.block_i
        gc = [
            padded[1 + d] // self.block_contract[d] for d in range(n - 1)
        ]
        steps = gi * math.prod(gc)
        x_bytes = steps * self.block_i * math.prod(self.block_contract) \
            * itemsize
        m_bytes = 0
        run = gi
        for d in range(n - 1):
            run *= gc[d]
            m_bytes += run * self.block_contract[d] * self.ranks[d] * itemsize
        o_bytes = gi * self.block_i * math.prod(self.ranks) * itemsize
        total = x_bytes + m_bytes + o_bytes
        return {
            "x_bytes": x_bytes,
            "matrix_bytes": m_bytes,
            "out_bytes": o_bytes,
            "total_bytes": total,
            "model_bytes": self.model_words(shape) * itemsize,
            "steps": steps,
            "working_set_bytes": self.working_set_words() * itemsize,
        }


def choose_multi_ttm_blocks(
    shape: Sequence[int],
    ranks: Sequence[int],
    itemsize: int = 4,
    *,
    memory: Memory | None = None,
) -> MultiTTMPlan:
    """Pick blocks for a canonical Multi-TTM (kept mode first) against a
    memory budget — the Multi-TTM counterpart of :func:`choose_blocks`.

    The Tucker ranks are never tiled (they are the small dimensions); the
    kept-mode and contraction blocks follow the same alignment-then-shrink
    strategy as the MTTKRP planner, with the same degenerate-extent and
    relax-below-budget guarantees."""
    if memory is None:
        memory = Memory.tpu_vmem(itemsize=itemsize)
    lane, sublane = memory.lane, memory.sublane
    n = len(shape)
    ranks = tuple(int(r) for r in ranks)

    def start(extent: int, unit: int, pref: int) -> int:
        if extent <= unit:
            return max(1, extent)
        return min(_round_up(extent, unit), pref)

    def floor(extent: int, unit: int) -> int:
        return max(1, extent) if extent <= unit else unit

    bi = start(shape[0], sublane, 128)
    bc: list[int] = []
    for d in range(1, n):
        if d == n - 1:
            bc.append(start(shape[d], lane, 128))
        else:
            bc.append(start(shape[d], sublane, max(sublane, 8)))
    fi = floor(shape[0], sublane)
    fc = [
        floor(shape[d], lane if d == n - 1 else sublane) for d in range(1, n)
    ]
    plan = MultiTTMPlan(bi, tuple(bc), ranks)
    while not plan.fits(memory):
        bi = plan.block_i
        bc = list(plan.block_contract)
        if bi > fi:
            bi = max(fi, bi // 2)
        else:
            shrunk = False
            for d in range(len(bc) - 1):
                if bc[d] > fc[d]:
                    bc[d] = max(fc[d], bc[d] // 2)
                    shrunk = True
                    break
            if not shrunk:
                if bc and bc[-1] > fc[-1]:
                    bc[-1] = max(fc[-1], bc[-1] // 2)
                else:
                    break
        plan = MultiTTMPlan(bi, tuple(bc), ranks)
    while not plan.fits(memory):
        dims = [plan.block_i, *plan.block_contract]
        j = max(range(len(dims)), key=lambda k: dims[k])
        if dims[j] <= 1:
            break  # all-1 blocks: the ranks alone exceed this memory
        dims[j] //= 2
        plan = MultiTTMPlan(dims[0], tuple(dims[1:]), ranks)
    return plan


def uniform_multi_ttm_plan(
    dims: Sequence[int], ranks: Sequence[int], memory: Memory | int
) -> MultiTTMPlan:
    """A :class:`MultiTTMPlan` with the paper's uniform b in every tensor
    mode; ``plan.model_words(dims)`` then equals
    ``core.bounds.multi_ttm_blocked_cost(dims, ranks, b)`` exactly."""
    from ..core.bounds import multi_ttm_best_block_size, multi_ttm_blocked_cost

    mem_words = memory.budget_words if isinstance(memory, Memory) else memory
    b = multi_ttm_best_block_size(dims, ranks, mem_words)
    plan = MultiTTMPlan(b, (b,) * (len(dims) - 1), tuple(int(r) for r in ranks))
    assert int(plan.model_words(dims)) == int(
        multi_ttm_blocked_cost(dims, ranks, b)
    )
    return plan


def mttkrp_traffic_model(
    shape: Sequence[int], rank: int, plan: BlockPlan, itemsize: int = 4
) -> dict[str, int]:
    """Back-compat functional spelling of :meth:`BlockPlan.traffic_model`."""
    return plan.traffic_model(shape, rank, itemsize)


# ---------------------------------------------------------------------------
# Uniform-b planning (the paper's exact Eq 9/10 setting; simulator + benches)
# ---------------------------------------------------------------------------

def best_uniform_block(dims: Sequence[int], memory: Memory | int) -> int:
    """Largest uniform b with b^N + N*b <= M (Eq 9); the simulator's and the
    sequential benchmarks' block selection. ``memory`` may be a word count
    or a :class:`Memory` (its word budget is used)."""
    mem_words = memory.budget_words if isinstance(memory, Memory) else memory
    return best_block_size(dims, mem_words)


def uniform_block_feasible(n: int, block: int, memory: Memory | int) -> bool:
    """Eq (9)/(20): b^N + N*b <= M, against a Memory or raw word count."""
    mem_words = memory.budget_words if isinstance(memory, Memory) else memory
    return blocked_feasible_b(n, block, mem_words)


def uniform_plan(dims: Sequence[int], rank: int, memory: Memory | int) -> BlockPlan:
    """A :class:`BlockPlan` with the paper's uniform b in every mode.

    ``plan.eq10_words(dims, rank)`` then equals
    ``core.bounds.seq_blocked_cost(dims, rank, b)`` exactly.
    """
    b = best_uniform_block(dims, memory)
    plan = BlockPlan(b, (b,) * (len(dims) - 1), rank)
    assert int(plan.eq10_words(dims, rank)) == int(
        seq_blocked_cost(dims, rank, b)
    )
    return plan
