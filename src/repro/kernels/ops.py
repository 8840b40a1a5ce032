"""jit'd public wrappers for the Pallas MTTKRP kernels.

Handles: mode canonicalization (transpose output mode to axis 0), TPU-
alignment padding, kernel dispatch (3-way specialized / N-way generic /
rank-augmented partial), un-padding, and dtype policy (f32 accumulation).

Block planning and the traffic models live in :mod:`repro.engine.plan` —
the single source of truth — and are re-exported here for back-compat
(``from repro.kernels.ops import choose_blocks`` keeps working).

``interpret=None`` auto-selects: real Mosaic lowering on TPU backends,
interpret mode elsewhere (this container validates on CPU).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from ..engine.plan import (  # noqa: F401  (re-exported planner API)
    LANE,
    SUBLANE,
    VMEM_BUDGET,
    VMEM_BYTES,
    BlockPlan,
    MultiTTMPlan,
    choose_blocks,
    choose_multi_ttm_blocks,
    mttkrp_traffic_model,
)
from ..observe.trace import annotated
from .mttkrp3 import mttkrp3_pallas
from .mttkrpn import mttkrp_partial_pallas, mttkrpn_pallas
from .multi_ttm import multi_ttm_keep_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def mttkrp_canonical_pallas(
    xp: jax.Array,
    fs: Sequence[jax.Array],
    *,
    plan: BlockPlan | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    variant: str | None = None,
) -> jax.Array:
    """Mode-0-canonical MTTKRP through the blocked kernels.

    ``xp`` is the (already transposed) tensor with the output mode at axis
    0; ``fs`` are the N-1 factors for axes 1..N-1 in order. Pads to the
    plan's block multiples (zero tensor padding contributes nothing; padded
    output rows/columns are sliced away), dispatches the 3-way specialized
    or N-way generic kernel, and un-pads.

    ``variant`` pins the kernel for 3-way tensors: ``"specialized"`` (the
    default, :func:`mttkrp3_pallas`) or ``"generic"`` (the N-way kernel) —
    the autotuner measures both. N > 3 always uses the generic kernel.
    """
    if variant not in (None, "specialized", "generic"):
        raise ValueError(f"unknown kernel variant {variant!r}")
    interpret = _auto_interpret() if interpret is None else interpret
    n = xp.ndim
    rank = fs[0].shape[1]
    out_rows = xp.shape[0]
    if plan is None:
        plan = choose_blocks(xp.shape, rank, xp.dtype.itemsize)
    tgt = plan.padded_shape(xp.shape)
    r_pad = _round_up(rank, plan.block_r)
    with annotated("repro.engine.relayout"):
        xp = jnp.pad(xp, [(0, t - s) for t, s in zip(tgt, xp.shape)])
        fs = [
            jnp.pad(f, ((0, tgt[1 + d] - f.shape[0]), (0, r_pad - rank)))
            for d, f in enumerate(fs)
        ]
    if n == 3 and variant != "generic":
        out = mttkrp3_pallas(
            xp, fs[0], fs[1],
            block_i=plan.block_i,
            block_j=plan.block_contract[0],
            block_k=plan.block_contract[1],
            block_r=plan.block_r,
            interpret=interpret,
        )
    else:
        out = mttkrpn_pallas(
            xp, fs,
            block_i=plan.block_i,
            block_contract=plan.block_contract,
            block_r=plan.block_r,
            interpret=interpret,
        )
    with annotated("repro.engine.relayout"):
        out = out[:out_rows, :rank]
        return out.astype(out_dtype) if out_dtype is not None else out


def mttkrp_pallas(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    interpret: bool | None = None,
    plan: BlockPlan | None = None,
    out_dtype=None,
    variant: str | None = None,
) -> jax.Array:
    """MTTKRP for any mode via the Pallas blocked kernel.

    Drop-in for :func:`repro.core.mttkrp.mttkrp` (f32 accumulation). The
    tensor is transposed so ``mode`` is axis 0, then dispatched through
    :func:`mttkrp_canonical_pallas`.
    """
    n = x.ndim
    if n < 3:
        raise ValueError("pallas kernel supports N >= 3 (use core.mttkrp)")
    perm = (mode,) + tuple(k for k in range(n) if k != mode)
    with annotated("repro.engine.relayout"):
        xp = jnp.transpose(x, perm)
    fs = [factors[k] for k in perm[1:]]
    return mttkrp_canonical_pallas(
        xp, fs, plan=plan, interpret=interpret,
        out_dtype=out_dtype or x.dtype, variant=variant,
    )


def mttkrp_partial_canonical_pallas(
    node: jax.Array,
    fs: Sequence[jax.Array],
    *,
    plan: BlockPlan | None = None,
    interpret: bool | None = None,
    out_dtype=None,
) -> jax.Array:
    """Rank-augmented partial contraction (dimension-tree internal node).

    ``node`` is ``(I, C_1..C_k, R)`` — kept modes flattened into axis 0,
    dropped modes next, rank last; ``fs`` are the k dropped factors
    ``(C_d, R)``. Pads, runs :func:`mttkrp_partial_pallas`, un-pads.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    rank = node.shape[-1]
    out_rows = node.shape[0]
    if plan is None:
        plan = choose_blocks(
            node.shape[:-1], rank, node.dtype.itemsize, x_has_rank=True
        )
    tgt = plan.padded_shape(node.shape[:-1])
    r_pad = _round_up(rank, plan.block_r)
    with annotated("repro.engine.relayout"):
        node = jnp.pad(
            node,
            [(0, t - s) for t, s in zip(tgt, node.shape[:-1])]
            + [(0, r_pad - rank)],
        )
        fs = [
            jnp.pad(f, ((0, tgt[1 + d] - f.shape[0]), (0, r_pad - rank)))
            for d, f in enumerate(fs)
        ]
    out = mttkrp_partial_pallas(
        node, fs,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        block_r=plan.block_r,
        interpret=interpret,
    )
    with annotated("repro.engine.relayout"):
        out = out[:out_rows, :rank]
        return out.astype(out_dtype) if out_dtype is not None else out


def multi_ttm_canonical_pallas(
    xp: jax.Array,
    mats: Sequence[jax.Array],
    *,
    plan: MultiTTMPlan | None = None,
    interpret: bool | None = None,
    out_dtype=None,
) -> jax.Array:
    """Kept-mode-first Multi-TTM through the blocked Kronecker kernel.

    ``xp`` is the (already transposed) tensor with the kept mode at axis
    0; ``mats`` are the k contracted-mode matrices ``(C_d, R_d)`` for
    axes 1..k in order. Pads the tensor modes to the plan's block
    multiples (zero padding contributes nothing; padded output rows are
    sliced away — the R_d are never padded), runs
    :func:`repro.kernels.multi_ttm.multi_ttm_keep_pallas`, and un-pads.
    Returns the flattened ``(I, prod R_d)`` result.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    ranks = tuple(m.shape[1] for m in mats)
    out_rows = xp.shape[0]
    if plan is None:
        plan = choose_multi_ttm_blocks(xp.shape, ranks, xp.dtype.itemsize)
    tgt = plan.padded_shape(xp.shape)
    with annotated("repro.engine.relayout"):
        xp = jnp.pad(xp, [(0, t - s) for t, s in zip(tgt, xp.shape)])
        mats = [
            jnp.pad(m, ((0, tgt[1 + d] - m.shape[0]), (0, 0)))
            for d, m in enumerate(mats)
        ]
    out = multi_ttm_keep_pallas(
        xp, mats,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        interpret=interpret,
    )
    with annotated("repro.engine.relayout"):
        out = out[:out_rows]
        return out.astype(out_dtype) if out_dtype is not None else out


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def mttkrp_pallas_jit(x, factors, mode: int, interpret: bool | None = None):
    return mttkrp_pallas(x, tuple(factors), mode, interpret=interpret)
