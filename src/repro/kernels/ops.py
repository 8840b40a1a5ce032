"""jit'd public wrappers for the Pallas MTTKRP kernels.

Handles: layout (a 3-way tensor is read in place by the specialized
kernel for every mode; N > 3 tensors, the generic variant and the partial
kernel read a copy with the output mode at axis 0), TPU-alignment padding
(only where a shape is off the plan's blocks), kernel dispatch (3-way
specialized / N-way generic / rank-augmented partial), un-padding, and
dtype policy (f32 accumulation).  Each tensor-sized transpose or pad is
counted as ``engine.tensor_relayouts`` in :mod:`repro.observe.metrics`.

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
    Memory,
    MultiTTMPlan,
    choose_blocks,
    choose_multi_ttm_blocks,
    mode_first,
    mttkrp_lane_pos,
    mttkrp_traffic_model,
)
from ..observe.metrics import TENSOR_RELAYOUTS, registry
from ..observe.trace import annotated
from .mttkrp3 import mttkrp3_pallas
from .mttkrpn import mttkrp_partial_pallas, mttkrpn_pallas
from .multi_ttm import multi_ttm_keep_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _reads_in_place(ndim: int, variant: str | None) -> bool:
    """Whether :func:`mttkrp_pallas` hands X to its kernel untransposed."""
    return ndim == 3 and variant != "generic"


def transpose_tensor(x: jax.Array, perm: Sequence[int]) -> jax.Array:
    """``x`` transposed to ``perm``: one counted tensor relayout, or ``x``
    itself for the identity permutation."""
    perm = tuple(perm)
    if perm == tuple(range(x.ndim)):
        return x
    with annotated("repro.engine.relayout"):
        registry().inc(TENSOR_RELAYOUTS)
        return jnp.transpose(x, perm)


def _pad_operands(
    x: jax.Array,
    x_shape: Sequence[int],
    mats: Sequence[jax.Array],
    mat_shapes: Sequence[tuple[int, int]],
) -> tuple[jax.Array, list[jax.Array]]:
    """Zero-pad the tensor up to ``x_shape`` and each matrix up to its
    ``mat_shapes`` entry, each only where its shape differs: ``jnp.pad``
    copies even at zero widths.  A tensor pad counts one
    ``engine.tensor_relayouts``."""
    pad_x = tuple(x.shape) != tuple(x_shape)
    pad_m = [tuple(m.shape) != tuple(t) for m, t in zip(mats, mat_shapes)]
    if not (pad_x or any(pad_m)):
        return x, list(mats)
    with annotated("repro.engine.relayout"):
        if pad_x:
            registry().inc(TENSOR_RELAYOUTS)
            x = jnp.pad(x, [(0, t - s) for t, s in zip(x_shape, x.shape)])
        mats = [
            jnp.pad(m, [(0, t - s) for t, s in zip(shape, m.shape)])
            if p else m
            for m, shape, p in zip(mats, mat_shapes, pad_m)
        ]
    return x, mats


def _crop(out: jax.Array, shape: Sequence[int], out_dtype) -> jax.Array:
    """The leading ``shape`` corner of a padded kernel output, cast to
    ``out_dtype`` when given."""
    if tuple(out.shape) != tuple(shape):
        with annotated("repro.engine.relayout"):
            out = out[tuple(slice(0, s) for s in shape)]
    return out.astype(out_dtype) if out_dtype is not None else out


def mttkrp_plan(
    shape: Sequence[int],
    rank: int,
    mode: int,
    itemsize: int = 4,
    *,
    memory: Memory | None = None,
    variant: str | None = None,
) -> BlockPlan:
    """The model-best plan for the MTTKRP of ``mode`` of a tensor stored
    as ``shape``, aligned to the layout its kernel reads."""
    return choose_blocks(
        mode_first(shape, mode), rank, itemsize, memory=memory,
        lane_pos=mttkrp_lane_pos(len(shape), mode, variant),
    )


def tensor_relayout(
    shape: Sequence[int], mode: int, plan: BlockPlan,
    variant: str | None = None,
) -> str:
    """What :func:`mttkrp_pallas` does to X before its kernel under
    ``plan``: ``"none"``, ``"pad"``, ``"transpose"`` or
    ``"transpose+pad"``."""
    canon = mode_first(shape, mode)
    steps = []
    if mode != 0 and not _reads_in_place(len(shape), variant):
        steps.append("transpose")
    if plan.padded_shape(canon) != canon:
        steps.append("pad")
    return "+".join(steps) or "none"


def _check_variant(variant: str | None) -> None:
    if variant not in (None, "specialized", "generic"):
        raise ValueError(f"unknown kernel variant {variant!r}")


def _mttkrp3_in_place(x, fs, mode, plan, interpret, out_dtype):
    """3-way MTTKRP of ``mode`` with X in its stored layout; ``fs`` are
    the two contracted factors in axis order.  Pads X, the factors and
    the rank only where the plan's blocks need it."""
    rank = fs[0].shape[1]
    if plan is None:
        plan = mttkrp_plan(x.shape, rank, mode, x.dtype.itemsize)
    axes = [k for k in range(3) if k != mode]
    blocks = [0, 0, 0]
    blocks[mode] = plan.block_i
    for k, b in zip(axes, plan.block_contract):
        blocks[k] = b
    tgt = tuple(_round_up(s, b) for s, b in zip(x.shape, blocks))
    r_pad = _round_up(rank, plan.block_r)
    out_rows = x.shape[mode]
    x, fs = _pad_operands(x, tgt, fs, [(tgt[k], r_pad) for k in axes])
    out = mttkrp3_pallas(
        x, fs, mode, blocks=blocks, block_r=plan.block_r,
        interpret=_auto_interpret() if interpret is None else interpret,
    )
    return _crop(out, (out_rows, rank), out_dtype)


def mttkrp_canonical_pallas(
    xp: jax.Array,
    fs: Sequence[jax.Array],
    *,
    plan: BlockPlan | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    variant: str | None = None,
) -> jax.Array:
    """Mode-0 MTTKRP through the blocked kernels.

    ``xp`` has the output mode at axis 0; ``fs`` are the N-1 factors for
    axes 1..N-1 in order. Pads to the plan's block multiples where a
    shape needs it (zero tensor padding contributes nothing; padded
    output rows/columns are sliced away), dispatches the 3-way
    specialized or N-way generic kernel, and un-pads.

    ``variant`` pins the kernel for 3-way tensors: ``"specialized"`` (the
    default, :func:`mttkrp3_pallas`) or ``"generic"`` (the N-way kernel) —
    the autotuner measures both. N > 3 always uses the generic kernel.
    """
    _check_variant(variant)
    if _reads_in_place(xp.ndim, variant):
        return _mttkrp3_in_place(xp, fs, 0, plan, interpret, out_dtype)
    interpret = _auto_interpret() if interpret is None else interpret
    rank = fs[0].shape[1]
    out_rows = xp.shape[0]
    if plan is None:
        plan = choose_blocks(xp.shape, rank, xp.dtype.itemsize)
    tgt = plan.padded_shape(xp.shape)
    r_pad = _round_up(rank, plan.block_r)
    xp, fs = _pad_operands(
        xp, tgt, fs, [(t, r_pad) for t in tgt[1:]]
    )
    out = mttkrpn_pallas(
        xp, fs,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        block_r=plan.block_r,
        interpret=interpret,
    )
    return _crop(out, (out_rows, rank), out_dtype)


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
    """MTTKRP for any mode via the Pallas blocked kernels.

    Drop-in for :func:`repro.core.mttkrp.mttkrp` (f32 accumulation).
    ``plan`` is in output-mode-first order (:class:`BlockPlan`). A 3-way
    tensor is read in place by :func:`mttkrp3_pallas` for every mode;
    otherwise (N > 3, or ``variant="generic"``) the tensor is transposed
    so ``mode`` is axis 0 and dispatched through
    :func:`mttkrp_canonical_pallas`.
    """
    n = x.ndim
    if n < 3:
        raise ValueError("pallas kernel supports N >= 3 (use core.mttkrp)")
    _check_variant(variant)
    out_dtype = out_dtype or x.dtype
    if _reads_in_place(n, variant):
        fs = [factors[k] for k in range(3) if k != mode]
        return _mttkrp3_in_place(x, fs, mode, plan, interpret, out_dtype)
    perm = (mode,) + tuple(k for k in range(n) if k != mode)
    return mttkrp_canonical_pallas(
        transpose_tensor(x, perm), [factors[k] for k in perm[1:]],
        plan=plan, interpret=interpret, out_dtype=out_dtype,
        variant=variant,
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
    node, fs = _pad_operands(
        node, tgt + (r_pad,), fs, [(t, r_pad) for t in tgt[1:]]
    )
    out = mttkrp_partial_pallas(
        node, fs,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        block_r=plan.block_r,
        interpret=interpret,
    )
    return _crop(out, (out_rows, rank), out_dtype)


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
    xp, mats = _pad_operands(
        xp, tgt, mats, [(t, r) for t, r in zip(tgt[1:], ranks)]
    )
    out = multi_ttm_keep_pallas(
        xp, mats,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        interpret=interpret,
    )
    return _crop(out, (out_rows,) + out.shape[1:], out_dtype)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def mttkrp_pallas_jit(x, factors, mode: int, interpret: bool | None = None):
    return mttkrp_pallas(x, tuple(factors), mode, interpret=interpret)
