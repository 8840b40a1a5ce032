"""Fused-sweep Pallas kernel: the Gauss-Seidel sweep's opening pair in ONE
pallas_call.

A CP-ALS sweep needs, per mode, one MTTKRP — and the per-mode chain re-reads
the tensor N times. The shared-memory MTTKRP paper (Hayashi et al.,
arXiv:1708.08976) shows the chain has inter-mode reuse: every mode's MTTKRP
except the last shares the contraction ``X x_{N-1} A^(N-1)`` with the
*pre-sweep* factor values, so one tensor pass can produce both

    B^(0)(i, r)            = sum_{c_1..c_{N-1}} X(i, c..) prod_d A_d(c_d, r)
    P(i, c_1..c_{N-2}, r)  = sum_{c_{N-1}}      X(i, c..) A_{N-1}(c_{N-1}, r)

without breaking Gauss-Seidel order (both consume only pre-sweep factors;
modes 1..N-2 then contract P against already-updated factors, and mode N-1
runs a fresh full MTTKRP — see :mod:`repro.engine.sweep` for the schedule).

This kernel computes the (B^(0), P) pair as a two-output ``pallas_call``
with the exact output-stationary layout of :mod:`repro.kernels.mttkrpn`:
grid ``(r, i, c_1..c_{N-1})`` with the contraction tiles innermost, the
X tile loaded ONCE per grid step and contracted ONCE on the MXU against
the last factor tile: that product is P's update, and its reduction
against the other factors' Khatri-Rao weights (VPU) is B^(0)'s. Both
outputs stay VMEM-resident across their contraction revisits (B^(0)
across all contraction steps; P across the innermost ``c_{N-1}`` sweep,
the only grid dim its index map drops).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observe.trace import annotated
from .common import compiler_params, launcher
from .mttkrpn import krp_contract


def _fused_pair_kernel(*refs, n_contract: int, acc_dtype):
    x_ref = refs[0]
    f_refs = refs[1 : 1 + n_contract]
    b0_ref = refs[1 + n_contract]
    p_ref = refs[2 + n_contract]

    first_contract_step = pl.program_id(2) == 0
    for d in range(1, n_contract):
        first_contract_step &= pl.program_id(2 + d) == 0

    @pl.when(first_contract_step)
    def _zero_b0():
        b0_ref[...] = jnp.zeros_like(b0_ref)

    # P's block map keeps (i, c_1..c_{N-2}, r): the block is revisited only
    # across the innermost c_{N-1} sweep, so it zeroes when that dim wraps
    @pl.when(pl.program_id(2 + n_contract - 1) == 0)
    def _zero_p():
        p_ref[...] = jnp.zeros_like(p_ref)

    # one MXU pass per slab feeds both outputs: P accumulates T = X x A_{N-1}
    # and B^(0) its Khatri-Rao-weighted reduction
    b0_ref[...] += krp_contract(x_ref, f_refs, acc_dtype, p_ref=p_ref)


def mttkrp_fused_pair_pallas(
    x: jax.Array,
    factors: Sequence[jax.Array],
    *,
    block_i: int,
    block_contract: Sequence[int],
    block_r: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Canonical fused pair: ``(B^(0), P = X x_{N-1} A_{N-1})`` from one
    tensor pass. ``factors`` are the N-1 non-output factors in tensor-axis
    order (axes 1..N-1). Pre-padded inputs required; both outputs are in
    ``acc_dtype``."""
    nc = x.ndim - 1
    assert nc >= 2, "fused pair needs >= 2 contraction dims"
    assert len(factors) == nc and len(block_contract) == nc
    r_sz = factors[0].shape[1]
    for d, f in enumerate(factors):
        assert f.shape == (x.shape[1 + d], r_sz)
        assert x.shape[1 + d] % block_contract[d] == 0
    assert x.shape[0] % block_i == 0 and r_sz % block_r == 0
    with annotated("repro.kernel.sweep"):
        return _fused_pair(
            x, tuple(factors), block_i=block_i,
            block_contract=tuple(block_contract), block_r=block_r,
            interpret=interpret, acc_dtype=acc_dtype,
        )


@launcher
def _fused_pair(
    x, factors, *, block_i, block_contract, block_r, interpret, acc_dtype
):
    nc = x.ndim - 1
    i_sz = x.shape[0]
    r_sz = factors[0].shape[1]
    grid = (
        r_sz // block_r,
        i_sz // block_i,
    ) + tuple(x.shape[1 + d] // block_contract[d] for d in range(nc))

    def x_map(r, i, *cs):
        return (i,) + cs

    def f_map_for(d):
        def f_map(r, i, *cs):
            return (cs[d], r)
        return f_map

    def b0_map(r, i, *cs):
        return (i, r)

    def p_map(r, i, *cs):
        return (i,) + cs[:-1] + (r,)

    in_specs = [
        pl.BlockSpec((block_i,) + block_contract, x_map)
    ] + [
        pl.BlockSpec((block_contract[d], block_r), f_map_for(d))
        for d in range(nc)
    ]
    p_shape = (i_sz,) + tuple(x.shape[1 + d] for d in range(nc - 1)) + (r_sz,)
    p_block = (block_i,) + block_contract[:-1] + (block_r,)
    kernel = functools.partial(
        _fused_pair_kernel, n_contract=nc, acc_dtype=acc_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((block_i, block_r), b0_map),
            pl.BlockSpec(p_block, p_map),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((i_sz, r_sz), acc_dtype),
            jax.ShapeDtypeStruct(p_shape, acc_dtype),
        ),
        interpret=interpret,
        compiler_params=compiler_params(2, nc),
        name="sweep",
    )(x, *factors)


def fused_pair_canonical_pallas(
    x: jax.Array,
    fs: Sequence[jax.Array],
    *,
    plan=None,
    interpret: bool | None = None,
    out_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Padding/un-padding wrapper around :func:`mttkrp_fused_pair_pallas`
    (mirrors :func:`repro.kernels.ops.mttkrp_canonical_pallas`).

    ``x`` already has the output mode at axis 0; ``fs`` are the N-1
    factors for axes 1..N-1 in order. Returns ``(b0, p)`` un-padded, with
    ``p`` of shape ``(I_0, I_1..I_{N-2}, R)``.
    """
    from .ops import (  # local: shared idiom
        _auto_interpret,
        _crop,
        _pad_operands,
        _round_up,
    )

    interpret = _auto_interpret() if interpret is None else interpret
    rank = fs[0].shape[1]
    orig_shape = x.shape
    if plan is None:
        from ..engine.plan import choose_sweep_blocks

        plan = choose_sweep_blocks(x.shape, rank, x.dtype.itemsize)
    tgt = plan.padded_shape(x.shape)
    r_pad = _round_up(rank, plan.block_r)
    x, fs = _pad_operands(x, tgt, fs, [(t, r_pad) for t in tgt[1:]])
    b0, p = mttkrp_fused_pair_pallas(
        x, fs,
        block_i=plan.block_i,
        block_contract=plan.block_contract,
        block_r=plan.block_r,
        interpret=interpret,
    )
    return (
        _crop(b0, (orig_shape[0], rank), out_dtype),
        _crop(p, tuple(orig_shape[:-1]) + (rank,), out_dtype),
    )
