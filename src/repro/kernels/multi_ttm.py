"""Blocked Pallas Multi-TTM kernel (the Tucker/HOSVD workhorse).

Computes the canonical kept-mode-first Multi-TTM

    O(i, r_1..r_k) = sum_c X(i, c_1..c_k) * prod_d A_d(c_d, r_d)

with the same output-stationary schedule as the MTTKRP kernels
(:mod:`repro.kernels.mttkrpn`): grid (i, c_1..c_k) with the contraction
tiles innermost, the output tile O(bi, prod R_d) VMEM-resident across the
whole contraction sweep, the tensor streamed once, and the *Kronecker*
weight block

    W[(c_1..c_k), (r_1..r_k)] = prod_d A_d(c_d, r_d)

applied in VMEM factor by factor — the rank-structured analog of the
MTTKRP kernels' Khatri-Rao weight (separate small rank axes here, one
shared rank axis there), never materialized in HBM: A_k on the MXU
against each tensor slab, then the Kronecker product of the other
factors' tiles (built one ``c_{k-1}`` slab at a time) in a second,
batched MXU contraction.  The Tucker
ranks are kept whole per tile (they are the small dimensions of the
problem); only the tensor modes are blocked, planned by
:class:`repro.engine.plan.MultiTTMPlan`.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observe.trace import annotated
from .common import compiler_params, fori_leading, launcher, mxu_dot, row


def _spread(r: int, s: int, outer: bool) -> jax.Array:
    """0/1 matrix that moves column ``a`` of an ``r``-wide (``outer``) or
    ``s``-wide operand to every column ``(a, *)`` resp. ``(*, a)`` of the
    C-order ``r*s`` Kronecker column space."""
    rows = r if outer else s
    q = jax.lax.broadcasted_iota(jnp.int32, (rows, r * s), 1)
    a = jax.lax.broadcasted_iota(jnp.int32, (rows, r * s), 0)
    return ((q // s if outer else q % s) == a).astype(jnp.float32)


def _kron_cols(u: jax.Array, m: jax.Array) -> jax.Array:
    """``W[c, (a, b)] = u[0, a] * m[c, b]`` for a ``(1, r)`` row ``u`` and
    a ``(c, s)`` tile ``m``.  The column expansion is two exact 0/1
    matmuls, because Mosaic cannot merge two axes into the lane axis."""
    r, s = u.shape[1], m.shape[1]
    return mxu_dot(u, _spread(r, s, True)) * mxu_dot(m, _spread(r, s, False))


def _kernel(*refs, n_contract: int, acc_dtype):
    x_ref = refs[0]
    m_refs = refs[1 : 1 + n_contract]
    o_ref = refs[1 + n_contract]

    first_contract_step = pl.program_id(1) == 0
    for d in range(1, n_contract):
        first_contract_step &= pl.program_id(1 + d) == 0

    @pl.when(first_contract_step)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Per index of the leading contraction axes c_1..c_{k-2}, the
    # (bi, c_{k-1}, c_k) slab meets A_k on the MXU, T(i, c_{k-1}, r_k);
    # then a batched MXU contraction over c_{k-1} against the Kronecker
    # weight W[c_{k-1}, (r_1..r_{k-1})] = prod_d A_d(c_d, r_d) gives the
    # (bi, R_k, R_1..R_{k-1}) block of the output.
    bi, c_sub, c_min = x_ref.shape[0], x_ref.shape[-2], x_ref.shape[-1]
    lead_refs, m_sub, m_min = m_refs[:-2], m_refs[-2], m_refs[-1]

    def body(idx, acc):
        slab = x_ref[(slice(None),) + tuple(idx)]
        t = mxu_dot(slab.reshape(bi * c_sub, c_min), m_min[...])
        t = t.reshape(bi, c_sub, m_min.shape[1])
        u = None
        for m, a in zip(lead_refs, idx):
            r = row(m, a, acc_dtype)
            u = r if u is None else _kron_cols(u, r)
        w = m_sub[...].astype(acc_dtype)
        if u is not None:
            w = _kron_cols(u, w)
        wb = jnp.broadcast_to(w[None], (bi,) + w.shape)
        return acc + mxu_dot(t, wb, contract=((1,), (1,)), batch=((0,), (0,)))

    o_ref[...] += fori_leading(
        x_ref.shape[1:-2], body, jnp.zeros(o_ref.shape, acc_dtype)
    )


def multi_ttm_keep_pallas(
    x: jax.Array,
    matrices: Sequence[jax.Array],
    *,
    block_i: int,
    block_contract: Sequence[int],
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Canonical kept-mode-first Multi-TTM: ``x`` is ``(I, C_1..C_k)``,
    ``matrices`` are the k contracted-mode matrices ``(C_d, R_d)``.
    Pre-padded tensor-mode extents required (the R_d are never padded);
    returns the flattened ``(I, prod R_d)`` in ``acc_dtype``."""
    nc = x.ndim - 1
    assert nc >= 2, "the kernel contracts at least two modes"
    assert len(matrices) == nc and len(block_contract) == nc
    for d, m in enumerate(matrices):
        assert m.shape[0] == x.shape[1 + d]
        assert x.shape[1 + d] % block_contract[d] == 0
    assert x.shape[0] % block_i == 0
    with annotated("repro.kernel.multi_ttm"):
        return _multi_ttm(
            x, tuple(matrices), block_i=block_i,
            block_contract=tuple(block_contract), interpret=interpret,
            acc_dtype=acc_dtype,
        )


@launcher
def _multi_ttm(x, matrices, *, block_i, block_contract, interpret, acc_dtype):
    nc = x.ndim - 1
    i_sz = x.shape[0]
    ranks = tuple(m.shape[1] for m in matrices)
    r_lead = math.prod(ranks[:-1])

    grid = (i_sz // block_i,) + tuple(
        x.shape[1 + d] // block_contract[d] for d in range(nc)
    )

    def x_map(i, *cs):
        return (i,) + cs

    def m_map_for(d):
        def m_map(i, *cs):
            return (cs[d], 0)
        return m_map

    def o_map(i, *cs):
        return (i, 0, 0)

    in_specs = [
        pl.BlockSpec((block_i,) + block_contract, x_map)
    ] + [
        pl.BlockSpec((block_contract[d], ranks[d]), m_map_for(d))
        for d in range(nc)
    ]
    kernel = functools.partial(_kernel, n_contract=nc, acc_dtype=acc_dtype)
    # the kernel's block is (i, r_k, r_1..r_{k-1}); restoring C order
    # moves only the small (I, prod R_d) output
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_i, ranks[-1], r_lead), o_map),
        out_shape=jax.ShapeDtypeStruct(
            (i_sz, ranks[-1], r_lead), acc_dtype
        ),
        interpret=interpret,
        compiler_params=compiler_params(1, nc),
        name="multi_ttm",
    )(x, *matrices)
    return jnp.swapaxes(out, 1, 2).reshape(i_sz, r_lead * ranks[-1])
