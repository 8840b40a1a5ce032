"""Generic N-way Pallas MTTKRP kernel (N >= 3) — same schedule as mttkrp3.

The grid is (r, i, c_1, ..., c_{N-1}) with the contraction tiles innermost:
the output tile O(bi, br) stays VMEM-resident across the whole contraction
sweep (output-stationary, Algorithm 2's reuse), the tensor is streamed once
per r-tile, and the rank-structured weight block

    W[(c_1..c_{N-1}), r] = Π_k A_k(c_k, r)

is applied factor by factor in VMEM (:func:`krp_contract`: the minor
factor on the MXU, the others as Khatri-Rao row weights on the VPU), so
it is never materialized in HBM. See mttkrp3.py for the full
TPU-adaptation rationale; this module generalizes it to arbitrary order
for 4-/5-way tensors.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observe.trace import annotated
from .common import compiler_params, fori_leading, launcher, mxu_dot, row


def krp_contract(x_ref, f_refs, acc_dtype, p_ref=None) -> jax.Array:
    """One tile's MTTKRP contribution ``sum_c X(i, c..) prod_d A_d(c_d, r)``.

    ``x_ref`` is the ``(bi, c_1..c_k)`` tensor tile, ``f_refs`` the k
    ``(c_d, br)`` factor tiles.  For each index of the leading contraction
    axes ``c_1..c_{k-2}`` the ``(bi, c_{k-1}, c_k)`` slab meets the MXU
    once, ``T = slab x_k A_k`` of shape ``(bi, c_{k-1}, br)``, and the
    Khatri-Rao weights of the other axes reduce ``T`` on the VPU.  The MXU
    does the same multiply-adds as one matmul against the full Khatri-Rao
    block would, and the tile is never flattened across its (sublane,
    lane) axes.  ``p_ref``, when given, accumulates ``T`` itself (the
    fused pair kernel's second output).  Returns the ``(bi, br)`` sum."""
    if len(f_refs) == 1:  # one contraction axis: a plain matmul
        return mxu_dot(x_ref[...], f_refs[0][...]).astype(acc_dtype)
    bi, c_sub, c_min = x_ref.shape[0], x_ref.shape[-2], x_ref.shape[-1]
    br = f_refs[0].shape[1]
    lead_refs, f_sub, f_min = f_refs[:-2], f_refs[-2], f_refs[-1]

    def body(idx, acc):
        at = (slice(None),) + tuple(idx)
        slab = x_ref[at]
        t = mxu_dot(slab.reshape(bi * c_sub, c_min), f_min[...])
        t = t.reshape(bi, c_sub, br)
        if p_ref is not None:
            p_ref[at] += t.astype(p_ref.dtype)
        w = f_sub[...].astype(acc_dtype)
        for f, a in zip(lead_refs, idx):
            w = w * row(f, a, acc_dtype)
        return acc + jnp.sum(t.astype(acc_dtype) * w[None], axis=1)

    return fori_leading(
        x_ref.shape[1:-2], body, jnp.zeros((bi, br), acc_dtype)
    )


def _kernel(*refs, n_contract: int, acc_dtype):
    x_ref = refs[0]
    f_refs = refs[1 : 1 + n_contract]
    o_ref = refs[1 + n_contract]

    first_contract_step = pl.program_id(2) == 0
    for d in range(1, n_contract):
        first_contract_step &= pl.program_id(2 + d) == 0

    @pl.when(first_contract_step)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += krp_contract(x_ref, f_refs, acc_dtype)


def _partial_kernel(*refs, n_contract: int, acc_dtype):
    """Rank-augmented partial contraction (dimension-tree internal node):

        O(i, r) += sum_c X(i, c_1..c_k, r) * prod_d A_d(c_d, r)

    Same output-stationary schedule as :func:`_kernel`, but the tensor tile
    carries the rank axis, so the weights combine elementwise along r (a
    VPU reduce, not an MXU matmul).  The tile is walked eight sublane rows
    of ``c_k`` at a time (all of ``c_k`` when it is not a multiple of
    eight), so the temporaries stay one ``(bi, 8, br)`` slab."""
    x_ref = refs[0]
    f_refs = refs[1 : 1 + n_contract]
    o_ref = refs[1 + n_contract]

    first_contract_step = pl.program_id(2) == 0
    for d in range(1, n_contract):
        first_contract_step &= pl.program_id(2 + d) == 0

    @pl.when(first_contract_step)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    bi, c_min, br = x_ref.shape[0], x_ref.shape[-2], x_ref.shape[-1]
    chunk = 8 if c_min % 8 == 0 else c_min

    def body(idx, acc):
        *lead, j = idx
        start = j * chunk
        if not isinstance(start, int):
            start = pl.multiple_of(start, chunk)
        rows = pl.ds(start, chunk)
        slab = x_ref[(slice(None),) + tuple(lead) + (rows, slice(None))]
        w = f_refs[-1][rows, :].astype(acc_dtype)
        for f, a in zip(f_refs[:-1], lead):
            w = w * row(f, a, acc_dtype)
        return acc + jnp.sum(slab.astype(acc_dtype) * w[None], axis=1)

    o_ref[...] += fori_leading(
        x_ref.shape[1:-2] + (c_min // chunk,), body,
        jnp.zeros((bi, br), acc_dtype),
    )


def mttkrp_partial_pallas(
    x: jax.Array,
    factors: Sequence[jax.Array],
    *,
    block_i: int,
    block_contract: Sequence[int],
    block_r: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Canonical rank-augmented partial MTTKRP: ``x`` is ``(I, C_1..C_k,
    R)`` (a dimension-tree node that already carries the rank axis),
    ``factors`` are the k dropped factors ``(C_d, R)``. Pre-padded inputs
    required; returns ``(I, R)`` in ``acc_dtype``."""
    nc = x.ndim - 2
    assert len(factors) == nc and len(block_contract) == nc
    r_sz = x.shape[-1]
    for d, f in enumerate(factors):
        assert f.shape == (x.shape[1 + d], r_sz)
        assert x.shape[1 + d] % block_contract[d] == 0
    assert x.shape[0] % block_i == 0 and r_sz % block_r == 0
    with annotated("repro.kernel.mttkrp_partial"):
        return _mttkrp_partial(
            x, tuple(factors), block_i=block_i,
            block_contract=tuple(block_contract), block_r=block_r,
            interpret=interpret, acc_dtype=acc_dtype,
        )


@launcher
def _mttkrp_partial(
    x, factors, *, block_i, block_contract, block_r, interpret, acc_dtype
):
    nc = x.ndim - 2
    i_sz = x.shape[0]
    r_sz = x.shape[-1]
    grid = (
        r_sz // block_r,
        i_sz // block_i,
    ) + tuple(x.shape[1 + d] // block_contract[d] for d in range(nc))

    def x_map(r, i, *cs):
        return (i,) + cs + (r,)

    def f_map_for(d):
        def f_map(r, i, *cs):
            return (cs[d], r)
        return f_map

    def o_map(r, i, *cs):
        return (i, r)

    in_specs = [
        pl.BlockSpec((block_i,) + block_contract + (block_r,), x_map)
    ] + [
        pl.BlockSpec((block_contract[d], block_r), f_map_for(d))
        for d in range(nc)
    ]
    kernel = functools.partial(
        _partial_kernel, n_contract=nc, acc_dtype=acc_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_i, block_r), o_map),
        out_shape=jax.ShapeDtypeStruct((i_sz, r_sz), acc_dtype),
        interpret=interpret,
        compiler_params=compiler_params(2, nc),
        name="mttkrp_partial",
    )(x, *factors)


def mttkrpn_pallas(
    x: jax.Array,
    factors: Sequence[jax.Array],
    *,
    block_i: int,
    block_contract: Sequence[int],
    block_r: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Canonical mode-0 N-way MTTKRP. ``factors`` are the N-1 non-output
    factors in tensor-axis order (axes 1..N-1). Pre-padded inputs required."""
    nc = x.ndim - 1
    assert len(factors) == nc and len(block_contract) == nc
    r_sz = factors[0].shape[1]
    for d, f in enumerate(factors):
        assert f.shape == (x.shape[1 + d], r_sz)
        assert x.shape[1 + d] % block_contract[d] == 0
    assert x.shape[0] % block_i == 0 and r_sz % block_r == 0
    with annotated("repro.kernel.mttkrpn"):
        return _mttkrpn(
            x, tuple(factors), block_i=block_i,
            block_contract=tuple(block_contract), block_r=block_r,
            interpret=interpret, acc_dtype=acc_dtype,
        )


@launcher
def _mttkrpn(
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

    def o_map(r, i, *cs):
        return (i, r)

    in_specs = [
        pl.BlockSpec((block_i,) + block_contract, x_map)
    ] + [
        pl.BlockSpec((block_contract[d], block_r), f_map_for(d))
        for d in range(nc)
    ]
    kernel = functools.partial(_kernel, n_contract=nc, acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_i, block_r), o_map),
        out_shape=jax.ShapeDtypeStruct((i_sz, r_sz), acc_dtype),
        interpret=interpret,
        compiler_params=compiler_params(2, nc),
        name="mttkrpn",
    )(x, *factors)
