"""Pallas TPU kernel: blocked 3-way MTTKRP — the TPU-native Algorithm 2.

Paper mapping (§V-B → TPU)
--------------------------
Algorithm 2 streams b×b×b tensor blocks through fast memory while holding
the corresponding factor subvectors, giving traffic I + Π⌈I_k/b⌉·R(N+1)b.
On TPU, fast memory is VMEM and the compute unit is the 128×128 MXU, so we
adapt (DESIGN.md §3):

* the tensor block is a (b0, b1, b2) VMEM tile (HBM→VMEM via BlockSpec);
* the N-ary multiplies are *restructured* (atomicity broken, as §V-C3
  licenses) into an MXU contraction against the Khatri-Rao block of the
  two contracted factors, applied **in VMEM** from their two factor tiles
  — never materialized in HBM (this is precisely the paper's "the KRP has
  few parameters" insight);
* the output tile is *output-stationary*: the grid iterates the two
  contraction tiles innermost so it accumulates in VMEM across the whole
  contraction sweep and is written back once per (output, r) tile —
  Algorithm 2's reuse of the B^{(n)} subvector.

Traffic per (o, r, c1, c2) grid step: one X tile + the factor tiles; X is
streamed once per r-tile (the R-loop of Algorithm 2), i.e.
   bytes ≈ I·(R/br) + Π(I_k/b_k)·(Σ_k b_k·br)
— the kernel's analytic model in ``engine.plan.BlockPlan.traffic_model``.

Mode handling: the kernel reads X in its stored ``(I0, I1, I2)`` layout for
every output mode — no transposed or padded copy of X is made per call.
The BlockSpec index maps are permuted per mode, so X's block is always
``(b0, b1, b2)`` in stored axis order and the ``(8, 128)`` tiling rule
falls on axes 1 and 2 whatever the mode. The tile bodies differ only in
where the output axis sits (the MXU always contracts X's lane axis or its
merged leading axes; Mosaic refuses to merge the (sublane, lane) tile):

* mode 0: ``T = X(b0·b1, b2) @ C(b2, r)``, then ``Σ_j B(j, r) T(i, j, r)``
  over the sublane axis (:func:`~repro.kernels.mttkrpn.krp_contract`);
* mode 1: the same matmul, reduced ``Σ_i A(i, r) T(i, j, r)`` over the
  leading axis;
* mode 2: the output is X's lane axis. The Khatri-Rao block
  ``W(b0·b1, r) = A(i, r)·B(j, r)`` is built in VMEM and contracted with
  X over the merged leading axis into the transposed tile
  ``O^T(r, b2) = W^T X(b0·b1, b2)``: X enters the MXU untransposed as its
  stationary operand, with b2 output columns. (The other orientation,
  ``X^T W``, ran 1.3x slower on a v5e at 1024^3, rank 64.) The kernel's
  ``(R, I2)`` output is transposed once, a factor-sized copy in the same
  compiled launch.

Only the N > 3 kernel, the generic variant and the partial kernel read a
mode-first copy (``kernels/ops.py``).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observe.trace import annotated
from .common import compiler_params, launcher, mxu_dot
from .mttkrpn import krp_contract


def _mttkrp3_kernel(x_ref, fa_ref, fb_ref, o_ref, *, mode, acc_dtype):
    """One grid step: O[o-tile, r-tile] += the tile's MTTKRP contribution.

    Refs (all VMEM tiles):
      x_ref:  (b0, b1, b2)  tensor block, stored axis order
      fa_ref: (b_a, br)     factor tile of the lower contracted axis a
      fb_ref: (b_b, br)     factor tile of the higher contracted axis b
      o_ref:  (b_mode, br)  output tile, accumulated across the (a, b)
              grid; (br, b2) for mode 2, whose output is stored transposed
    """
    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    if mode == 0:
        o_ref[...] += krp_contract(x_ref, (fa_ref, fb_ref), acc_dtype)
        return
    b0, b1, b2 = x_ref.shape
    x2 = x_ref[...].reshape(b0 * b1, b2)
    if mode == 1:
        br = fb_ref.shape[1]
        t = mxu_dot(x2, fb_ref[...]).reshape(b0, b1, br)
        w = fa_ref[...].astype(acc_dtype)
        o_ref[...] += jnp.sum(t.astype(acc_dtype) * w[:, None, :], axis=0)
        return
    fa = fa_ref[...].astype(acc_dtype)
    fb = fb_ref[...].astype(acc_dtype)
    w = (fa[:, None, :] * fb[None, :, :]).reshape(b0 * b1, fa.shape[1])
    o_ref[...] += mxu_dot(w.astype(x2.dtype), x2, contract=((0,), (0,)))


def mttkrp3_pallas(
    x: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    blocks: Sequence[int],
    block_r: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """3-way MTTKRP of output ``mode`` with X read in its stored layout:
    ``O(i_mode, r) = Σ X(i0, i1, i2) Π_{k != mode} A_k(i_k, r)``.

    ``factors`` are the two contracted factors in axis order, ``blocks``
    the ``(b0, b1, b2)`` tile in stored axis order. Inputs must be padded
    to multiples of the blocks (the ops.py wrapper does this only when a
    shape needs it). Output is ``acc_dtype`` of shape ``(I_mode, R)``.
    """
    assert x.ndim == 3 and mode in (0, 1, 2) and len(factors) == 2
    axes = tuple(k for k in range(3) if k != mode)
    r_sz = factors[0].shape[1]
    for k, f in zip(axes, factors):
        assert f.shape == (x.shape[k], r_sz)
    assert all(s % b == 0 for s, b in zip(x.shape, blocks))
    assert r_sz % block_r == 0
    with annotated("repro.kernel.mttkrp3"):
        return _mttkrp3(
            x, tuple(factors), mode=mode, blocks=tuple(blocks),
            block_r=block_r, interpret=interpret, acc_dtype=acc_dtype,
        )


@launcher
def _mttkrp3(x, factors, *, mode, blocks, block_r, interpret, acc_dtype):
    axes = tuple(k for k in range(3) if k != mode)
    r_sz = factors[0].shape[1]

    def x_map(o, r, ca, cb):
        idx = [o, o, o]
        idx[axes[0]], idx[axes[1]] = ca, cb
        return tuple(idx)

    grid = (
        x.shape[mode] // blocks[mode],
        r_sz // block_r,
        x.shape[axes[0]] // blocks[axes[0]],
        x.shape[axes[1]] // blocks[axes[1]],
    )
    if mode == 2:
        out_shape = (r_sz, x.shape[2])
        out_spec = pl.BlockSpec(
            (block_r, blocks[2]), lambda o, r, ca, cb: (r, o)
        )
    else:
        out_shape = (x.shape[mode], r_sz)
        out_spec = pl.BlockSpec(
            (blocks[mode], block_r), lambda o, r, ca, cb: (o, r)
        )
    kernel = functools.partial(
        _mttkrp3_kernel, mode=mode, acc_dtype=acc_dtype
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(blocks, x_map),
            pl.BlockSpec(
                (blocks[axes[0]], block_r), lambda o, r, ca, cb: (ca, r)
            ),
            pl.BlockSpec(
                (blocks[axes[1]], block_r), lambda o, r, ca, cb: (cb, r)
            ),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, acc_dtype),
        interpret=interpret,
        compiler_params=compiler_params(2, 2),
        name="mttkrp3",
    )(x, *factors)
    return out.T if mode == 2 else out
