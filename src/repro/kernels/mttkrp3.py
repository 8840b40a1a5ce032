"""Pallas TPU kernel: blocked 3-way MTTKRP — the TPU-native Algorithm 2.

Paper mapping (§V-B → TPU)
--------------------------
Algorithm 2 streams b×b×b tensor blocks through fast memory while holding
the corresponding factor subvectors, giving traffic I + Π⌈I_k/b⌉·R(N+1)b.
On TPU, fast memory is VMEM and the compute unit is the 128×128 MXU, so we
adapt (DESIGN.md §3):

* the tensor block is a (bi, bj, bk) VMEM tile (HBM→VMEM via BlockSpec);
* the N-ary multiplies are *restructured* (atomicity broken, as §V-C3
  licenses) into an MXU contraction against the Khatri-Rao block
  W[(j,k), r] = A(j,r)·B(k,r), applied **in VMEM** from its bj·br + bk·br
  words — never materialized in HBM (this is precisely the paper's "the KRP
  has few parameters" insight). The tile update
      O(bi×br) += Σ_j A(j,r) · (X(bi·bj × bk) @ B(bk × br))
  does the MXU work of one X(bi × bj·bk) @ W matmul without flattening
  the tile across its (sublane, lane) axes, which Mosaic refuses;
* the output tile O(bi, br) is *output-stationary*: the grid iterates the
  contraction dims (j, k) innermost so O accumulates in VMEM across the
  whole (j, k) sweep and is written back once per (i, r) tile — Algorithm
  2's reuse of the B^{(n)} subvector.

Traffic per (i,r,j,k) grid step: X tile (once per (j,k) per (i,r)... the
i-grid re-reads X for every r-tile, matching the R-loop of Algorithm 2) +
factor tiles; totals match seq_blocked_cost with b_n=bi, R-tiling, i.e.
   bytes ≈ I·(R/br) + Π(I_k/b_k)·(bj·br + bk·br + bi·br)
— the kernel's analytic model in ops.mttkrp3_traffic_model.

Mode handling: the wrapper canonicalizes to mode 0 by transposing the
tensor (one HBM pass, fused by XLA where possible).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observe.trace import annotated
from .common import compiler_params
from .mttkrpn import krp_contract


def _mttkrp3_kernel(x_ref, a_ref, b_ref, o_ref, *, acc_dtype):
    """One grid step: O[i-tile, r-tile] += X[i,j,k] @ KRP(A[j], B[k]).

    Refs (all VMEM tiles):
      x_ref: (bi, bj, bk)   tensor block
      a_ref: (bj, br)       mode-1 factor tile
      b_ref: (bk, br)       mode-2 factor tile
      o_ref: (bi, br)       output tile, accumulated across the (j,k) grid
    """
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((j == 0) & (k == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # (bi*bj, bk) @ B on the MXU, then the A(j, r) weights on the VPU
    o_ref[...] += krp_contract(x_ref, (a_ref, b_ref), acc_dtype)


def mttkrp3_pallas(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    block_i: int = 128,
    block_j: int = 8,
    block_k: int = 128,
    block_r: int = 128,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Canonical mode-0 3-way MTTKRP: O(i,r) = Σ_jk X(i,j,k)A(j,r)B(k,r).

    Inputs must be pre-padded to multiples of the block sizes (the ops.py
    wrapper does this). Output is ``acc_dtype`` of shape (I, R).
    """
    i_sz, j_sz, k_sz = x.shape
    r_sz = a.shape[1]
    assert a.shape == (j_sz, r_sz) and b.shape == (k_sz, r_sz)
    assert i_sz % block_i == 0 and j_sz % block_j == 0
    assert k_sz % block_k == 0 and r_sz % block_r == 0

    grid = (
        i_sz // block_i,
        r_sz // block_r,
        j_sz // block_j,
        k_sz // block_k,
    )
    kernel = functools.partial(_mttkrp3_kernel, acc_dtype=acc_dtype)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_i, block_j, block_k), lambda i, r, j, k: (i, j, k)
            ),
            pl.BlockSpec((block_j, block_r), lambda i, r, j, k: (j, r)),
            pl.BlockSpec((block_k, block_r), lambda i, r, j, k: (k, r)),
        ],
        out_specs=pl.BlockSpec((block_i, block_r), lambda i, r, j, k: (i, r)),
        out_shape=jax.ShapeDtypeStruct((i_sz, r_sz), acc_dtype),
        interpret=interpret,
        compiler_params=compiler_params(2, 2),
        name="mttkrp3",
    )
    with annotated("repro.kernel.mttkrp3"):
        return call(x, a, b)
