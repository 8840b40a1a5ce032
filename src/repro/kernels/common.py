"""Pieces every Pallas kernel in this package shares.

Mosaic (the TPU kernel compiler) refuses reshapes that merge the
(sublane, lane) tile of a block into one axis, e.g. ``(bi, 8, 32) ->
(bi, 256)``.  So no kernel flattens its tensor tile into a matrix.  Each
one contracts the tile's minor (lane) axis on the MXU, which needs only
a collapse of the leading axes into the sublane axis, and combines the
other contraction axes afterwards: the sublane axis in the same step,
the leading axes one slab at a time in :func:`fori_leading`.  That also
bounds the in-kernel temporaries by one slab instead of one tile.

Each kernel's launcher is a :func:`launcher`: its ``pallas_call`` is
built once per static signature, not on every eager call.
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observe.metrics import LAUNCHER_TRACES, registry

#: Scoped VMEM each kernel may use.  Mosaic's default (16 MiB) must hold
#: the double-buffered BlockSpec tiles at their (8, 128)-padded size plus
#: the in-kernel temporaries; the planner's Eq-9 budget
#: (``engine.plan.VMEM_BUDGET``) counts unpadded words of one buffer.  A
#: v5e TensorCore has 128 MiB of VMEM, so half of it leaves the planner's
#: blocks room for both.
VMEM_LIMIT_BYTES = 64 * 2**20


def compiler_params(n_parallel: int, n_arbitrary: int) -> pltpu.CompilerParams:
    """Mosaic parameters for a grid of ``n_parallel`` independent axes
    followed by ``n_arbitrary`` accumulation (contraction) axes."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary,
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


#: Every :func:`launcher`, for :func:`clear_launcher_caches`.
_LAUNCHERS: list = []


def launcher(body: Callable) -> Callable:
    """``body``, which builds one ``pallas_call`` and runs it, as a
    ``jax.jit`` whose keyword-only arguments are static.  The kernel is
    then traced, lowered and compiled once per static signature (shapes
    and dtypes come in through the avals); a later eager call at that
    signature takes JAX's compiled-dispatch fast path, and an eager
    ``vmap`` reuses the batched program.  Each trace counts one
    ``kernels.launcher_traces``."""
    static = tuple(
        name for name, p in inspect.signature(body).parameters.items()
        if p.kind is p.KEYWORD_ONLY
    )

    @functools.wraps(body)
    def traced(*args, **kwargs):
        registry().inc(LAUNCHER_TRACES)
        return body(*args, **kwargs)

    jitted = jax.jit(traced, static_argnames=static)
    _LAUNCHERS.append(jitted)
    return jitted


def clear_launcher_caches() -> None:
    """Drop every launcher's traced and compiled programs, so that the
    next call at any signature builds its ``pallas_call`` again."""
    for f in _LAUNCHERS:
        f.clear_cache()


def mxu_dot(
    a: jax.Array,
    b: jax.Array,
    contract: tuple[Sequence[int], Sequence[int]] = ((1,), (0,)),
    batch: tuple[Sequence[int], Sequence[int]] = ((), ()),
) -> jax.Array:
    """``dot_general`` with fp32 accumulation.  fp32 operands run at
    ``Precision.HIGHEST``: the MXU's default precision for fp32 is one
    bf16 pass, which would set the kernels' error (~1e-3) instead of the
    fp32 accumulation.  Narrower operands run at their native precision."""
    wide = jnp.float32 in (a.dtype, b.dtype)
    precision = jax.lax.Precision.HIGHEST if wide else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, batch), precision=precision,
        preferred_element_type=jnp.float32,
    )


def fori_leading(
    extents: Sequence[int],
    body: Callable[[tuple, jax.Array], jax.Array],
    init: jax.Array,
) -> jax.Array:
    """Fold ``body(idx, carry)`` over every index of the leading
    contraction axes ``extents`` (C order); ``idx`` holds one scalar per
    axis.  With no leading axes ``body`` runs once with ``idx == ()``."""
    extents = tuple(extents)
    count = math.prod(extents)
    if count == 1:
        return body((0,) * len(extents), init)

    def step(flat, carry):
        idx = []
        for e in reversed(extents):
            idx.append(flat % e)
            flat = flat // e
        return body(tuple(reversed(idx)), carry)

    return jax.lax.fori_loop(0, count, step, init)


def row(ref, i, dtype=jnp.float32) -> jax.Array:
    """Row ``i`` (possibly traced) of a 2-D ref as a ``(1, cols)``
    array of ``dtype``."""
    return ref[pl.ds(i, 1), :].astype(dtype)
