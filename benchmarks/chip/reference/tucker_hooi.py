"""Plain Tucker/HOOI in ``jax.numpy``: the reference for the Tucker cells.

HOSVD initialization: ``A_k`` holds the leading ``R_k`` eigenvectors of
the Gram ``X_(k) X_(k)^T``.  One HOOI sweep then, for each mode ``k``,
contracts every other mode with its factor (one TTM at a time, smallest
result first) and takes the leading eigenvectors of the result's mode-k
Gram.  The core is the last mode's result contracted with its new factor,
and the fit is ``1 - sqrt(||X||^2 - ||G||^2) / ||X||``.  Subspaces are
compared through their projectors, so eigenvector signs do not matter.
It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.precision import einsum2, prepare, ttm_chain

_L = "abcdefgh"


@functools.partial(jax.jit, static_argnames=("r",))
def _leading(gram, r):
    _, v = jnp.linalg.eigh(gram)
    return v[:, ::-1][:, :r]


def _mode_gram(y, k, precision):
    n = (y[0] if isinstance(y, tuple) else y).ndim
    a = _L[:n]
    b = a[:k] + "z" + a[k + 1:]
    return einsum2(f"{a},{b}->{a[k]}z", y, y, precision)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _hosvd_gram(xp, k, precision):
    with jax.default_matmul_precision(precision):
        return _mode_gram(xp, k, precision)


@functools.partial(jax.jit, static_argnames=("keep", "precision"))
def _hooi_gram(xp, factors, keep, precision):
    """The mode-``keep`` Gram of ``X`` contracted with ``A_j^T`` on every
    other mode, the modes taken in the order that shrinks it most first."""
    with jax.default_matmul_precision(precision):
        others = [j for j in range(len(factors)) if j != keep]
        others.sort(key=lambda j: factors[j].shape[1] / factors[j].shape[0])
        y = ttm_chain(xp, {j: factors[j] for j in others}, precision)
        return _mode_gram(y, keep, precision), y


@functools.partial(jax.jit, static_argnames=("precision",))
def _fit(x, y, a_last, precision):
    with jax.default_matmul_precision(precision):
        core = ttm_chain(y, {y.ndim - 1: a_last}, precision)
        normx = jnp.sqrt(jnp.sum(jnp.square(x)))
        err = jnp.maximum(normx**2 - jnp.sum(jnp.square(core)), 0.0)
        return core, 1.0 - jnp.sqrt(err) / normx


@functools.partial(jax.jit, static_argnames=("precision",))
def _prepare(x, precision):
    return prepare(x, precision)


def solve(x, ranks, sweeps: int, precision: str = "highest"):
    """HOSVD then ``sweeps`` HOOI sweeps: (core, factors, fits).  Each
    step is its own small program, so one eigendecomposition program
    serves every mode of a cube."""
    with jax.default_matmul_precision(precision):
        xp = x if precision == "highest" else _prepare(x, precision)
        n = x.ndim
        factors = [_leading(_hosvd_gram(xp, k, precision), ranks[k])
                   for k in range(n)]
        fits = []
        for _ in range(sweeps):
            for k in range(n):
                g, y = _hooi_gram(xp, tuple(factors), k, precision)
                factors[k] = _leading(g, ranks[k])
            core, fit = _fit(x, y, factors[n - 1], precision)
            fits.append(fit)
        return core, factors, jnp.stack(fits)


def subspace_gap(a, b) -> float:
    """``||A A^T - B B^T||_F / sqrt(2 R)`` in float64: the root mean
    square sine of the principal angles between the two column spaces."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a @ a.T - b @ b.T) / np.sqrt(2 * a.shape[1]))


def gaps(answer: dict, ref: dict) -> dict:
    """The numbers compared for one decomposition: the widest subspace gap
    over the modes, and the gap in the number of sweeps (each sweep
    reports its fit)."""
    return {
        "subspace_gap": max(subspace_gap(a, b) for a, b in
                            zip(answer["factors"], ref["factors"])),
        "sweeps_gap": abs(len(answer["fits"]) - len(ref["fits"])),
    }


def reference(x, answer: dict, cfg: dict, precision: str) -> dict:
    """The reference's decomposition of the same tensor, same ranks and
    sweeps, from its own HOSVD."""
    core, f, fits = solve(x, tuple(cfg["ranks"]), int(cfg["sweeps_per_solve"]),
                          precision)
    return {"core": core, "factors": f, "fits": np.asarray(fits)}
