"""Plain CP-ALS in ``jax.numpy``: the reference for the CP cells.

One sweep updates every mode in turn: ``B = X_(n) (KRP of the others)``,
the normal equations ``A_n (Gamma_n + ridge I) = B`` with ``Gamma_n`` the
Hadamard product of the other factors' Grams and the ridge
``1e-5 tr(Gamma_n) / R + 1e-12`` the drivers document, then column
normalization into the weights.  The fit after a sweep is
``1 - ||X - [[lambda; A]]|| / ||X||`` by the inner-product identity.
It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.precision import einsum2, prepare

_L = "abcdefgh"


def mttkrp(xp, factors, mode: int, precision: str) -> jax.Array:
    """``X_(mode)`` times the Khatri-Rao product of the other factors, as
    a chain of pairwise contractions from the last mode down."""
    n = len(factors)
    cur, idx = xp, _L[:n]
    first = True
    for k in reversed(range(n)):
        if k == mode:
            continue
        out = idx.replace(_L[k], "")
        if first:
            spec = f"{idx},{_L[k]}z->{out}z"
            first = False
        else:
            spec = f"{idx}z,{_L[k]}z->{out}z"
        cur = einsum2(spec, cur, factors[k], precision)
        idx = out
    return cur


def _hadamard(grams, skip):
    out = jnp.ones_like(grams[0])
    for k, g in enumerate(grams):
        if k != skip:
            out = out * g
    return out


def _ridge(gamma, rank):
    return 1e-5 * jnp.trace(gamma) / rank + 1e-12


@functools.partial(jax.jit, static_argnames=("precision",))
def _prepare(x, precision):
    return prepare(x, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _sweep(xp, factors, grams, normx, precision):
    """One ALS sweep: the new factors, Grams, weights and fit."""
    with jax.default_matmul_precision(precision):
        factors, grams = list(factors), list(grams)
        n, rank = len(factors), factors[0].shape[1]
        for mode in range(n):
            b = mttkrp(xp, factors, mode, precision)
            gamma = _hadamard(grams, mode)
            # A = B (Gamma + ridge I)^-1: the inverse of the small R x R
            # matrix, then one matmul at the stated precision
            inv = jnp.linalg.inv(gamma + _ridge(gamma, rank) * jnp.eye(rank))
            a = einsum2("ir,rs->is", b, inv, precision)
            weights = jnp.maximum(jnp.linalg.norm(a, axis=0), 1e-30)
            a = a / weights
            factors[mode] = a
            grams[mode] = einsum2("ir,is->rs", a, a, precision)
        inner = jnp.sum(b * (a * weights))
        recon = jnp.sum(_hadamard(grams, -1) * jnp.outer(weights, weights))
        err = jnp.maximum(normx**2 - 2 * inner + recon, 0.0)
        return factors, grams, weights, 1.0 - jnp.sqrt(err) / normx


@functools.partial(jax.jit, static_argnames=("precision",))
def _start(x, init, precision):
    with jax.default_matmul_precision(precision):
        grams = [einsum2("ir,is->rs", f, f, precision) for f in init]
        return grams, jnp.sqrt(jnp.sum(jnp.square(x)))


def solve(x, init, sweeps: int, precision: str = "highest"):
    """``sweeps`` ALS sweeps from ``init``: (factors, weights, fits).
    One compiled sweep serves them all."""
    xp = x if precision == "highest" else _prepare(x, precision)
    factors = list(init)
    grams, normx = _start(x, factors, precision)
    fits = []
    for _ in range(sweeps):
        factors, grams, weights, fit = _sweep(xp, factors, grams, normx,
                                              precision)
        fits.append(fit)
    return factors, weights, jnp.stack(fits)


@jax.jit
def _last_mttkrp(x, factors):
    with jax.default_matmul_precision("highest"):
        return mttkrp(x, factors, len(factors) - 1, "highest")


def step_gap(x, factors, weights) -> float:
    """How far the last factor is from the ALS update of the others.

    In a sweep the last mode is updated last, from the final factors of
    the other modes, so ``A_last diag(w) (Gamma + ridge I)`` must equal
    the MTTKRP of ``X`` with those factors.  Returns
    ``||A_last diag(w) (Gamma + ridge I) - B|| / ||B||``, with ``B`` at
    ``highest`` and the product in float64: one step, not the iteration,
    so the number does not grow with the ALS trajectory's sensitivity."""
    b = np.asarray(_last_mttkrp(x, list(factors)), np.float64)
    fs = [np.asarray(f, np.float64) for f in factors]
    w = np.asarray(weights, np.float64)
    rank = w.shape[0]
    gamma = np.ones((rank, rank))
    for f in fs[:-1]:
        gamma = gamma * (f.T @ f)
    ridge = 1e-5 * np.trace(gamma) / rank + 1e-12
    got = (fs[-1] * w) @ (gamma + ridge * np.eye(rank))
    return float(np.linalg.norm(got - b) / np.linalg.norm(b))


def kruskal_gap(f1, w1, f2, w2) -> float:
    """``||[[w1; f1]] - [[w2; f2]]||_F / ||[[w2; f2]]||_F`` from the
    factors' Grams, in float64 on the host."""
    f1 = [np.asarray(f, np.float64) for f in f1]
    f2 = [np.asarray(f, np.float64) for f in f2]
    w1 = np.asarray(w1, np.float64)
    w2 = np.asarray(w2, np.float64)

    def inner(fa, wa, fb, wb):
        g = np.outer(wa, wb)
        for a, b in zip(fa, fb):
            g = g * (a.T @ b)
        return g.sum()

    n11, n22 = inner(f1, w1, f1, w1), inner(f2, w2, f2, w2)
    d2 = max(n11 + n22 - 2 * inner(f1, w1, f2, w2), 0.0)
    return float(np.sqrt(d2 / n22))


def gaps(answer: dict, ref: dict) -> dict:
    """The numbers compared for one decomposition: the final fit's gap,
    the relative distance between the two Kruskal tensors, the last step's
    distance from the ALS update (``step_gap``), and, where the answer
    carries every sweep's fit, the first sweep's fit gap and the gap in
    the number of sweeps."""
    out = {
        "fit_gap": abs(float(answer["fits"][-1]) - float(ref["fits"][-1])),
        "model_gap": kruskal_gap(answer["factors"], answer["weights"],
                                 ref["factors"], ref["weights"]),
        "step_gap": step_gap(answer["x"], answer["factors"],
                             answer["weights"]),
    }
    if answer.get("sweep_fits"):
        out["sweeps_gap"] = abs(len(answer["fits"]) - len(ref["fits"]))
        out["fit1_gap"] = abs(float(answer["fits"][0]) - float(ref["fits"][0]))
    return out


def reference(x, answer: dict, cfg: dict, precision: str) -> dict:
    """The reference's decomposition for one answer: same tensor, same
    initial factors, same sweeps."""
    f, w, fits = solve(x, tuple(answer["init"]), int(cfg["sweeps_per_solve"]),
                       precision)
    return {"factors": f, "weights": w, "fits": np.asarray(fits), "x": x,
            "sweep_fits": True}
