"""CP decomposition drivers (the paper's application context)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ExecutionContext
from repro.core.cp_als import cp_als, cp_gradient
from repro.core.krp import mttkrp_via_matmul
from repro.core.mttkrp import mttkrp
from repro.core.tensor import (
    random_low_rank_tensor,
    relative_error,
    tensor_from_factors,
)


def test_als_recovers_exact_low_rank():
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(0), (12, 10, 8), 3)
    res = cp_als(x, 3, n_iters=60, key=jax.random.PRNGKey(1))
    assert res.final_fit > 0.999
    recon = tensor_from_factors(res.factors, res.weights)
    assert float(relative_error(x, recon)) < 0.02


def test_als_weights_not_double_counted():
    """Regression: λ used to be folded into the last-updated factor AND
    returned in CPResult.weights, so reconstructing with weights scaled by
    λ twice.  Now the factors are column-normalized Kruskal form: applying
    weights exactly once reconstructs X; the old double-application leaves
    a large error."""
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(20), (12, 10, 8), 3)
    res = cp_als(x, 3, n_iters=60, key=jax.random.PRNGKey(25))
    assert res.final_fit > 0.999
    # every factor is column-normalized (λ lives only in .weights)
    for f in res.factors:
        np.testing.assert_allclose(
            np.asarray(jnp.linalg.norm(f, axis=0)), 1.0, rtol=1e-4
        )
    once = tensor_from_factors(res.factors, res.weights)
    assert float(relative_error(x, once)) < 0.02
    assert float(relative_error(x, res.reconstruct())) < 0.02
    # the buggy convention (weights applied twice) must NOT reconstruct
    folded = [f for f in res.factors]
    folded[-1] = folded[-1] * res.weights
    twice = tensor_from_factors(folded, res.weights)
    assert float(relative_error(x, twice)) > 0.05


def test_als_fit_monotone_after_burnin():
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(2), (10, 9, 8), 4)
    res = cp_als(x, 4, n_iters=25, key=jax.random.PRNGKey(3))
    fits = res.fits[3:]
    assert all(b >= a - 1e-3 for a, b in zip(fits, fits[1:]))


def test_als_dimension_tree_matches_plain():
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(4), (8, 7, 6, 5), 2)
    plain = cp_als(x, 2, n_iters=8, key=jax.random.PRNGKey(5))
    tree = cp_als(
        x, 2, n_iters=8, key=jax.random.PRNGKey(5), sweep="dimtree"
    )
    for a, b in zip(plain.fits, tree.fits):
        assert abs(a - b) < 5e-3


def test_als_with_matmul_baseline_backend():
    """Any MTTKRP backend must be pluggable: the explicit-KRP baseline gives
    the same decomposition."""
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(6), (9, 8, 7), 2)
    a = cp_als(x, 2, n_iters=10, key=jax.random.PRNGKey(7), mttkrp_fn=mttkrp)
    b = cp_als(
        x, 2, n_iters=10, key=jax.random.PRNGKey(7),
        mttkrp_fn=mttkrp_via_matmul,
    )
    for fa, fb in zip(a.fits, b.fits):
        assert abs(fa - fb) < 5e-3


def test_distributed_path_rejects_unsupported_combinations():
    """The distributed branch fails loudly (before any mesh work) on
    options the sweep driver cannot honor, instead of silently ignoring
    them."""
    x = jnp.zeros((4, 4, 4))
    dist = ExecutionContext.create(distributed=True)
    with pytest.raises(ValueError, match="mttkrp_fn"):
        cp_als(x, 2, ctx=dist, mttkrp_fn=mttkrp)
    with pytest.raises(ValueError, match="not supported on the distributed"):
        cp_als(x, 2, ctx=dist, sweep="dimtree")
    with pytest.raises(ValueError, match="tune=True is not supported"):
        cp_als(x, 2, ctx=ExecutionContext.create(
            distributed=True, backend="auto", tune=True))


def test_gradient_engine_parity():
    """Regression: cp_gradient used to hardcode the naive einsum MTTKRP and
    accept no engine knobs.  It now dispatches through the engine like
    cp_als: the Pallas backend (interpret mode on CPU) yields the same
    optimization trajectory as the einsum backend."""
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(30), (8, 6, 5), 2)
    ein = cp_gradient(x, 2, n_iters=30, lr=0.05, key=jax.random.PRNGKey(31))
    pal = cp_gradient(
        x, 2, n_iters=30, lr=0.05, key=jax.random.PRNGKey(31),
        ctx=ExecutionContext.create(backend="pallas", interpret=True),
    )
    assert len(ein.fits) == len(pal.fits) > 0
    for a, b in zip(ein.fits, pal.fits):
        assert abs(a - b) < 1e-4, (a, b)


def test_gradient_driver_converges():
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(8), (10, 8, 6), 2)
    res = cp_gradient(x, 2, n_iters=400, lr=0.03, key=jax.random.PRNGKey(9))
    assert res.final_fit > 0.95


def test_als_4way():
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(10), (6, 5, 4, 7), 2)
    res = cp_als(x, 2, n_iters=40, key=jax.random.PRNGKey(11))
    assert res.final_fit > 0.99


def test_als_noisy_tensor_partial_fit():
    key = jax.random.PRNGKey(12)
    x, _ = random_low_rank_tensor(key, (14, 12, 10), 3)
    noise = 0.01 * jax.random.normal(jax.random.PRNGKey(13), x.shape)
    res = cp_als(x + noise, 3, n_iters=30, key=jax.random.PRNGKey(14))
    assert 0.9 < res.final_fit <= 1.0


def test_als_overdetermined_rank_ok():
    """Rank larger than the true rank must not blow up (ridge regularized)."""
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(15), (8, 8, 8), 2)
    res = cp_als(x, 5, n_iters=15, key=jax.random.PRNGKey(16))
    assert np.isfinite(res.final_fit)
    assert res.final_fit > 0.98
