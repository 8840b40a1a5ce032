"""Serving layer: bucketing, padding exactness, convergence masks, spans.

Pins the contracts :mod:`repro.launch.serve` claims in its docstring:

- **Bucketing** — requests with equal tune-cache keys (padded shape,
  rank, dtype, memory model) land in ONE bucket and are executed by one
  batched call; anything that changes the key splits the bucket.
- **Padding exactness** — a zero-padded tensor with zero-padded initial
  factors evolves identically to the unpadded run under CP-ALS, so the
  cropped served result matches a direct :func:`repro.cp_als` call.
- **Per-element convergence masks** — a bucket mixing easy and hard
  tensors freezes the converged entries while the rest keep iterating.
- **Observability** — one ``serve_request`` span per request (with queue
  and execute phases) and one ``serve_bucket`` span per bucket.
- **ExecutionContext.compilation_cache** — validated, JSON round-tripped,
  and applied to JAX's persistent-cache config by
  ``ensure_compilation_cache()``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.tensor import random_factors, random_low_rank_tensor
from repro.engine.context import ExecutionContext
from repro.launch.serve import (
    DecompositionServer,
    bucket_key,
    bucket_shape,
    pad_to_bucket,
)
from repro.observe.trace import Trace


def _ctx(**kw):
    kw.setdefault("backend", "einsum")
    return ExecutionContext.create(**kw)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_bucket_shape_rounds_up_to_quantum():
    assert bucket_shape((7, 6, 5)) == (8, 8, 8)
    assert bucket_shape((8, 3, 2)) == (8, 8, 8)
    assert bucket_shape((9, 8, 17), pad_to=8) == (16, 8, 24)
    assert bucket_shape((5, 4), pad_to=1) == (5, 4)
    with pytest.raises(ValueError):
        bucket_shape((4, 4), pad_to=0)


def test_equal_keys_share_a_bucket():
    # same padded shape + rank + dtype -> same bucket key
    k1 = bucket_key((7, 6, 5), 3, jnp.float32)
    k2 = bucket_key((8, 3, 2), 3, jnp.float32)
    assert k1 == k2
    # anything that changes the tune-cache identity splits the bucket
    assert bucket_key((7, 6, 5), 4, jnp.float32) != k1
    assert bucket_key((7, 6, 5), 3, jnp.float64) != k1
    assert bucket_key((9, 6, 5), 3, jnp.float32) != k1
    assert bucket_key((3, 3, 3), 3, jnp.float32, pad_to=4) != bucket_key(
        (3, 3, 3), 3, jnp.float32, pad_to=8
    )


def test_server_groups_equal_keys_into_one_batched_call():
    srv = DecompositionServer(_ctx(), n_iters=3, tol=0.0)
    key = jax.random.PRNGKey(0)
    for i, shape in enumerate([(7, 6, 5), (8, 3, 2), (5, 5, 5)]):
        key, k = jax.random.split(key)
        x, _ = random_low_rank_tensor(k, shape, 3)
        srv.submit(x, 3, request_id=f"r{i}")
    # a fourth request in a DIFFERENT bucket (rank changes the key)
    key, k = jax.random.split(key)
    x, _ = random_low_rank_tensor(k, (7, 6, 5), 2)
    srv.submit(x, 2, request_id="r3")
    assert len(srv) == 4
    results = srv.flush()
    assert len(srv) == 0
    assert set(results) == {"r0", "r1", "r2", "r3"}
    assert results["r0"].bucket == results["r1"].bucket == results["r2"].bucket
    assert results["r0"].batch == 3
    assert results["r3"].bucket != results["r0"].bucket
    assert results["r3"].batch == 1
    # results come back cropped to each request's own shape
    assert [tuple(f.shape) for f in results["r1"].factors] == [
        (8, 3), (3, 3), (2, 3)
    ]


def test_submit_rejects_vectors():
    srv = DecompositionServer(_ctx())
    with pytest.raises(ValueError, match=">=2-way"):
        srv.submit(jnp.ones((5,)), 2)


# ---------------------------------------------------------------------------
# padding exactness
# ---------------------------------------------------------------------------

def test_pad_to_bucket_round_trips():
    x = jax.random.normal(jax.random.PRNGKey(3), (7, 6, 5))
    p = pad_to_bucket(x, (8, 8, 8))
    assert p.shape == (8, 8, 8)
    # the original block survives untouched; the padding is exactly zero
    assert np.array_equal(np.asarray(p[:7, :6, :5]), np.asarray(x))
    assert float(jnp.abs(p[7:]).sum()) == 0.0
    assert float(jnp.abs(p[:, 6:]).sum()) == 0.0
    assert float(jnp.abs(p[:, :, 5:]).sum()) == 0.0
    # already at the bucket shape -> returned as-is
    assert pad_to_bucket(p, (8, 8, 8)) is p
    with pytest.raises(ValueError, match="cannot pad"):
        pad_to_bucket(x, (6, 6, 6))


def test_served_result_matches_direct_cp_als():
    """The whole pipeline — pad, batch, crop — is invisible: a served
    request equals a direct ``cp_als`` on the unpadded tensor with the
    same init (the server seeds request ``i`` of a fresh server with
    ``PRNGKey(i+1)`` on the element shape)."""
    shape, rank, n_iters, tol = (7, 6, 5), 3, 6, 1e-4
    x, _ = random_low_rank_tensor(jax.random.PRNGKey(7), shape, rank)
    x = x + 0.05 * jax.random.normal(jax.random.PRNGKey(8), shape)
    srv = DecompositionServer(_ctx(), n_iters=n_iters, tol=tol)
    srv.submit(x, rank, request_id="solo")
    served = srv.flush()["solo"]
    init = random_factors(jax.random.PRNGKey(1), shape, rank, x.dtype)
    direct = repro.cp_als(
        x, rank, n_iters=n_iters, init_factors=init, tol=tol,
        ctx=_ctx(),
    )
    # cp_als appends one fit per completed sweep, so len(fits) is its
    # sweep count; early break == convergence
    assert served.n_iters == len(direct.fits)
    assert served.converged == (len(direct.fits) < n_iters)
    np.testing.assert_allclose(
        np.asarray(served.weights), np.asarray(direct.weights),
        rtol=0, atol=1e-6,
    )
    for fs, fd in zip(served.factors, direct.factors):
        assert fs.shape == fd.shape
        np.testing.assert_allclose(
            np.asarray(fs), np.asarray(fd), rtol=0, atol=1e-6
        )
    assert served.fit == pytest.approx(float(direct.final_fit), abs=1e-6)


# ---------------------------------------------------------------------------
# per-element convergence masks
# ---------------------------------------------------------------------------

def test_convergence_mask_freezes_easy_requests():
    """One exactly-low-rank tensor (converges in a few sweeps) and one
    noise tensor (never converges) share a bucket: the easy entry stops
    iterating early while the hard one runs to the sweep cap."""
    shape, rank, n_iters = (8, 8, 8), 3, 25
    # easy key 13: under JAX's partitionable threefry stream the tensor
    # from key 11 stalls ALS from PRNGKey(1) at fit 0.91
    easy, _ = random_low_rank_tensor(jax.random.PRNGKey(13), shape, rank)
    hard = jax.random.normal(jax.random.PRNGKey(12), shape)
    srv = DecompositionServer(_ctx(), n_iters=n_iters, tol=1e-5)
    srv.submit(easy, rank, request_id="easy")
    srv.submit(hard, rank, request_id="hard")
    results = srv.flush()
    assert results["easy"].bucket == results["hard"].bucket
    assert results["easy"].converged
    assert results["easy"].n_iters < n_iters
    assert results["easy"].n_iters < results["hard"].n_iters
    assert results["easy"].fit == pytest.approx(1.0, abs=1e-4)
    # the frozen entry tracks its solo run (same PRNGKey(1) init).
    # Batched grams use a differently-ordered float32 reduction, so the
    # sweep where the fit delta crosses tol can shift by one — but the
    # converged answer is the same decomposition.
    init = random_factors(jax.random.PRNGKey(1), shape, rank, easy.dtype)
    solo = repro.cp_als(
        easy, rank, n_iters=n_iters, init_factors=init, tol=1e-5,
        ctx=_ctx(),
    )
    assert abs(results["easy"].n_iters - len(solo.fits)) <= 1
    np.testing.assert_allclose(
        np.asarray(results["easy"].weights), np.asarray(solo.weights),
        rtol=1e-4,
    )


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_flush_records_one_span_per_request_and_bucket():
    srv = DecompositionServer(_ctx(observe=True), n_iters=3, tol=0.0)
    key = jax.random.PRNGKey(5)
    for i, shape in enumerate([(7, 6, 5), (6, 6, 5), (7, 6, 5)]):
        key, k = jax.random.split(key)
        srv.submit(jax.random.normal(k, shape), 3, request_id=f"r{i}")
    with Trace() as tr:
        results = srv.flush()
    reqs = [e for e in tr.events if e["kind"] == "serve_request"]
    buckets = [e for e in tr.events if e["kind"] == "serve_bucket"]
    assert len(reqs) == 3
    assert len(buckets) == 1
    assert {e["request_id"] for e in reqs} == {"r0", "r1", "r2"}
    for e in reqs:
        # both serving phases are reported, and they are sane
        assert e["queue_s"] >= 0.0
        assert e["execute_s"] > 0.0
        assert e["bucket"] == buckets[0]["bucket"]
        assert e["batch"] == 3
        assert e["cold"] is True
    assert buckets[0]["batch"] == 3
    assert buckets[0]["padded_shape"] == [8, 8, 8]
    # telemetry agrees with the returned results
    assert results["r0"].queue_s >= 0.0
    assert results["r0"].execute_s == pytest.approx(
        buckets[0]["execute_s"]
    )
    # a second flush of the same bucket is warm
    key, k = jax.random.split(key)
    srv.submit(jax.random.normal(k, (7, 6, 5)), 3, request_id="r4")
    with Trace() as tr2:
        srv.flush()
    (bucket2,) = (e for e in tr2.events if e["kind"] == "serve_bucket")
    assert bucket2["cold"] is False


def test_observed_capture_skips_unobserved_servers():
    # a capture="observed" trace only records ctx.observe=True calls
    srv = DecompositionServer(_ctx(observe=False), n_iters=2, tol=0.0)
    srv.submit(jax.random.normal(jax.random.PRNGKey(1), (6, 5, 4)), 2)
    with Trace(capture="observed") as tr:
        srv.flush()
    assert [e for e in tr.events if e["kind"].startswith("serve")] == []
    # and with no trace active at all, flushing records nothing anywhere
    srv2 = DecompositionServer(_ctx(observe=True), n_iters=2, tol=0.0)
    srv2.submit(jax.random.normal(jax.random.PRNGKey(2), (6, 5, 4)), 2)
    srv2.flush()  # must not raise


# ---------------------------------------------------------------------------
# compilation_cache context field
# ---------------------------------------------------------------------------

def test_compilation_cache_round_trips_and_validates(tmp_path):
    ctx = ExecutionContext.create(
        backend="einsum", compilation_cache=str(tmp_path / "cc")
    )
    back = ExecutionContext.from_json(ctx.to_json())
    assert back == ctx
    assert back.compilation_cache == str(tmp_path / "cc")
    # absent key in older payloads -> None (back-compat)
    d = ctx.to_dict()
    d.pop("compilation_cache")
    assert ExecutionContext.from_dict(d).compilation_cache is None
    with pytest.raises((TypeError, ValueError)):
        ExecutionContext.create(backend="einsum", compilation_cache=7)


def test_ensure_compilation_cache_points_jax_at_the_directory(tmp_path):
    cc = str(tmp_path / "cc")
    ctx = ExecutionContext.create(backend="einsum", compilation_cache=cc)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert ctx.ensure_compilation_cache() == cc
        import os

        assert os.path.isdir(cc)
        assert jax.config.jax_compilation_cache_dir == cc
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    # a context without the field is a no-op
    assert ExecutionContext.create(
        backend="einsum"
    ).ensure_compilation_cache() is None


def test_ensure_compilation_cache_yields_to_environment(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins: no other directory is configured."""
    env_dir = str(tmp_path / "env_cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    ctx = ExecutionContext.create(
        backend="einsum", compilation_cache=str(tmp_path / "ctx_cc")
    )
    prev = jax.config.jax_compilation_cache_dir
    assert ctx.ensure_compilation_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == prev
    assert not (tmp_path / "ctx_cc").exists()


def test_checkout_compilation_cache_is_fixed_and_ignored():
    """The checkout's cache directory is one fixed path that git ignores."""
    import os

    from repro.engine.context import CHECKOUT_COMPILATION_CACHE

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CHECKOUT_COMPILATION_CACHE == os.path.join(root, ".cache", "jax")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".cache/" in f.read().split()
