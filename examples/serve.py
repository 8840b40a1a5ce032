"""Decomposition-as-a-service demo: batched engine + request queue.

Three steps:

1. The batched engine path — ``repro.mttkrp`` with a leading batch axis
   is ONE dispatch for B tensors (same answer as a Python loop), and
   ``repro.cp_als_batched`` runs B decompositions as one vmapped sweep
   with per-element convergence masks.
2. The serving layer — a ``DecompositionServer`` buckets mixed-shape
   requests by tune-cache key, pads within each bucket (exactly — the
   cropped result matches the unpadded run bit-for-bit), and executes
   one batched call per bucket.
3. Warm starts — a context with ``compilation_cache=<dir>`` persists
   every compiled program, so the next process serving the same buckets
   skips recompilation.  This demo keeps them in the checkout's
   ``.cache/jax`` (``JAX_COMPILATION_CACHE_DIR``, when set, wins).

    PYTHONPATH=src python examples/serve.py
    REPRO_EX_TINY=1 PYTHONPATH=src python examples/serve.py   # CI smoke
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

import repro
from repro.core.tensor import random_low_rank_tensor
from repro.engine.context import CHECKOUT_COMPILATION_CACHE
from repro.launch.serve import DecompositionServer


def main():
    tiny = os.environ.get("REPRO_EX_TINY") == "1"
    dims, rank = ((10, 8, 6) if tiny else (20, 16, 12)), 3
    batch = 3 if tiny else 6
    n_iters = 4 if tiny else 12

    # 1. the batched engine path: one dispatch, B answers
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch,) + dims)
    factors = [
        jax.random.normal(jax.random.PRNGKey(k + 1), (batch, d, rank))
        for k, d in enumerate(dims)
    ]
    batched = repro.mttkrp(x, factors, 0)  # leading B axis -> batched
    looped = jnp.stack([
        repro.mttkrp(x[b], [f[b] for f in factors], 0)
        for b in range(batch)
    ])
    print(f"batched MTTKRP over B={batch}: max |batched - looped| = "
          f"{float(jnp.max(jnp.abs(batched - looped))):.2e}")

    res = repro.cp_als_batched(x, rank, n_iters=n_iters, tol=1e-4)
    print(f"cp_als_batched: fits={[f'{f:.3f}' for f in res.fits]} "
          f"iters={[int(i) for i in res.n_iters]}")

    # 2. the serving layer: mixed shapes, one batched call per bucket;
    # 3. warm starts: compiled programs persist in the checkout's cache
    ctx = repro.ExecutionContext.create(
        backend="auto", compilation_cache=CHECKOUT_COMPILATION_CACHE
    )
    server = DecompositionServer(ctx, n_iters=n_iters, tol=1e-4)
    for i in range(batch):
        shape = tuple(d - i for d in dims)  # jitter: same bucket
        t, _ = random_low_rank_tensor(
            jax.random.PRNGKey(10 + i), shape, rank
        )
        server.submit(t, rank, request_id=f"req{i}")
    results = server.flush()
    buckets = {r.bucket for r in results.values()}
    print(f"served {len(results)} mixed-shape requests in "
          f"{len(buckets)} bucket(s):")
    for rid in sorted(results):
        r = results[rid]
        print(f"  {rid}: shape->crop fit={r.fit:.4f} "
              f"iters={r.n_iters} batch={r.batch} "
              f"{'cold' if r.cold else 'warm'}")
    cache_dir = ctx.ensure_compilation_cache()
    n_cached = sum(len(fs) for _, _, fs in os.walk(cache_dir))
    print(f"persistent compilation cache {cache_dir}: {n_cached} "
          f"program(s) kept for the next process")


if __name__ == "__main__":
    main()
