"""Benchmark: the serving layer's amortization claims, measured.

Two tables:

  * ``serve_batched_B{b}`` / ``serve_looped_B{b}`` — requests/sec of ONE
    batched ``cp_als_batched`` call on a B-stack vs a Python loop of B
    single ``cp_als`` calls (same inits, warm programs). The batched
    path pays plan resolution and dispatch once per sweep-mode instead
    of once per request — the Eq-9/10 amortization argument applied to
    launch overhead; at B>=4 batched must be strictly faster.
  * ``serve_queue_B{b}`` — end-to-end ``DecompositionServer`` flush
    (bucketing + padding + batched execute) in requests/sec.

``REPRO_BENCH_TINY=1`` shrinks shapes/batches for CI smoke runs.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

SHAPE, RANK, ITERS = (16, 14, 12), 4, 5
BATCHES = (1, 2, 4, 8)
TINY_SHAPE, TINY_BATCHES = (10, 8, 6), (1, 4)


def _timed(fn, reps: int = 3) -> float:
    jax.block_until_ready(fn())  # warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def rows() -> list[tuple[str, float, str]]:
    tiny = os.environ.get("REPRO_BENCH_TINY") == "1"
    shape = TINY_SHAPE if tiny else SHAPE
    batches = TINY_BATCHES if tiny else BATCHES
    iters = 3 if tiny else ITERS
    out: list[tuple[str, float, str]] = []

    from repro.core.cp_als import cp_als
    from repro.core.tensor import random_factors
    from repro.engine.batch import cp_als_batched
    from repro.launch.serve import DecompositionServer

    key = jax.random.PRNGKey(0)
    for b in batches:
        x = jax.random.normal(key, (b,) + shape)
        keys = jax.random.split(jax.random.PRNGKey(1), b)
        inits = [
            jnp.stack(f) for f in zip(*[
                random_factors(k, shape, RANK, x.dtype) for k in keys
            ])
        ]

        us_batched = _timed(lambda: cp_als_batched(
            x, RANK, n_iters=iters, init_factors=inits
        ).weights)
        us_looped = _timed(lambda: [
            cp_als(
                x[i], RANK, n_iters=iters,
                init_factors=[f[i] for f in inits],
            ).weights
            for i in range(b)
        ][-1])
        speedup = us_looped / us_batched
        out.append((
            f"serve_batched_B{b}", us_batched,
            f"req_per_s={b / (us_batched * 1e-6):.1f} "
            f"batched_speedup={speedup:.2f}x",
        ))
        out.append((
            f"serve_looped_B{b}", us_looped,
            f"req_per_s={b / (us_looped * 1e-6):.1f}",
        ))

        def queue_flush(xb=x, b=b):
            srv = DecompositionServer(n_iters=iters, tol=0.0)
            for i in range(b):
                srv.submit(xb[i], RANK, request_id=f"r{i}")
            return jnp.asarray(
                [r.fit for r in srv.flush().values()]
            )

        us_queue = _timed(queue_flush)
        out.append((
            f"serve_queue_B{b}", us_queue,
            f"req_per_s={b / (us_queue * 1e-6):.1f}",
        ))
    return out


if __name__ == "__main__":
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")
