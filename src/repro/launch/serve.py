"""Decomposition-as-a-service: bucket, pad, batch — one plan per bucket.

The serving layer on top of the batched engine
(:mod:`repro.engine.batch`). Incoming requests (one tensor each, a CP
rank, a dtype) are **bucketed** by their tune-cache key: extents are
rounded up to the bucket quantum (``pad_to``), and every request whose
padded shape / rank / dtype / memory model agree lands in the same
bucket. A flush pads each request to its bucket's plan shape, stacks
the bucket into one ``(B, I_0, ..., I_{N-1})`` array, and runs ONE
:func:`~repro.engine.batch.cp_als_batched` call per bucket — one plan
resolution, one compiled program, one kernel launch per contraction for
all B requests. This is the same amortization the paper's Eq 9/10 make
for factor traffic, applied one level up: plan choice, autotune lookup,
and XLA compilation are paid once per bucket, not once per request.

Padding is exact, not approximate: a zero-padded tensor with zero-padded
initial factors evolves *identically* to the unpadded run under CP-ALS
(padded MTTKRP rows are zero, so padded factor rows stay zero and
contribute nothing to any Gram), so cropping the result recovers the
unpadded answer bit-for-bit. ``tests/test_serve.py`` pins this.

Warm starts persist across processes through JAX's compilation cache:
an :class:`~repro.engine.context.ExecutionContext` with
``compilation_cache=<dir>`` makes the server call
``ensure_compilation_cache()`` before its first flush, so a second
server process serving the same buckets reloads every compiled program
from disk.  The CLI keeps that cache in the checkout's ``.cache/jax``
(``CHECKOUT_COMPILATION_CACHE``) unless ``JAX_COMPILATION_CACHE_DIR``
names another directory.

CLI demo (synthetic workload, prints req/s)::

    PYTHONPATH=src python -m repro.launch.serve \
        --requests 16 --shape 12x10x8 --rank 4
"""

from __future__ import annotations

import argparse
import time
import uuid
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp

from ..engine.context import CHECKOUT_COMPILATION_CACHE, ExecutionContext
from ..engine.plan import Memory
from ..observe import trace as _otrace

#: Default bucket quantum: extents round up to the next multiple.
DEFAULT_PAD_TO = 8


def bucket_shape(
    shape: Sequence[int], pad_to: int = DEFAULT_PAD_TO
) -> tuple[int, ...]:
    """The plan shape a request's tensor is padded to: each extent
    rounded up to the next multiple of ``pad_to``, so nearby shapes
    share one bucket (and therefore one plan and one compiled
    program)."""
    if pad_to < 1:
        raise ValueError(f"pad_to must be >= 1, got {pad_to}")
    return tuple(-(-int(s) // pad_to) * pad_to for s in shape)


def bucket_key(
    shape: Sequence[int],
    rank: int,
    dtype,
    *,
    memory: Memory | None = None,
    pad_to: int = DEFAULT_PAD_TO,
) -> str:
    """The bucket identity: the tune-cache key of the *padded* problem
    (``kind="serve"``), so two requests share a bucket exactly when the
    engine would resolve them to the same tuned plan."""
    from ..tune.cache import cache_key  # lazy: launch <-> tune layering

    mem = memory or Memory.abstract(2 ** 20)
    return cache_key(
        bucket_shape(shape, pad_to), rank, 0, dtype, mem, kind="serve"
    )


def pad_to_bucket(x: jax.Array, padded: Sequence[int]) -> jax.Array:
    """Zero-pad ``x`` up to the bucket's plan shape (exact for CP-ALS:
    see the module docstring's invariance argument)."""
    if tuple(x.shape) == tuple(padded):
        return x
    widths = [(0, int(p) - int(s)) for s, p in zip(x.shape, padded)]
    if any(w[1] < 0 for w in widths):
        raise ValueError(
            f"cannot pad shape {tuple(x.shape)} down to {tuple(padded)}"
        )
    return jnp.pad(x, widths)


@dataclass
class Request:
    """One queued decomposition request."""

    request_id: str
    x: jax.Array
    rank: int
    key: str  # bucket key
    enqueued_at: float = field(default_factory=time.perf_counter)


@dataclass
class ServeResult:
    """One served decomposition: the cropped per-request CP result plus
    the serving telemetry (bucket, batch size, queue/execute seconds,
    whether this flush compiled the bucket's program cold)."""

    request_id: str
    factors: list[jax.Array]
    weights: jax.Array
    fit: float
    n_iters: int
    converged: bool
    bucket: str
    batch: int
    queue_s: float
    execute_s: float
    cold: bool


class DecompositionServer:
    """The request queue + batched executor.

    ``submit()`` enqueues a tensor; ``flush()`` groups the queue into
    buckets (equal :func:`bucket_key` → one bucket), pads within each
    bucket to the bucket's plan shape, executes ONE
    :func:`~repro.engine.batch.cp_als_batched` call per bucket, and
    returns cropped per-request :class:`ServeResult` values. Per-element
    convergence masks mean a bucket mixing easy and hard tensors stops
    updating the easy ones as soon as they converge.

    With ``ctx.observe`` on and an active :class:`repro.observe.Trace`,
    every flush records one ``serve_request`` span per request (queue
    and execute phase seconds) and one ``serve_bucket`` span per bucket
    (batch size, padded shape, cold/warm); with ``annotate`` on, the
    profiler sees ``repro.serve.flush`` and, per bucket,
    ``repro.serve.pack`` and ``repro.serve.unpack`` around the batched
    driver's own spans.
    """

    def __init__(
        self,
        ctx: ExecutionContext | None = None,
        *,
        pad_to: int = DEFAULT_PAD_TO,
        n_iters: int = 20,
        tol: float = 1e-4,
    ):
        self.ctx = ctx or ExecutionContext.default()
        self.pad_to = int(pad_to)
        self.n_iters = int(n_iters)
        self.tol = float(tol)
        self._queue: list[Request] = []
        self._seen_buckets: set[str] = set()
        self._seed = 0
        # point XLA's persistent cache at the context's directory BEFORE
        # the first compile, so warm-start processes reload from disk
        self.ctx.ensure_compilation_cache()

    def __len__(self) -> int:
        return len(self._queue)

    def submit(
        self, x: jax.Array, rank: int, request_id: str | None = None
    ) -> str:
        """Enqueue one tensor for CP decomposition; returns the request
        id (generated when not given). Nothing executes until
        :meth:`flush`."""
        if x.ndim < 2:
            raise ValueError(
                f"serve requests are >=2-way tensors, got shape "
                f"{tuple(x.shape)}"
            )
        rid = request_id if request_id is not None else uuid.uuid4().hex
        key = bucket_key(
            x.shape, rank, x.dtype, memory=self.ctx.memory,
            pad_to=self.pad_to,
        )
        self._queue.append(Request(rid, x, int(rank), key))
        return rid

    def flush(self) -> dict[str, ServeResult]:
        """Execute the queue: one batched call per bucket; returns
        ``{request_id: ServeResult}`` and empties the queue."""
        with _otrace.annotated("repro.serve.flush"):
            return self._flush()

    def _pack(self, reqs: list[Request], padded, rank: int, dtype):
        """The bucket's stacked, zero-padded tensors and initial factors."""
        from ..core.tensor import random_factors

        with _otrace.annotated("repro.serve.pack"):
            xs = jnp.stack(
                [pad_to_bucket(r.x.astype(dtype), padded) for r in reqs]
            )
            # per-request random inits on the ELEMENT shape, zero-padded
            # to the bucket shape: the padding-invariance contract
            inits = []
            for r in reqs:
                self._seed += 1
                fs = random_factors(
                    jax.random.PRNGKey(self._seed), r.x.shape, rank, dtype
                )
                inits.append([
                    jnp.zeros((p, rank), dtype).at[: f.shape[0]].set(f)
                    for f, p in zip(fs, padded)
                ])
            init_factors = [
                jnp.stack([init[k] for init in inits])
                for k in range(len(padded))
            ]
            return xs, init_factors

    def _flush(self) -> dict[str, ServeResult]:
        from ..engine.batch import cp_als_batched

        queue, self._queue = self._queue, []
        buckets: dict[str, list[Request]] = {}
        for req in queue:
            buckets.setdefault(req.key, []).append(req)
        out: dict[str, ServeResult] = {}
        for key, reqs in buckets.items():
            t_exec0 = time.perf_counter()
            cold = key not in self._seen_buckets
            self._seen_buckets.add(key)
            padded = bucket_shape(reqs[0].x.shape, self.pad_to)
            rank = reqs[0].rank
            dtype = reqs[0].x.dtype
            xs, init_factors = self._pack(reqs, padded, rank, dtype)
            res = cp_als_batched(
                xs, rank, n_iters=self.n_iters,
                init_factors=init_factors, tol=self.tol, ctx=self.ctx,
            )
            jax.block_until_ready(res.weights)
            t_exec1 = time.perf_counter()
            execute_s = t_exec1 - t_exec0
            if _otrace.should_record(self.ctx.observe):
                _otrace.record_event(
                    "serve_bucket",
                    bucket=key,
                    batch=len(reqs),
                    padded_shape=list(padded),
                    rank=rank,
                    cold=cold,
                    execute_s=execute_s,
                )
            for b, r in enumerate(reqs):
                with _otrace.annotated("repro.serve.unpack"):
                    out[r.request_id] = sr = ServeResult(
                        request_id=r.request_id,
                        factors=[
                            f[b, : r.x.shape[k]]
                            for k, f in enumerate(res.factors)
                        ],
                        weights=res.weights[b],
                        fit=float(res.fits[b]),
                        n_iters=int(res.n_iters[b]),
                        converged=bool(res.converged[b]),
                        bucket=key,
                        batch=len(reqs),
                        queue_s=t_exec0 - r.enqueued_at,
                        execute_s=execute_s,
                        cold=cold,
                    )
                if _otrace.should_record(self.ctx.observe):
                    _otrace.record_event(
                        "serve_request",
                        request_id=r.request_id,
                        bucket=key,
                        batch=sr.batch,
                        shape=list(r.x.shape),
                        rank=rank,
                        queue_s=sr.queue_s,
                        execute_s=sr.execute_s,
                        fit=sr.fit,
                        n_iters=sr.n_iters,
                        converged=sr.converged,
                        cold=cold,
                    )
        return out


def _parse_shape(s: str) -> tuple[int, ...]:
    return tuple(int(t) for t in s.split("x"))


def main(argv: list[str] | None = None) -> int:
    """Synthetic-workload demo: enqueue ``--requests`` random low-rank
    tensors (shapes jittered below ``--shape`` so several element shapes
    share each bucket), flush once, and print bucket stats and req/s."""
    ap = argparse.ArgumentParser(prog="repro.launch.serve", description=__doc__)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--shape", type=_parse_shape, default=(12, 10, 8))
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--pad-to", type=int, default=DEFAULT_PAD_TO)
    ap.add_argument(
        "--cache-dir", default=CHECKOUT_COMPILATION_CACHE,
        help="persistent XLA compilation cache directory (warm starts); "
        "JAX_COMPILATION_CACHE_DIR, when set, takes its place",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from ..core.tensor import random_low_rank_tensor

    ctx = ExecutionContext.create(
        backend="auto", compilation_cache=args.cache_dir
    )
    server = DecompositionServer(
        ctx, pad_to=args.pad_to, n_iters=args.iters, tol=args.tol
    )
    key = jax.random.PRNGKey(args.seed)
    for i in range(args.requests):
        key, k1, k2 = jax.random.split(key, 3)
        # jitter extents down by up to pad_to-1: same bucket, mixed shapes
        jit = jax.random.randint(
            k1, (len(args.shape),), 0, max(args.pad_to, 2)
        )
        shape = tuple(
            max(int(s) - int(j), 2) for s, j in zip(args.shape, jit)
        )
        x, _ = random_low_rank_tensor(k2, shape, args.rank)
        server.submit(x, args.rank, request_id=f"req{i}")
    t0 = time.perf_counter()
    results = server.flush()
    dt = time.perf_counter() - t0
    n_buckets = len({r.bucket for r in results.values()})
    print(
        f"served {len(results)} request(s) in {dt * 1e3:.1f} ms "
        f"({len(results) / dt:.1f} req/s) across {n_buckets} bucket(s)"
    )
    for rid in sorted(results, key=lambda r: int(r[3:])):
        r = results[rid]
        print(
            f"  {rid}: fit={r.fit:.4f} iters={r.n_iters} "
            f"converged={r.converged} batch={r.batch} "
            f"{'cold' if r.cold else 'warm'}"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
