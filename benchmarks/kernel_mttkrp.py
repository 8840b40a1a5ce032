"""Benchmark: the Pallas blocked-MTTKRP kernel (TPU Algorithm 2).

interpret-mode correctness timing vs the jnp oracle, plus the kernel's
modeled HBM traffic against the paper's Eq (10) and the tensor-size floor
(the kernel runs in interpret mode, so its times are no device times).
All planning/traffic numbers come from the engine planner — the same
BlockPlan object the kernel executes.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from repro.core import bounds
from repro.engine import choose_blocks, mttkrp
from repro.kernels.ref import mttkrp_ref

CASES = [
    ((64, 64, 64), 16),
    ((128, 32, 64), 8),
    ((32, 32, 32, 16), 8),
]


def rows() -> list[tuple[str, float, str]]:
    out = []
    key = jax.random.PRNGKey(0)
    for dims, rank in CASES:
        kx, *kf = jax.random.split(key, len(dims) + 1)
        x = jax.random.normal(kx, dims, jnp.float32)
        fs = [
            jax.random.normal(k, (d, rank), jnp.float32)
            for k, d in zip(kf, dims)
        ]
        from repro import ExecutionContext

        pal_ctx = ExecutionContext.create(backend="pallas", interpret=True)
        t0 = time.perf_counter()
        got = mttkrp(x, fs, 0, ctx=pal_ctx)
        jax.block_until_ready(got)
        dt = (time.perf_counter() - t0) * 1e6
        ref = mttkrp_ref(x, fs, 0)
        err = float(jnp.max(jnp.abs(got - ref)))
        plan = choose_blocks(dims, rank)
        traffic = plan.traffic_model(dims, rank)
        tensor_bytes = math.prod(dims) * 4
        # paper ideal for VMEM-sized fast memory
        m_words = 8 * 2 ** 20 // 4
        lb = bounds.seq_lb(dims, rank, m_words) * 4
        name = f"kernel_mttkrp[{'x'.join(map(str, dims))},R{rank}]"
        derived = (
            f"maxerr={err:.2e};plan={plan.block_i}x"
            f"{'x'.join(map(str, plan.block_contract))}xR{plan.block_r};"
            f"modeled_bytes={traffic['total_bytes']};"
            f"eq10_bytes={traffic['eq10_bytes']};"
            f"tensor_bytes={tensor_bytes};lb_bytes={lb:.0f};"
            f"traffic/tensor={traffic['total_bytes'] / tensor_bytes:.2f}"
        )
        out.append((name, dt, derived))
    return out
