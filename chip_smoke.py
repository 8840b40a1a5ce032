"""Bring-up smoke test: the decomposition stack's main path on one TPU chip.

    python chip_smoke.py [--seed N]            # phases (a)-(d), one chip
    python chip_smoke.py --chips 4 [--seed N]  # the distributed drivers only

Runs in one process, through the public entry points
(``ExecutionContext`` -> ``repro.cp_als`` / ``repro.tucker_hooi`` /
``repro.mttkrp`` / ``repro.multi_ttm`` -> the Pallas kernels, and
``launch.serve.DecompositionServer``), with the kernels compiled by
Mosaic (``interpret=False``).  Data is a seeded low-rank tensor plus
noise, generated on the device one mode-0 slab at a time.

Phases, one JSON line each:

  (a) ``cp3``    CP-ALS, 1024^3 fp32, rank 64, per-mode sweeps;
  (b) ``cp4``    CP-ALS, 256x256x256x32 fp32, rank 32, fused and
                 per-mode sweeps (the third mode halves until the
                 compiled programs fit the chip's HBM; the line says so);
  (c) ``tucker`` HOOI, 1024^3 fp32, ranks (32, 32, 32);
  (d) ``serve``  ``DecompositionServer(backend="auto")``, 8 requests of
                 about 256^3 at rank 16;

or, with ``--chips 4``:

  (e) ``cp_dist``     ``cp_als`` on a (2, 2, 1) grid vs the sequential
                      driver;
  (f) ``tucker_dist`` ``tucker_hooi`` on a (2, 2, 1) grid vs sequential.

Each line gives ``compile_s`` (ahead-of-time compiles of the phase's
kernel-bearing engine calls), ``first_call_s`` and ``run_s`` (the
driver's first and second call, each timed around
``block_until_ready``), the change in ``engine.pallas_dispatches``,
whether ``tpu_custom_call`` is in every compiled text, and its checks:
each a measured error, its tolerance, and the tolerance's reason.  The
drivers and the references run under
``jax.default_matmul_precision("highest")``, so XLA's own fp32 dots are
full fp32 and the comparisons see the kernels; the kernels pin
``Precision.HIGHEST`` for fp32 operands themselves.

The last line is ``{"ok": true, "device": {...}}`` when every phase
passed.  With no TPU, or outside this repository, the script prints no
result and exits non-zero.  The persistent compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
``.cache/jax``.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

HIGHEST = "highest"
#: fp32 with HIGHEST-precision MXU passes: sums of 2^20..2^30 products in
#: a blocked order against the reference's order
TOL_KERNEL = 1e-4
TOL_KERNEL_WHY = (
    "fp32 accumulation of up to 2^30 products in blocked order vs the "
    "reference's order; MXU at Precision.HIGHEST"
)
#: fits of two fp32 runs of the same iteration from the same init
TOL_FIT = 1e-4
TOL_FIT_WHY = (
    "same init and sweeps; fp32 reduction order differs between backends "
    "and ALS carries it across sweeps"
)


# ---------------------------------------------------------------------------
# data and references
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "rank", "noise"))
def low_rank_plus_noise(key, shape, rank, noise=0.1):
    """A rank-``rank`` CP tensor plus Gaussian noise of relative size
    about ``noise``, built one mode-0 slab at a time so the device holds
    the tensor and one slab of temporaries."""
    keys = jax.random.split(key, len(shape) + 1)
    fs = [jax.random.normal(k, (s, rank)) for k, s in zip(keys, shape)]
    letters = "abcdefgh"[: len(shape) - 1]
    spec = "r," + ",".join(f"{c}r" for c in letters) + "->" + letters
    sigma = noise * math.sqrt(rank)

    def slab(i):
        s = jnp.einsum(spec, fs[0][i], *fs[1:], precision=HIGHEST)
        k = jax.random.fold_in(keys[-1], i)
        return s + sigma * jax.random.normal(k, shape[1:])

    return jax.lax.map(slab, jnp.arange(shape[0]))


@functools.partial(jax.jit, static_argnames=("shape", "ranks", "noise"))
def tucker_plus_noise(key, shape, ranks, noise=0.1):
    """A multilinear-rank-``ranks`` tensor plus noise, slab by slab."""
    keys = jax.random.split(key, len(shape) + 2)
    core = jax.random.normal(keys[0], ranks)
    fs = [jnp.linalg.qr(jax.random.normal(k, (s, r)))[0]
          for k, s, r in zip(keys[1:], shape, ranks)]
    scale = math.sqrt(math.prod(ranks) / math.prod(shape))
    letters = "abcdefgh"[: len(shape)]
    outs = "jklmnopq"[: len(shape) - 1]
    spec = (letters + "," + letters[0] + ","
            + ",".join(o + c for o, c in zip(outs, letters[1:]))
            + "->" + outs)

    def slab(i):
        s = jnp.einsum(spec, core, fs[0][i], *fs[1:], precision=HIGHEST)
        k = jax.random.fold_in(keys[-1], i)
        return s + noise * scale * jax.random.normal(k, shape[1:])

    return jax.lax.map(slab, jnp.arange(shape[0]))


def mttkrp_ref(x, factors, mode):
    n = x.ndim
    letters = "abcdefgh"[:n]
    ins = [letters] + [letters[k] + "z" for k in range(n) if k != mode]
    fs = [f for k, f in enumerate(factors) if k != mode]
    spec = ",".join(ins) + "->" + letters[mode] + "z"
    return jnp.einsum(spec, x, *fs, precision=HIGHEST)


def multi_ttm_ref(x, mats, keep):
    n = x.ndim
    letters = "abcdefgh"[:n]
    ranks = "pqrstuvw"[:n]
    ins, ops, out = [letters], [x], ""
    for k in range(n):
        if k == keep:
            out += letters[k]
        else:
            ins.append(letters[k] + ranks[k])
            ops.append(mats[k])
            out += ranks[k]
    return jnp.einsum(",".join(ins) + "->" + out, *ops, precision=HIGHEST)


def rel_err(out, ref) -> float:
    scale = float(jnp.max(jnp.abs(ref)))
    return float(jnp.max(jnp.abs(out - ref))) / max(scale, 1e-30)


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

def pallas_dispatches() -> float:
    from repro.observe.metrics import PALLAS_DISPATCHES, registry

    return registry().counter(PALLAS_DISPATCHES)


def aot(fn, *args):
    """Compile ``fn`` for ``args`` ahead of time: (seconds, has a Mosaic
    kernel, bytes the program needs on the device)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return dt, "tpu_custom_call" in compiled.as_text(), need


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def hbm_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2**30))


def sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def check(name, value, tol, why):
    return {"name": name, "value": value, "tol": tol, "why": why,
            "ok": bool(value <= tol)}


def finish(rec):
    """Fill ``ok`` and the headline error fields of a phase record."""
    checks = rec.get("checks", [])
    errs = [c for c in checks if c["name"].endswith("rel_err")] or checks
    worst = max(errs, key=lambda c: c["value"] / c["tol"] if c["tol"]
                else float(c["value"] > 0), default=None)
    if worst is not None:
        rec["max_rel_err"] = worst["value"]
        rec["tol"] = worst["tol"]
        rec["tol_reason"] = worst["why"]
    rec["ok"] = bool(
        checks and all(c["ok"] for c in checks)
        and rec.get("pallas_dispatches", 0) > 0
        and rec.get("tpu_custom_call", False)
    )
    return rec


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_cp(name, key, shape, rank, sweeps, iters, ctx_kw, shrink_mode=None):
    """CP-ALS through ``backend="pallas"`` with each sweep schedule in
    ``sweeps`` vs ``backend="einsum"``, plus every mode's MTTKRP vs the
    reference.  ``shrink_mode`` halves that mode until the kernel
    programs fit the chip's HBM."""
    import repro
    from repro.core.tensor import random_factors
    from repro.engine.sweep import _fused_pair

    ctx = repro.ExecutionContext.create(backend="pallas", **ctx_kw)
    ctx_e = repro.ExecutionContext.create(backend="einsum", **ctx_kw)
    rec = {"phase": name}
    shape = tuple(shape)
    asked = shape

    def programs(shp):
        xs, fs = sds(shp), [sds((s, rank)) for s in shp]
        progs = [(f"mttkrp{m}", functools.partial(
            lambda x, f, m: repro.mttkrp(x, f, m, ctx=ctx), m=m), xs, fs)
            for m in range(len(shp))]
        if "fused" in sweeps:
            progs.append(("fused_pair",
                          lambda x, f: _fused_pair(x, f, ctx), xs, fs))
        return progs

    limit = hbm_bytes()
    while True:
        try:
            compiled = [(n, *aot(fn, xs, fs))
                        for n, fn, xs, fs in programs(shape)]
            need = max(c[3] for c in compiled)
            why = (f"memory_analysis needs {need / 2**30:.2f} GiB of "
                   f"{limit / 2**30:.2f} GiB HBM")
        except jax.errors.JaxRuntimeError as e:  # the compiler's HBM refusal
            if shrink_mode is None or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            need, why = float("inf"), str(e).split("\n")[0][:300]
        # 15 % headroom for the arrays the drivers keep alive around it
        if shrink_mode is None or need <= 0.85 * limit \
                or shape[shrink_mode] <= 8:
            break
        rec.setdefault("shrunk", []).append(f"{list(shape)}: {why}")
        shape = tuple(s // 2 if k == shrink_mode else s
                      for k, s in enumerate(shape))
    if shape != asked:
        rec["shape_asked"] = list(asked)
    rec.update(shape=list(shape), rank=rank, iters=iters,
               compile_s=sum(c[1] for c in compiled),
               tpu_custom_call=all(c[2] for c in compiled),
               device_bytes_needed=max(c[3] for c in compiled))

    x = low_rank_plus_noise(key, shape, rank)
    init = random_factors(jax.random.fold_in(key, 1), shape, rank, x.dtype)
    d0 = pallas_dispatches()
    checks, fits = [], {}
    with jax.default_matmul_precision(HIGHEST):
        ref = repro.cp_als(x, rank, n_iters=iters, init_factors=init,
                           ctx=ctx_e)
        fits["einsum"] = ref.final_fit
        for sweep in sweeps:
            run = functools.partial(
                repro.cp_als, x, rank, n_iters=iters, init_factors=init,
                ctx=ctx, sweep=sweep,
            )
            _, first = timed(lambda: run().weights)
            res_w, warm = timed(run)
            rec[f"first_call_s_{sweep}"] = first
            rec[f"run_s_{sweep}"] = warm
            fits[sweep] = res_w.final_fit
            checks.append(check(
                f"fit_{sweep}_vs_einsum",
                abs(res_w.final_fit - ref.final_fit), TOL_FIT, TOL_FIT_WHY,
            ))
        for m in range(len(shape)):
            out = repro.mttkrp(x, res_w.factors, m, ctx=ctx)
            checks.append(check(
                f"mttkrp{m}_rel_err",
                rel_err(out, mttkrp_ref(x, res_w.factors, m)),
                TOL_KERNEL, TOL_KERNEL_WHY,
            ))
        jax.block_until_ready(out)
    rec["run_s"] = rec[f"run_s_{sweeps[0]}"]
    rec["first_call_s"] = rec[f"first_call_s_{sweeps[0]}"]
    rec["pallas_dispatches"] = pallas_dispatches() - d0
    rec["fits"] = fits
    rec["checks"] = checks
    return finish(rec)


def phase_tucker(key, shape, ranks, iters, ctx_kw):
    import repro

    ctx = repro.ExecutionContext.create(backend="pallas", **ctx_kw)
    ctx_e = repro.ExecutionContext.create(backend="einsum", **ctx_kw)
    shape, ranks = tuple(shape), tuple(ranks)
    rec = {"phase": "tucker", "shape": list(shape), "ranks": list(ranks),
           "iters": iters}
    xs = sds(shape)
    ms = [sds((s, r)) for s, r in zip(shape, ranks)]
    keeps = list(range(len(shape))) + [None]
    compiled = [aot(functools.partial(
        lambda x, m, k: repro.multi_ttm(x, m, k, ctx=ctx), k=k), xs, ms)
        for k in keeps]
    rec["compile_s"] = sum(c[0] for c in compiled)
    rec["tpu_custom_call"] = all(c[1] for c in compiled)
    rec["device_bytes_needed"] = max(c[2] for c in compiled)

    x = tucker_plus_noise(key, shape, ranks)
    d0 = pallas_dispatches()
    checks = []
    with jax.default_matmul_precision(HIGHEST):
        ref = repro.tucker_hooi(x, ranks, n_iters=iters, ctx=ctx_e)
        run = functools.partial(repro.tucker_hooi, x, ranks, n_iters=iters,
                                ctx=ctx)
        _, rec["first_call_s"] = timed(lambda: run().core)
        res, rec["run_s"] = timed(run)
        checks.append(check("fit_vs_einsum",
                            abs(res.final_fit - ref.final_fit),
                            TOL_FIT, TOL_FIT_WHY))
        for k in keeps:
            out = repro.multi_ttm(x, res.factors, k, ctx=ctx)
            checks.append(check(
                f"multi_ttm_keep{'_core' if k is None else k}_rel_err",
                rel_err(out, multi_ttm_ref(x, res.factors, k)),
                TOL_KERNEL, TOL_KERNEL_WHY,
            ))
    rec["pallas_dispatches"] = pallas_dispatches() - d0
    rec["fits"] = {"pallas": res.final_fit, "einsum": ref.final_fit}
    rec["checks"] = checks
    return finish(rec)


def phase_serve(key, shape, rank, n_requests, iters, ctx_kw):
    import repro
    from repro.core.tensor import random_factors
    from repro.launch.serve import DecompositionServer, bucket_shape

    ctx = repro.ExecutionContext.create(backend="auto", **ctx_kw)
    rec = {"phase": "serve", "requests": n_requests, "rank": rank,
           "iters": iters}
    shapes = [tuple(s - (i + k) % 8 for k, s in enumerate(shape))
              for i in range(n_requests)]
    padded = bucket_shape(shapes[0])
    rec["bucket_shape"] = list(padded)
    xb, fb = sds((n_requests,) + padded), [
        sds((n_requests, s, rank)) for s in padded]
    compiled = [aot(functools.partial(
        lambda x, f, m: repro.mttkrp(x, f, m, ctx=ctx), m=m), xb, fb)
        for m in range(len(padded))]
    rec["compile_s"] = sum(c[0] for c in compiled)
    rec["tpu_custom_call"] = all(c[1] for c in compiled)

    xs = [low_rank_plus_noise(jax.random.fold_in(key, i), s, rank)
          for i, s in enumerate(shapes)]
    d0 = pallas_dispatches()
    checks = []
    with jax.default_matmul_precision(HIGHEST):
        def serve():
            server = DecompositionServer(ctx, n_iters=iters, tol=0.0)
            for i, x in enumerate(xs):
                server.submit(x, rank, request_id=f"req{i}")
            return server.flush()

        _, rec["first_call_s"] = timed(lambda: [
            r.weights for r in serve().values()])
        served, rec["run_s"] = timed(serve)
        diffs = []
        for i, x in enumerate(xs):
            # a fresh server seeds request i with PRNGKey(i + 1)
            init = random_factors(jax.random.PRNGKey(i + 1), x.shape, rank,
                                  x.dtype)
            direct = repro.cp_als(x, rank, n_iters=iters, init_factors=init,
                                  ctx=ctx)
            diffs.append(abs(served[f"req{i}"].fit - direct.final_fit))
        checks.append(check("max_fit_diff_vs_direct_cp_als", max(diffs),
                            TOL_FIT, "same init; the bucket pads with zeros, "
                            "which changes blocking and reduction order"))
        # the bucket's batched MTTKRP (one launch for all requests)
        stack = jnp.stack([jnp.pad(x, [(0, p - s) for s, p in
                                       zip(x.shape, padded)]) for x in xs])
        facs = [jnp.stack([
            jnp.pad(served[f"req{i}"].factors[k],
                    ((0, padded[k] - shapes[i][k]), (0, 0)))
            for i in range(n_requests)]) for k in range(len(padded))]
        out = repro.mttkrp(stack, facs, 0, ctx=ctx)
        ref = jnp.stack([mttkrp_ref(stack[b], [f[b] for f in facs], 0)
                         for b in range(n_requests)])
        checks.append(check("batched_mttkrp0_rel_err", rel_err(out, ref),
                            TOL_KERNEL, TOL_KERNEL_WHY))
    rec["fits"] = [served[f"req{i}"].fit for i in range(n_requests)]
    rec["pallas_dispatches"] = pallas_dispatches() - d0
    rec["checks"] = checks
    return finish(rec)


def sharded_tensor(gen, key, mesh, *args):
    """Generate X directly in its block distribution (no device ever holds
    all of it) and check that each device holds exactly its block."""
    from jax.sharding import NamedSharding
    from repro.distributed import tensor_spec

    shape = args[0]
    out = NamedSharding(mesh, tensor_spec(len(shape)))
    x = jax.jit(gen.__wrapped__, static_argnums=tuple(range(1, len(args) + 1)),
                out_shardings=out)(key, *args)
    grid = [mesh.shape[a] for a in mesh.axis_names]
    want = [s // g for s, g in zip(shape, grid)]
    shards = [{"device": sh.device.id, "shape": list(sh.data.shape),
               "index": [[i.start or 0, i.stop] for i in sh.index]}
              for sh in x.addressable_shards]
    blocks = (len({sh["device"] for sh in shards}) == math.prod(grid)
              and all(sh["shape"] == want for sh in shards))
    return x, shards, check("x_shards_not_blocks", int(not blocks), 0,
                            "each device holds only its block of X")


def compiled_has_kernel(sweep, mesh, x, state):
    """Compile one distributed sweep and look for the Mosaic kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.tensor import frob_norm

    normx = jax.device_put(frob_norm(x), NamedSharding(mesh, P()))
    return "tpu_custom_call" in sweep.lower(*state, normx).compile().as_text()


def phase_cp_dist(key, shape, rank, grid, iters, ctx_kw):
    import repro
    from repro.core.tensor import random_factors
    from repro.distributed import build_cp_sweep, make_grid_mesh, place_cp_state

    rec = {"phase": "cp_dist", "shape": list(shape), "rank": rank,
           "grid": list(grid), "iters": iters}
    mesh = make_grid_mesh(grid, dims=shape)
    x, rec["shards"], placed = sharded_tensor(
        low_rank_plus_noise, key, mesh, tuple(shape), rank)
    init = random_factors(jax.random.fold_in(key, 1), shape, rank, x.dtype)
    ctx = repro.ExecutionContext.create(backend="pallas", mesh=mesh, **ctx_kw)
    ctx_seq = repro.ExecutionContext.create(backend="pallas", **ctx_kw)
    x1 = jax.device_put(x, jax.devices()[0])
    d0 = pallas_dispatches()
    with jax.default_matmul_precision(HIGHEST):
        run = functools.partial(repro.cp_als, x, rank, n_iters=iters,
                                init_factors=init, ctx=ctx)
        _, rec["first_call_s"] = timed(lambda: run().weights)
        par, rec["run_s"] = timed(run)
        seq = repro.cp_als(x1, rank, n_iters=iters, init_factors=init,
                           ctx=ctx_seq)
    gaps = [rel_err(p * par.weights, s * seq.weights)
            for p, s in zip(par.factors, seq.factors)]
    rec["checks"] = [
        placed,
        check("fit_vs_sequential", abs(par.final_fit - seq.final_fit),
              TOL_FIT, TOL_FIT_WHY + "; collectives reorder the sums"),
        check("max_factor_rel_err_vs_sequential", max(gaps), 1e-3,
              "ALS amplifies fp32 reordering over sweeps; the same bound "
              "as tests/dist_worker.py"),
    ]
    rec["fits"] = {"parallel": par.final_fit, "sequential": seq.final_fit}
    rec["pallas_dispatches"] = pallas_dispatches() - d0
    rec["tpu_custom_call"] = compiled_has_kernel(
        build_cp_sweep(mesh, x.ndim, ctx=ctx), mesh, x,
        place_cp_state(mesh, x, init))
    return finish(rec)


def phase_tucker_dist(key, shape, ranks, grid, iters, ctx_kw):
    import repro
    from repro.distributed import (
        build_tucker_sweep, make_grid_mesh, place_tucker_state,
    )

    rec = {"phase": "tucker_dist", "shape": list(shape),
           "ranks": list(ranks), "grid": list(grid), "iters": iters}
    mesh = make_grid_mesh(grid, dims=shape)
    x, rec["shards"], placed = sharded_tensor(
        tucker_plus_noise, key, mesh, tuple(shape), tuple(ranks))
    ctx = repro.ExecutionContext.create(backend="pallas", mesh=mesh, **ctx_kw)
    ctx_seq = repro.ExecutionContext.create(backend="pallas", **ctx_kw)
    x1 = jax.device_put(x, jax.devices()[0])
    d0 = pallas_dispatches()
    with jax.default_matmul_precision(HIGHEST):
        run = functools.partial(repro.tucker_hooi, x, ranks, n_iters=iters,
                                ctx=ctx)
        _, rec["first_call_s"] = timed(lambda: run().core)
        par, rec["run_s"] = timed(run)
        seq = repro.tucker_hooi(x1, ranks, n_iters=iters, ctx=ctx_seq)
    # orthonormal factors compared as subspaces: A A^T, not A itself
    gap = max(float(jnp.max(jnp.abs(p @ p.T - s @ s.T)))
              for p, s in zip(par.factors, seq.factors))
    rec["checks"] = [
        placed,
        check("fit_vs_sequential", abs(par.final_fit - seq.final_fit),
              TOL_FIT, TOL_FIT_WHY + "; collectives reorder the sums"),
        check("max_subspace_gap_vs_sequential", gap, 1e-3,
              "largest entry of A A^T - B B^T; the same bound as "
              "tests/dist_worker.py"),
    ]
    rec["fits"] = {"parallel": par.final_fit, "sequential": seq.final_fit}
    rec["pallas_dispatches"] = pallas_dispatches() - d0
    rec["tpu_custom_call"] = compiled_has_kernel(
        build_tucker_sweep(mesh, x.ndim, tuple(ranks), ctx=ctx), mesh, x,
        place_tucker_state(mesh, x, seq.factors))
    return finish(rec)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_phase(name, fn):
    try:
        rec = fn()
    except Exception as e:  # a failed phase fails the run, with its cause
        rec = {"phase": name, "ok": False, "error": f"{type(e).__name__}: "
               f"{e}"[:2000], "traceback": traceback.format_exc()[-4000:]}
    print(json.dumps(rec), flush=True)
    return rec["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    try:
        import repro
        from repro.engine.context import CHECKOUT_COMPILATION_CACHE
    except ImportError as e:
        print(f"chip_smoke: run it from the repository: {e}",
              file=sys.stderr)
        return 2

    ctx_kw = {"interpret": False,
              "compilation_cache": CHECKOUT_COMPILATION_CACHE}
    repro.ExecutionContext.create(**ctx_kw).ensure_compilation_cache()
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        grid = (2, 2, 1)
        phases = [
            ("cp_dist", lambda: phase_cp_dist(
                jax.random.fold_in(key, 5), (512, 512, 512), 32, grid, 4,
                ctx_kw)),
            ("tucker_dist", lambda: phase_tucker_dist(
                jax.random.fold_in(key, 6), (512, 512, 512), (32, 32, 32),
                grid, 3, ctx_kw)),
        ]
    else:
        phases = [
            ("cp3", lambda: phase_cp(
                "cp3", jax.random.fold_in(key, 1), (1024, 1024, 1024), 64,
                ("per_mode",), 3, ctx_kw)),
            ("cp4", lambda: phase_cp(
                "cp4", jax.random.fold_in(key, 2), (256, 256, 256, 32), 32,
                ("fused", "per_mode"), 3, ctx_kw, shrink_mode=2)),
            ("tucker", lambda: phase_tucker(
                jax.random.fold_in(key, 3), (1024, 1024, 1024), (32, 32, 32),
                3, ctx_kw)),
            ("serve", lambda: phase_serve(
                jax.random.fold_in(key, 4), (256, 256, 256), 16, 8, 5,
                ctx_kw)),
        ]
    ok = [run_phase(name, fn) for name, fn in phases]
    if not all(ok):
        print(f"chip_smoke: {ok.count(False)} phase(s) failed",
              file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
