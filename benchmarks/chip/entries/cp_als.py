"""CP-ALS through ``repro.cp_als``: a rank-``rank`` CP tensor plus noise,
initial factors drawn from a key, ``sweeps_per_solve`` sweeps with
``tol=0``, and the MTTKRPs each sweep asks for."""

from __future__ import annotations

from benchlib import data
from benchlib.driver import block, call


def make_tensor(key, shape, cfg):
    d = cfg["data"]
    return data.low_rank_plus_noise(key, shape, int(d["rank"]),
                                    float(d["noise"]))


def init(key, shape, cfg):
    return data.init_factors(key, tuple(shape), int(cfg["rank"]))


def solve(x, init, cfg, ctx) -> dict:
    import repro

    r = repro.cp_als(x, int(cfg["rank"]), n_iters=int(cfg["sweeps_per_solve"]),
                     init_factors=init, tol=0.0, ctx=ctx, sweep=cfg["sweep"])
    block((r.factors, r.weights))
    return {"factors": r.factors, "weights": r.weights, "fits": list(r.fits),
            "sweep_fits": True}


def work(shape, cfg) -> list[dict]:
    n = int(cfg["sweeps_per_solve"])
    return [call("mttkrp", shape, n, rank=int(cfg["rank"]), mode=m,
                 itemsize=4) for m in range(len(shape))]
