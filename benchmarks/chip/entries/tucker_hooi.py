"""Tucker by HOOI through ``repro.tucker_hooi``: a field with a decaying
multilinear spectrum, the program's own HOSVD start, ``sweeps_per_solve``
sweeps with ``tol=0``, and the Multi-TTMs each sweep asks for."""

from __future__ import annotations

from benchlib import data
from benchlib.driver import block, call


def make_tensor(key, shape, cfg):
    d = cfg["data"]
    return data.tucker_field(key, tuple(shape), tuple(d["core"]),
                             tuple(cfg["ranks"]), float(d["scale_at_rank"]),
                             float(d["noise"]))


def init(key, shape, cfg):
    return None


def solve(x, init, cfg, ctx) -> dict:
    import repro

    r = repro.tucker_hooi(x, tuple(cfg["ranks"]),
                          n_iters=int(cfg["sweeps_per_solve"]), tol=0.0,
                          ctx=ctx)
    block((r.core, r.factors))
    return {"factors": r.factors, "core": r.core, "fits": list(r.fits)}


def work(shape, cfg) -> list[dict]:
    n = int(cfg["sweeps_per_solve"])
    return [call("multi_ttm", shape, n, ranks=list(cfg["ranks"]), keep=k,
                 itemsize=4) for k in range(len(shape))]
