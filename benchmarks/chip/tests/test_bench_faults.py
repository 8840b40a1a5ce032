"""A run whose timed path is broken underneath reads ``correct: false``:
once for each fault a cell can have (a solve that returns its state
unchanged, an answer altered where it is produced, half of a served batch
left out, sweeps left out, mode 0 updated from a stale MTTKRP).  The look
for a chip is skipped; the rest of the run is the benchmark's own."""

import jax.numpy as jnp
import pytest

import repro
import repro.engine.batch as batch_mod
from _bench_helpers import CHIP, make_root, run
from benchlib import faults, spec


def _cp_unchanged(orig):
    def fake(x, rank, n_iters=20, init_factors=None, **kw):
        real = orig(x, rank, n_iters=n_iters, init_factors=init_factors, **kw)
        return repro.CPResult([jnp.asarray(f) for f in init_factors],
                              jnp.ones((rank,), x.dtype), real.fits)
    return fake


def _cp_altered(orig):
    def fake(*a, **kw):
        r = orig(*a, **kw)
        r.factors[0] = r.factors[0].at[0, 0].multiply(-1.0)
        return r
    return fake


def _tucker_unchanged(orig):
    def fake(x, ranks, n_iters=10, **kw):
        real = orig(x, ranks, n_iters=n_iters, **kw)
        start = orig(x, ranks, n_iters=0, **kw)   # the HOSVD it starts from
        return repro.TuckerResult(start.core, start.factors, real.fits)
    return fake


def _tucker_altered(orig):
    def fake(*a, **kw):
        r = orig(*a, **kw)
        r.factors[0] = r.factors[0].at[0, 0].multiply(-1.0)
        return r
    return fake


def _cp_one_sweep(orig):
    def fake(x, rank, n_iters=20, **kw):
        return orig(x, rank, n_iters=1, **kw)
    return fake


def _cp_stale_mode0(orig):
    """The reference with mode 0 one sweep stale, in the program's place."""
    ref = spec.load_module(CHIP, "reference", "cp_als")

    def fake(x, rank, n_iters=20, init_factors=None, **kw):
        r = faults.stale_mode0(ref, x, {"init": init_factors},
                               {"sweeps_per_solve": n_iters})
        return repro.CPResult(r["factors"], r["weights"], list(r["fits"]))
    return fake


def _tucker_one_sweep(orig):
    def fake(x, ranks, n_iters=10, **kw):
        return orig(x, ranks, n_iters=1, **kw)
    return fake


def _batch_one_sweep(orig):
    def fake(x, rank, n_iters=20, **kw):
        return orig(x, rank, n_iters=1, **kw)
    return fake


def _batch_half(orig):
    def fake(x, rank, n_iters=20, key=None, init_factors=None, **kw):
        res = orig(x, rank, n_iters=n_iters, init_factors=init_factors, **kw)
        half = x.shape[0] // 2
        # the second half of the batch is left out: it keeps its inits
        res.factors = [f.at[half:].set(i[half:])
                       for f, i in zip(res.factors, init_factors)]
        return res
    return fake


def _batch_altered(orig):
    def fake(*a, **kw):
        res = orig(*a, **kw)
        res.factors[0] = res.factors[0].at[0, 0, 0].multiply(-1.0)
        return res
    return fake


TUCKER = "tucker4-hcci-f32.repeat"
FAULTS = [
    ("cp3-f32.cube1024", repro, "cp_als", _cp_unchanged),
    ("cp3-f32.cube1024", repro, "cp_als", _cp_altered),
    ("cp3-f32.cube1024", repro, "cp_als", _cp_one_sweep),
    ("cp3-f32.cube1024", repro, "cp_als", _cp_stale_mode0),
    (TUCKER, repro, "tucker_hooi", _tucker_unchanged),
    (TUCKER, repro, "tucker_hooi", _tucker_altered),
    (TUCKER, repro, "tucker_hooi", _tucker_one_sweep),
    ("cp3-f32.serve256x8", batch_mod, "cp_als_batched", _batch_half),
    ("cp3-f32.serve256x8", batch_mod, "cp_als_batched", _batch_altered),
    ("cp3-f32.serve256x8", batch_mod, "cp_als_batched", _batch_one_sweep),
]


#: the numbers that catch a fault the others cannot see
CAUGHT_BY = {
    "_cp_one_sweep": ("sweeps_gap",),
    "_cp_stale_mode0": ("fit_gap", "model_gap"),
    "_tucker_one_sweep": ("sweeps_gap",),
    "_batch_one_sweep": ("fit_gap", "model_gap"),
}


@pytest.mark.parametrize(
    "workload,module,name,fault", FAULTS,
    ids=[f"{w}-{f.__name__.lstrip('_')}" for w, _, _, f in FAULTS])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, workload,
                                          module, name, fault):
    monkeypatch.setattr(module, name, fault(getattr(module, name)))
    r = run(make_root(tmp_path), workload)
    assert r["attempted"] > 0
    assert not r["correct"], r["checks"]
    names = CAUGHT_BY.get(fault.__name__)
    if names:
        bad = [n for n in names if r["checks"][n]["value"] is None
               or r["checks"][n]["value"] > r["checks"][n]["limit"]]
        assert bad, r["checks"]
