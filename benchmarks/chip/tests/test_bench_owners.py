"""The per-layer idle shares: device-idle time owned by the innermost
program span of each layer, on a hand-made trace, on traces recorded on
the chip, and against the spans the program opens."""

import json

import pytest

from _bench_helpers import CHIP
from benchlib import harness, owners, spec, xtrace

MS = 1_000_000  # ns
SHARES = {"driver_idle_share": owners.DRIVERS,
          "engine_idle_share": owners.ENGINE,
          "kernel_launch_idle_share": owners.KERNELS,
          "serve_idle_share": owners.SERVING}


def _trace():
    # window 0..100 ms, one chip busy 30..40 (a kernel) and 60..70
    return {
        "devices": {"/device:TPU:0": [["mttkrp3 f32[8,4]", 30 * MS, 40 * MS,
                                       "kernel"],
                                      ["fusion", 60 * MS, 70 * MS, "other"]]},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.round", 0, 100 * MS],
                 ["repro.serve.flush", 2 * MS, 98 * MS],
                 ["repro.serve.pack", 2 * MS, 8 * MS],
                 ["repro.cp_als_batched", 8 * MS, 90 * MS],
                 ["repro.cp_als_batched.sweep", 9 * MS, 90 * MS],
                 ["repro.mttkrp.batched.mode0", 10 * MS, 45 * MS],
                 ["repro.engine.resolve", 11 * MS, 13 * MS],
                 ["repro.engine.relayout", 13 * MS, 16 * MS],
                 ["repro.kernel.mttkrp3", 16 * MS, 29 * MS],
                 ["repro.cp_als_batched.update", 45 * MS, 55 * MS],
                 ["repro.serve.unpack", 92 * MS, 97 * MS]],
    }


def _run(trace):
    return harness.RunRecord(setup_s=1.0, window_s=0.1, units=1,
                             peak_bytes=0, work=[], compiles=0, extra={},
                             trace=trace,
                             peaks=spec.peaks(CHIP, "TPU v5 lite"))


def _read(name, run):
    return spec.load_module(CHIP, "metrics", name).read(run)


def test_idle_is_owned_by_the_innermost_span():
    run = _run(_trace())
    # idle: 0..30, 40..60, 70..100 (80 ms)
    # serving: 2..8 pack, 90..92 and 97..98 flush, 92..97 unpack = 14 ms
    assert _read("serve_idle_share", run) == pytest.approx(14.0)
    # drivers: 8..9 call, 9..10, 55..60 and 70..90 sweep, 45..55 update
    # = 37 ms
    assert _read("driver_idle_share", run) == pytest.approx(37.0)
    # engine: 10..11 and 29..30 and 40..45 the mode span, 11..16 = 12 ms
    assert _read("engine_idle_share", run) == pytest.approx(12.0)
    assert _read("kernel_launch_idle_share", run) == pytest.approx(13.0)
    dev = "/device:TPU:0"
    # 0..2 and 98..100 under bench.round alone
    assert owners.unowned(run.trace, dev) == 4 * MS
    assert sum(_read(m, run) for m in SHARES) + 4.0 == pytest.approx(
        _read("device_idle_share", run))


def test_nothing_to_read_gives_nothing():
    """A program that opens no span of a layer (the parent of this
    metric), or a trace with no device, reads nothing."""
    bench_only = _trace()
    bench_only["host"] = bench_only["host"][:2]
    no_device = dict(_trace(), devices={})
    for m in SHARES:
        assert _read(m, _run(bench_only)) is None
        assert _read(m, _run(no_device)) is None
        assert _read(m, _run(None)) is None


@pytest.mark.parametrize("name", ["cp3-f32.cube1024.solve",
                                  "cp3-f32.serve256x8.round",
                                  "tucker3-f32.cube1024.solve"])
def test_traces_without_program_spans_read_nothing(name):
    tr = xtrace.load(str(CHIP / "tests" / "data" / f"{name}.trace.json.gz"))
    for m in SHARES:
        assert _read(m, _run(tr)) is None


OWNED = ["cp3-f32.cube1024.owners", "cp3-f32.serve256x8.owners",
         "tucker4-hcci-f32.repeat.owners"]


@pytest.mark.parametrize("name", OWNED)
def test_shares_on_a_trace_recorded_on_the_chip(name):
    """One unit of a traced chip run with the program's spans, trimmed
    (touching non-kernel ops merged, which keeps every union): each
    share gives the reading recorded with it, the four shares and the
    unowned idle add up to the device's idle share, and the breakdown
    names the program's spans."""
    data = CHIP / "tests" / "data"
    rec = json.loads((data / f"{name}.json").read_text())
    tr = xtrace.load(str(data / f"{name}.trace.json.gz"))
    run = _run(tr)
    got = {m: _read(m, run) for m in [*SHARES, "device_idle_share"]}
    for m, v in got.items():
        want = rec["expected"][m]
        assert (v is None) == (want is None), m
        if want is not None:
            assert v == pytest.approx(want, rel=1e-9), m
    for m in ("driver_idle_share", "engine_idle_share",
              "kernel_launch_idle_share"):
        assert got[m] is not None, m
    assert (got["serve_idle_share"] is not None) == ("serve" in name)
    (dev,) = tr["devices"]
    lo, hi = xtrace.window(tr)
    unowned = 100.0 * owners.unowned(tr, dev) / (hi - lo)
    assert sum(v or 0.0 for m, v in got.items() if m in SHARES) + unowned \
        == pytest.approx(got["device_idle_share"], abs=0.1)
    gaps = [k for k, _ in harness.breakdown(tr)["idle_gaps"]]
    assert any(k.startswith("repro.kernel.") for k in gaps)
    assert any(k.startswith("repro.engine.") for k in gaps)


def test_every_program_span_belongs_to_a_layer(monkeypatch):
    """The spans the program opens on each cell's path, at a tiny size
    on the CPU, all fall under one layer's prefixes, so the four shares
    and the unowned idle cover the device's idle time."""
    import contextlib

    import jax
    import jax.numpy as jnp

    import repro
    from repro.launch.serve import DecompositionServer

    names = []

    def recorder(name, **kw):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", recorder)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 5))
    with repro.Trace():
        for backend in ("auto", "pallas"):
            ctx = repro.ExecutionContext.create(backend=backend,
                                                interpret=True)
            repro.cp_als(x, 3, n_iters=1, ctx=ctx)
            repro.tucker_hooi(x, (2, 2, 2), n_iters=1, ctx=ctx)
            server = DecompositionServer(ctx, n_iters=1, tol=0.0)
            server.submit(x, 3)
            server.submit(x[:7], 3)
            server.flush()
        repro.cp_als(x, 3, n_iters=1, sweep="fused",
                     ctx=repro.ExecutionContext.create(backend="pallas",
                                                       interpret=True))
        repro.cp_als(jnp.stack([x[:5, :5, :5]] * 2)[0], 3, n_iters=1,
                     sweep="dimtree",
                     ctx=repro.ExecutionContext.create(backend="pallas",
                                                       interpret=True))
    layers = (owners.DRIVERS + owners.ENGINE + owners.KERNELS
              + owners.SERVING)
    assert names
    stray = sorted({n for n in names if not n.startswith(layers)})
    assert stray == []
    for prefixes in (owners.DRIVERS, owners.ENGINE, owners.KERNELS,
                     owners.SERVING):
        assert any(n.startswith(prefixes) for n in names), prefixes
