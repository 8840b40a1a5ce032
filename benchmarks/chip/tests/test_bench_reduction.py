"""The metrics' readers on a hand-made trace and run whose answers are
known, the interval arithmetic they share, and the breakdown."""

import pytest

from _bench_helpers import CHIP
from benchlib import harness, spec, xtrace

MS = 1_000_000  # ns


def _trace():
    # window 0..100 ms on two chips
    k, c, o = "kernel", "collective", "other"
    return {
        "devices": {
            "/device:TPU:0": [["krn", 10 * MS, 40 * MS, k],
                              ["fusion.1", 40 * MS, 50 * MS, o],
                              ["all-gather.1", 50 * MS, 60 * MS, c],
                              ["copy.2", 55 * MS, 58 * MS, o],
                              ["krn", 90 * MS, 120 * MS, k]],
            "/device:TPU:1": [["krn", 0, 20 * MS, k],
                              ["reduce-scatter.3", 30 * MS, 50 * MS, c]],
        },
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.solve", 0, 100 * MS],
                 ["repro.mttkrp.mode1", 60 * MS, 90 * MS]],
    }


def _run(**kw):
    base = dict(setup_s=12.5, window_s=20.0, units=4, peak_bytes=3 * 2**29,
                work=[], compiles=2, extra={}, trace=_trace(),
                peaks=spec.peaks(CHIP, "TPU v5 lite"))
    base.update(kw)
    return harness.RunRecord(**base)


def _read(name, run):
    return spec.load_module(CHIP, "metrics", name).read(run)


def test_interval_arithmetic():
    assert xtrace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert xtrace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xtrace.subtract([(0, 10), (20, 30)], [(5, 25)]) == \
        [(0, 5), (25, 30)]
    assert xtrace.clip([(0, 5), (8, 20)], 2, 10) == [(2, 5), (8, 10)]


def test_idle_share():
    # chip 0 busy 10..60 and 90..100 = 60 ms; chip 1 busy 0..20, 30..50
    assert _read("device_idle_share", _run()) == pytest.approx(
        (40 + 60) / 2)


def test_non_kernel_busy_share():
    # chip 0: 40..50 and 50..60 outside kernels = 20 ms; chip 1: 30..50
    assert _read("non_kernel_busy_share", _run()) == pytest.approx(20.0)


def test_end_to_end_readers():
    run = _run()
    assert _read("setup_s", run) == 12.5
    assert _read("solve_s", run) == 5.0
    assert _read("peak_hbm_gib", run) == 1.5


def test_roofline_shares():
    peaks = {"f32_highest_flops": 1e12, "hbm_bytes_per_s": 1e9}
    # kernel time in the window: 30 + 10 + 20 = 60 ms
    mt = spec.load_module(CHIP, "metrics", "mttkrp_roofline_share")
    call = {"kind": "mttkrp", "shape": [64, 64, 64], "rank": 8, "mode": 0,
            "count": 3, "itemsize": 4}
    want = 100 * 3 * mt.least_time(call, peaks) / 0.060
    got = _read("mttkrp_roofline_share", _run(work=[call], peaks=peaks))
    assert got == pytest.approx(want)
    assert _read("multi_ttm_roofline_share",
                 _run(work=[call], peaks=peaks)) is None
    tt = spec.load_module(CHIP, "metrics", "multi_ttm_roofline_share")
    tcall = {"kind": "multi_ttm", "shape": [64, 64, 64], "ranks": [4, 4, 4],
             "keep": 1, "count": 2, "itemsize": 4}
    assert _read("multi_ttm_roofline_share",
                 _run(work=[tcall], peaks=peaks)) == pytest.approx(
        100 * 2 * tt.least_time(tcall, peaks) / 0.060)


def test_nothing_to_read_gives_nothing():
    empty = {"devices": {}, "host": [["bench.window", 0, MS]]}
    for name in ("device_idle_share", "non_kernel_busy_share",
                 "mttkrp_roofline_share", "multi_ttm_roofline_share"):
        assert _read(name, _run(trace=empty)) is None
    assert _read("compiles_per_solve", _run()) == 0.5
    assert _read("compiles_per_solve", _run(units=0)) is None


def test_breakdown_names_ops_and_idle_spans():
    b = harness.breakdown(_trace())
    ops = dict(b["device_ops"])
    assert ops["krn [kernel]"] == pytest.approx((30 + 10 + 20) * 1e-3 / 2)
    gaps = dict(b["idle_gaps"])
    # chip 0 idle 0..10 (under the solve) and 60..90 (under the program's
    # span); chip 1 idle 20..30 and 50..100, of which 60..90 under the
    # program's span
    assert gaps["repro.mttkrp.mode1"] == pytest.approx((30 + 30) * 1e-3 / 2)
    assert gaps["bench.solve"] == pytest.approx((10 + 10 + 20) * 1e-3 / 2)
    assert sum(gaps.values()) == pytest.approx((40 + 60) * 1e-3 / 2)


def test_innermost_label():
    spans = [["bench.solve", 0, 100], ["a", 10, 50], ["b", 20, 30]]
    assert xtrace.innermost(spans, 0, 100) == [
        (0, 10, "bench.solve"), (10, 20, "a"), (20, 30, "b"),
        (30, 50, "a"), (50, 100, "bench.solve")]
    assert xtrace.label_time([(5, 25), (60, 70)],
                             xtrace.innermost(spans, 0, 100)) == {
        "bench.solve": 15, "a": 10, "b": 5}


FIXTURES = ["cp3-f32.cube1024.solve", "tucker3-f32.cube1024.solve",
            "cp3-f32.serve256x8.round"]
METRICS = ["device_idle_share", "non_kernel_busy_share",
           "mttkrp_roofline_share", "multi_ttm_roofline_share",
           "compiles_per_solve"]


@pytest.mark.parametrize("name", FIXTURES)
def test_readers_on_a_trace_recorded_on_the_chip(name):
    """One unit of a traced chip run, trimmed (touching non-kernel ops
    merged, which keeps every union): each reader gives the reading
    recorded with it, and the readings hang together."""
    import json

    data = CHIP / "tests" / "data"
    rec = json.loads((data / f"{name}.json").read_text())
    tr = xtrace.load(str(data / f"{name}.trace.json.gz"))
    run = _run(trace=tr, work=rec["work"], units=rec["units"],
               compiles=rec["compiles"])
    got = {m: _read(m, run) for m in METRICS}
    for m in METRICS:
        want = rec["expected"][m]
        assert (got[m] is None) == (want is None), m
        if want is not None:
            assert got[m] == pytest.approx(want, rel=1e-9), m
    (dev,) = tr["devices"]
    lo, hi = xtrace.window(tr)
    kernel = xtrace.length(xtrace.union(xtrace.ops(tr, dev, ("kernel",))))
    assert kernel > 0
    busy = 100 - got["device_idle_share"]
    assert busy == pytest.approx(got["non_kernel_busy_share"]
                                 + 100 * kernel / (hi - lo))
    roof = got["mttkrp_roofline_share"] or got["multi_ttm_roofline_share"]
    assert 0 < roof <= 100
    top = [k for k, _ in harness.breakdown(tr)["device_ops"]]
    assert any(k.startswith("tpu_custom_call") and k.endswith("[kernel]")
               for k in top)
