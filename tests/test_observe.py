"""Observability layer: span schema round-trips, registry counters,
bounds-audit triples, the zero-overhead observe=False contract, and the
report CLI (ISSUE PR 7 acceptance)."""

import json

import jax
import jax.numpy as jnp
import pytest

import repro
from repro import ExecutionContext, Memory
from repro.observe import (
    SPAN_SCHEMA,
    Trace,
    audit_mttkrp,
    audit_multi_ttm,
    current_trace,
    load_trace,
    registry,
    summarize_events,
)
from repro.observe.metrics import (
    PALLAS_DISPATCHES,
    TUNE_CACHE_HITS,
    TUNE_CACHE_MISSES,
    MetricsRegistry,
)
from repro.observe.trace import BASE_FIELDS, should_record

DIMS, RANK = (8, 6, 5), 3  # the pinned 3-way problem


def _problem(dims=DIMS, rank=RANK, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), dims)
    fs = [
        jax.random.normal(jax.random.PRNGKey(seed + k + 1), (d, rank))
        for k, d in enumerate(dims)
    ]
    return x, fs


# ---------------------------------------------------------------------------
# Trace: recording, ring buffer, schema round-trip
# ---------------------------------------------------------------------------

def test_nothing_recorded_without_active_trace():
    x, fs = _problem()
    assert current_trace() is None
    # engine calls outside a Trace must not record anywhere
    repro.mttkrp(x, fs, 0, ctx=ExecutionContext.create(observe=True))
    assert current_trace() is None


def test_span_schema_and_jsonl_roundtrip(tmp_path):
    x, fs = _problem()
    ctx = ExecutionContext.create(observe=True)
    p = tmp_path / "trace.jsonl"
    with Trace(path=str(p)) as tr:
        repro.mttkrp(x, fs, 1, ctx=ctx)
        assert current_trace() is tr
    events = tr.events
    assert len(events) == 1
    e = events[0]
    for field in BASE_FIELDS:
        assert field in e
    assert e["schema"] == SPAN_SCHEMA
    assert e["kind"] == "mttkrp"
    assert e["shape"] == list(DIMS) and e["rank"] == RANK and e["mode"] == 1
    assert e["backend"] in ("einsum", "blocked_host", "pallas")
    assert e["modeled_words"] > 0
    assert e["lower_bound_words"] >= 0
    # the dispatch interval, on the profiler's clock (ns since the epoch)
    assert e["end_ns"] > e["start_ns"] > 1.5e18
    assert "compute_dtype" in e and "out_dtype" in e
    # the JSONL round-trip is exact (events are pure JSON)
    back = load_trace(str(p))
    assert back == events


def test_trace_ring_buffer_evicts_and_counts():
    before = registry().counter("trace.events_dropped")
    with Trace(capacity=2) as tr:
        for i in range(5):
            tr.record("synthetic", i=i)
    assert len(tr) == 2
    assert [e["i"] for e in tr.events] == [3, 4]  # oldest evicted
    assert registry().counter("trace.events_dropped") == before + 3


def test_trace_validates_arguments():
    with pytest.raises(ValueError, match="capture"):
        Trace(capture="everything")
    with pytest.raises(ValueError, match="capacity"):
        Trace(capacity=0)


# ---------------------------------------------------------------------------
# Capture gating: observe=False / capture="observed" emit nothing
# ---------------------------------------------------------------------------

def test_capture_observed_requires_ctx_opt_in():
    x, fs = _problem()
    with Trace(capture="observed") as tr:
        repro.mttkrp(x, fs, 0, ctx=ExecutionContext.create(observe=False))
        assert len(tr) == 0  # not opted in: nothing recorded
        repro.mttkrp(x, fs, 0, ctx=ExecutionContext.create(observe=True))
        assert len(tr) == 1


def test_should_record_rejects_tracers():
    x, _ = _problem()

    recorded = []

    def probe(xx):
        recorded.append(should_record(True, xx))
        return xx * 2

    with Trace():
        jax.jit(probe)(x)  # traced: operands are tracers
        probe(x)           # eager: concrete
    assert recorded == [False, True]


def test_observe_flag_does_not_change_hlo():
    """The zero-overhead contract: compiled HLO is byte-identical with
    observe on or off (recording is driver-side only)."""
    x, fs = _problem()

    def lower_text(observe):
        ctx = ExecutionContext.create(observe=observe)

        def call(xx, *ffs):
            return repro.mttkrp(xx, list(ffs), 0, ctx=ctx)

        return jax.jit(call).lower(x, *fs).as_text()

    with Trace() as tr:
        on = lower_text(True)
        off = lower_text(False)
        assert len(tr) == 0  # nothing recorded while tracing either
    assert on == off


# ---------------------------------------------------------------------------
# Profiler spans: one helper, never under staging, never syncing
# ---------------------------------------------------------------------------

@pytest.fixture
def span_names(monkeypatch):
    """The names of the profiler spans the program opens, in order (the
    annotations are replaced by recorders; nothing else changes)."""
    import contextlib

    names: list[str] = []

    def recorder(name, **kw):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", recorder)
    return names


def _pallas_ctx():
    return ExecutionContext.create(backend="pallas", interpret=True)


def test_spans_cover_drivers_engine_kernels_and_serving(span_names):
    """Eagerly, every layer opens its spans; without a Trace (or with
    annotate=False) none opens."""
    from repro.engine.batch import cp_als_batched
    from repro.launch.serve import DecompositionServer

    x, fs = _problem()
    repro.cp_als(x, RANK, n_iters=1, init_factors=fs, ctx=_pallas_ctx())
    with Trace(annotate=False):
        repro.cp_als(x, RANK, n_iters=1, init_factors=fs, ctx=_pallas_ctx())
    assert span_names == []
    with Trace():
        repro.cp_als(x, RANK, n_iters=2, init_factors=fs, ctx=_pallas_ctx())
        cp_als_batched(jnp.stack([x, x]), RANK, n_iters=1, ctx=_pallas_ctx())
        repro.tucker_hooi(x, (2, 2, 2), n_iters=1, ctx=_pallas_ctx())
        server = DecompositionServer(_pallas_ctx(), n_iters=1, tol=0.0)
        server.submit(x, RANK)
        server.flush()
    got = set(span_names)
    for want in (
        "repro.cp_als", "repro.cp_als.sweep", "repro.cp_als.update",
        "repro.cp_als.fit", "repro.mttkrp.mode0", "repro.engine.relayout",
        "repro.kernel.mttkrp3", "repro.cp_als_batched",
        "repro.cp_als_batched.sweep", "repro.cp_als_batched.update",
        "repro.cp_als_batched.fit", "repro.mttkrp.batched.mode2",
        "repro.tucker_hooi", "repro.tucker.hosvd_init",
        "repro.tucker.sweep", "repro.tucker.eigh", "repro.tucker.fit",
        "repro.multi_ttm.keep1", "repro.kernel.multi_ttm",
        "repro.serve.flush", "repro.serve.pack", "repro.serve.unpack",
    ):
        assert want in got, want
    # the eager vmap of a batched dispatch still launches a named kernel
    assert span_names.count("repro.kernel.mttkrp3") == 2 * 3 + 3 + 3
    assert span_names.count("repro.cp_als.sweep") == 2


def test_resolve_span_on_the_auto_backend(span_names):
    x, fs = _problem()
    with Trace():
        repro.mttkrp(x, fs, 0, ctx=ExecutionContext.create(backend="auto"))
    assert span_names == ["repro.mttkrp.mode0", "repro.engine.resolve"]


def test_no_span_is_entered_under_jit(span_names):
    """Staging a program opens no span: not the engine's, the kernels'
    or the drivers' pieces that jit can trace."""
    from repro.core.tucker import hosvd_init
    from repro.kernels import ops as kernel_ops

    x, fs = _problem()
    ctx = _pallas_ctx()
    with Trace():
        jax.jit(lambda a, *b: repro.mttkrp(a, list(b), 1, ctx=ctx))(x, *fs)
        jax.jit(lambda a, *b: repro.mttkrp(a, list(b), 0, ctx=ctx))(
            jnp.stack([x, x]), *fs)
        jax.jit(lambda a, *m: repro.multi_ttm(a, list(m), keep=2, ctx=ctx))(
            x, *[f[:, :2] for f in fs])
        jax.jit(lambda a: kernel_ops.mttkrp_pallas(a, fs, 2, interpret=True))(x)
        jax.jit(lambda a: hosvd_init(a, (2, 2, 2)))(x)
        assert span_names == []
        repro.mttkrp(x, fs, 1, ctx=ctx)  # eager: the same call opens spans
    assert span_names[0] == "repro.mttkrp.mode1"


def test_new_span_sites_do_not_change_hlo():
    """Byte-identical HLO with a Trace active or not, through every kind
    of new span site: relayout, kernel launch, resolve, batched vmap."""
    from repro.kernels import ops as kernel_ops

    x, fs = _problem()
    auto = ExecutionContext.create(backend="auto")
    calls = [
        (lambda a, *b: kernel_ops.mttkrp_pallas(a, b, 0, interpret=True),
         (x, *fs)),
        (lambda a, *b: repro.mttkrp(a, list(b), 2, ctx=_pallas_ctx()),
         (jnp.stack([x, x]), *fs)),
        (lambda a, *b: repro.mttkrp(a, list(b), 1, ctx=auto), (x, *fs)),
        (lambda a, *m: repro.multi_ttm(a, list(m), keep=0,
                                       ctx=_pallas_ctx()),
         (x, *[f[:, :2] for f in fs])),
    ]
    for fn, args in calls:
        off = jax.jit(fn).lower(*args).as_text()
        with Trace() as tr:
            on = jax.jit(fn).lower(*args).as_text()
        assert on == off
        assert len(tr) == 0


@pytest.fixture
def no_host_reads(monkeypatch):
    """Device-to-host reads fail: the transfer guard (what fires on a
    TPU) and, since a CPU array needs no transfer, a read of an array's
    host value too."""
    from jax._src import array

    def refuse(self):
        raise AssertionError("a device array was read on the host")

    monkeypatch.setattr(array.ArrayImpl, "_value", property(refuse))
    with jax.transfer_guard_device_to_host("disallow"):
        yield


def test_traced_batched_solve_syncs_where_untraced_does(no_host_reads):
    """tol=0: neither the untraced nor the traced batched solve reads a
    device value; the trace converts its arrays when read."""
    from repro.engine.batch import cp_als_batched

    x, _ = _problem()
    xb = jnp.stack([x, 2 * x])
    cp_als_batched(xb, RANK, n_iters=3, tol=0.0)
    with Trace() as tr:
        cp_als_batched(xb, RANK, n_iters=3, tol=0.0)
    assert len(tr) > 0


def test_recorded_arrays_convert_once_when_read(tmp_path):
    from repro.engine.batch import cp_als_batched

    x, fs = _problem()
    p = tmp_path / "t.jsonl"
    with Trace(path=str(p)) as tr:
        repro.cp_als(x, RANK, n_iters=2, init_factors=fs)
        r = cp_als_batched(jnp.stack([x, x]), RANK, n_iters=2)
    it = next(e for e in tr.events if e["kind"] == "cp_als_iter")
    assert isinstance(it["weights"], list) and len(it["weights"]) == RANK
    bi = [e for e in tr.events if e["kind"] == "cp_als_batched_iter"]
    assert bi[-1]["fits"] == pytest.approx([float(f) for f in r.fits])
    assert bi[-1]["converged"] == [False, False]
    assert load_trace(str(p)) == tr.events


def test_event_intervals_match_profiler_sweep_spans(tmp_path):
    """Each cp_als_iter event spans its repro.cp_als.sweep step span in
    the .xplane.pb, on the same clock (the profiler stores spans relative
    to its Task Environment's profile_start_time)."""
    import glob
    import os

    x, fs = _problem()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Trace() as tr:
            repro.cp_als(x, RANK, n_iters=3, init_factors=fs)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    t0 = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    spans = sorted(
        (t0 + e.start_ns, t0 + e.end_ns)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == "repro.cp_als.sweep"
    )
    iters = [e for e in tr.events if e["kind"] == "cp_als_iter"]
    assert len(spans) == len(iters) == 3
    for (s, e), ev in zip(spans, iters):
        assert abs(ev["start_ns"] - s) < 1e6 and abs(ev["end_ns"] - e) < 1e6


# ---------------------------------------------------------------------------
# ExecutionContext.observe: JSON round-trip, old artifacts load
# ---------------------------------------------------------------------------

def test_observe_field_roundtrips_and_defaults_off():
    ctx = ExecutionContext.create(observe=True)
    assert ctx.observe is True
    back = ExecutionContext.from_json(ctx.to_json())
    assert back == ctx and back.observe is True
    # pre-observability JSON (no "observe" key) still loads
    d = json.loads(ExecutionContext.create().to_json())
    d.pop("observe")
    assert ExecutionContext.from_dict(d).observe is False


# ---------------------------------------------------------------------------
# MetricsRegistry: counters match known dispatch counts per backend
# ---------------------------------------------------------------------------

def test_registry_counts_dispatches_per_backend():
    """One mttkrp per mode on the pinned problem: the pallas backend
    increments the dispatch counter once per call, the host backends not
    at all — measured with snapshots, never resets."""
    x, fs = _problem()
    for backend, per_call in (
        ("einsum", 0), ("blocked_host", 0), ("pallas", 1),
    ):
        ctx = ExecutionContext.create(backend=backend, interpret=True)
        before = registry().snapshot()
        for mode in range(len(DIMS)):
            repro.mttkrp(x, fs, mode, ctx=ctx)
        delta = registry().delta(before)
        expected = per_call * len(DIMS)
        assert delta.get(PALLAS_DISPATCHES, 0) == expected, (backend, delta)


def test_snapshots_do_not_interfere():
    """The reset footgun is gone: two interleaved measurements each see
    only their own increments."""
    reg = MetricsRegistry()
    snap_a = reg.snapshot()
    reg.inc("k")
    snap_b = reg.snapshot()
    reg.inc("k")
    assert reg.delta(snap_a) == {"k": 2}
    assert reg.delta(snap_b) == {"k": 1}
    assert snap_a.get("k", 0) == 0  # snapshots are immutable views


def test_registry_histograms_and_to_dict():
    reg = MetricsRegistry()
    reg.inc("c", 2)
    reg.set_gauge("g", 7.5)
    reg.observe("h", 1.0)
    reg.observe("h", 3.0)
    assert reg.histogram("h") == (1.0, 3.0)
    d = reg.to_dict()
    assert d["counters"] == {"c": 2}
    assert d["gauges"] == {"g": 7.5}
    assert d["histograms"]["h"] == {
        "count": 2, "sum": 4.0, "min": 1.0, "max": 3.0,
    }


def test_tune_cache_counters():
    from repro.tune.cache import isolated_cache
    from repro.tune.search import resolve

    with isolated_cache():
        before = registry().snapshot()
        resolve(DIMS, RANK, 0, jnp.float32)
        delta = registry().delta(before)
        assert delta.get(TUNE_CACHE_MISSES, 0) == 1
        assert TUNE_CACHE_HITS not in delta


def test_pallas_dispatch_count_shim_removed():
    """The deprecated shim is gone: the registry counter is the only
    spelling (repro.verify rule RV106 keeps it that way)."""
    import repro.engine
    import repro.engine.execute as execute

    assert not hasattr(execute, "pallas_dispatch_count")
    assert "pallas_dispatch_count" not in repro.engine.__all__
    with pytest.raises(ImportError):
        from repro.engine.execute import pallas_dispatch_count  # noqa: F401


# ---------------------------------------------------------------------------
# Acceptance: cp_als under observe=True — per-dispatch triples
# ---------------------------------------------------------------------------

def test_cp_als_trace_triples_match_plan_model(tmp_path):
    """The PR's acceptance check: one cp_als run on the pinned problem
    produces a JSONL trace whose every dispatch triple satisfies
    lower bound <= modeled Eq-10 words, with modeled_words exactly the
    BlockPlan's eq10_words for that dispatch."""
    from repro.core.bounds import seq_lb_memory
    from repro.engine.execute import _mode_first
    from repro.engine.plan import choose_blocks

    x, fs = _problem()
    ctx = ExecutionContext.create(observe=True)
    p = tmp_path / "cp_als.jsonl"
    with Trace(path=str(p)):
        repro.cp_als(x, RANK, n_iters=2, init_factors=fs, ctx=ctx)
    events = load_trace(str(p))
    dispatches = [e for e in events if e["kind"] == "mttkrp"]
    iters = [e for e in events if e["kind"] == "cp_als_iter"]
    assert len(dispatches) == 2 * len(DIMS)  # one per mode per sweep
    assert len(iters) == 2
    mem = Memory.tpu_vmem(itemsize=4)
    for e in dispatches:
        assert e["lower_bound_words"] <= e["modeled_words"]
        plan = choose_blocks(
            _mode_first(DIMS, e["mode"]), RANK, 4, memory=mem
        )
        assert e["modeled_words"] == int(plan.eq10_words(
            _mode_first(DIMS, e["mode"]), RANK
        ))
        assert e["lower_bound_words"] == max(
            seq_lb_memory(DIMS, RANK, mem.budget_words), 0.0
        )
    for k, e in enumerate(iters):
        assert e["it"] == k and 0.0 <= e["fit"] <= 1.0
        assert len(e["weights"]) == RANK
    assert iters[0]["fit_delta"] is None
    assert iters[1]["fit_delta"] is not None


def test_tucker_trace_events(tmp_path):
    x, _ = _problem()
    ctx = ExecutionContext.create(observe=True)
    with Trace() as tr:
        repro.tucker_hooi(x, (2, 2, 2), n_iters=1, ctx=ctx)
    kinds = [e["kind"] for e in tr.events]
    assert kinds.count("multi_ttm") == len(DIMS)
    assert kinds.count("tucker_iter") == 1
    mt = next(e for e in tr.events if e["kind"] == "multi_ttm")
    assert mt["lower_bound_words"] <= mt["modeled_words"]


# ---------------------------------------------------------------------------
# Bounds audit
# ---------------------------------------------------------------------------

def test_audit_mttkrp_triple_on_cpu():
    x, fs = _problem()
    with Trace() as tr:
        row = audit_mttkrp(x, fs, 0)
    assert row.measured_bytes >= row.lower_bound_bytes
    assert row.modeled_words > 0
    assert row.lower_bound_words >= 0
    assert row.measured_over_model is not None
    d = row.to_dict()
    assert d["modeled_bytes"] == row.modeled_words * row.itemsize
    audit_events = [e for e in tr.events if e["kind"] == "bounds_audit"]
    assert len(audit_events) == 1
    assert audit_events[0]["measured_bytes"] == row.measured_bytes


def test_audit_multi_ttm_triple_on_cpu():
    x, _ = _problem()
    mats = [
        jax.random.normal(jax.random.PRNGKey(10 + k), (d, 2))
        for k, d in enumerate(DIMS)
    ]
    row = audit_multi_ttm(x, mats, keep=None)
    assert row.measured_bytes >= row.lower_bound_bytes
    assert row.modeled_words > 0


# ---------------------------------------------------------------------------
# summarize_events + report CLI
# ---------------------------------------------------------------------------

def test_summarize_events_totals():
    events = [
        {"kind": "mttkrp", "modeled_words": 100, "itemsize": 4,
         "lower_bound_words": 10},
        {"kind": "bounds_audit", "modeled_words": 50, "itemsize": 4,
         "lower_bound_words": 0, "measured_bytes": 300.0},
    ]
    s = summarize_events(events)
    assert s["events"] == 2
    assert s["modeled_words"] == 150.0
    assert s["lower_bound_words"] == 10.0
    assert s["measured_bytes"] == 300.0
    assert s["optimality_ratio"] == pytest.approx(300.0 / 600.0)
    empty = summarize_events([])
    assert empty["measured_bytes"] is None
    assert empty["optimality_ratio"] is None


def test_report_cli_renders_table(tmp_path, capsys):
    from repro.observe.report import main as report_main

    x, fs = _problem()
    p = tmp_path / "trace.jsonl"
    with Trace(path=str(p)):
        repro.mttkrp(x, fs, 0, ctx=ExecutionContext.create(observe=True))
        audit_mttkrp(x, fs, 0)
    assert report_main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "| kind |" in out and "mttkrp" in out and "bounds_audit" in out


def test_report_cli_empty_trace_fails(tmp_path):
    from repro.observe.report import main as report_main

    p = tmp_path / "empty.jsonl"
    p.write_text("")
    assert report_main([str(p)]) == 1  # empty table = broken pipeline
    assert report_main([str(tmp_path / "missing.jsonl")]) == 2


def test_report_cli_flags_excess_traffic(tmp_path, capsys):
    from repro.observe.report import main as report_main

    p = tmp_path / "hot.jsonl"
    e = {
        "schema": SPAN_SCHEMA, "seq": 0, "time_s": 0.0,
        "kind": "bounds_audit", "itemsize": 4, "modeled_words": 10,
        "lower_bound_words": 0, "measured_bytes": 400.0,
    }
    p.write_text(json.dumps(e) + "\n")
    assert report_main([str(p)]) == 0  # flagged but not strict
    assert "!" in capsys.readouterr().out
    assert report_main([str(p), "--strict"]) == 1
    assert report_main([str(p), "--strict", "--flag-factor", "20"]) == 0


# ---------------------------------------------------------------------------
# Benchmark stamping + perf gate traffic columns
# ---------------------------------------------------------------------------

def test_perf_gate_traffic_threshold():
    from benchmarks.perf_gate import compare_traffic

    old = {"row": {"name": "row", "us_per_call": 1.0,
                   "trace": {"modeled_words": 100.0,
                             "optimality_ratio": 1.0}}}
    new_ok = {"row": {"name": "row", "us_per_call": 1.0,
                      "trace": {"modeled_words": 110.0,
                                "optimality_ratio": 1.1}}}
    new_bad = {"row": {"name": "row", "us_per_call": 1.0,
                       "trace": {"modeled_words": 200.0,
                                 "optimality_ratio": 1.0}}}
    assert compare_traffic(old, new_ok, traffic_threshold=0.25) == []
    v = compare_traffic(old, new_bad, traffic_threshold=0.25)
    assert len(v) == 1 and "modeled_words" in v[0]
    # rows lacking a trace on either side are skipped
    assert compare_traffic(
        old, {"row": {"name": "row"}}, traffic_threshold=0.25
    ) == []


def test_repro_exports_trace():
    assert repro.Trace is Trace
    assert "Trace" in repro.__all__
