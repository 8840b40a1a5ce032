"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the metrics: the end-to-end ones, or with
``trace`` the per-layer ones, each by its reader ``metrics/<name>.py``.

The order is fixed: set-up (data from the seed, one warm pass over the
window's shapes) is ``setup_s``; the window runs whole units until
``seconds`` have passed and finishes the one in flight; then the device's
memory peak is read, the program's objects are dropped, the plain
reference runs on a sample of the window's answers drawn from the seed,
and last the trace is reduced.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import say as _say
from . import spec as specmod

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclass
class RunRecord:
    """What a metric's reader may read: the host's clock and the device's
    memory peak (end-to-end readers), the trace, the work asked for and
    the program's compiles (per-layer readers), and what the driver
    measured beyond them (``extra``)."""

    setup_s: float
    window_s: float
    units: int
    peak_bytes: int
    work: list
    compiles: int
    extra: dict
    trace: dict | None = None
    peaks: dict | None = None


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the checkout's ``.cache/jax`` (a fixed path)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".cache" / "jax")


def scratch_dir(root: Path, workload: str) -> Path:
    """Per-cell scratch inside the checkout, emptied at the start of a run."""
    d = Path(root) / ".cache" / "bench" / workload
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    return d


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def setup_jax(root: Path) -> str:
    import jax

    d = cache_dir(root)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


class CompileCounter:
    """Counts JAX's own lowering events while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        self.backend = 0

    def _event(self, name, secs, **kw):
        if self.on and name == LOWERING_EVENT:
            self.count += 1
        if self.on and name == BACKEND_COMPILE_EVENT:
            self.backend += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)
        return False


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def sample_answers(answers: list, k: int, seed: int) -> list:
    """``k`` answers drawn from the seed; the first is always in."""
    if len(answers) <= k:
        return list(answers)
    rng = np.random.default_rng(int(seed) + 1)
    rest = rng.choice(np.arange(1, len(answers)), size=k - 1, replace=False)
    return [answers[0]] + [answers[i] for i in sorted(rest)]


def compare(cell, driver, seed: int, precision: str = "highest",
            stand_in=None):
    """The numbers compared, each the worst over the sampled answers.

    With ``stand_in`` set, ``stand_in(ref, x, answer, cfg)`` takes the
    program's place: the control (the reference one precision step down)
    or a planted fault, whose readings set a limit's upper end.
    """
    import jax

    ref = specmod.load_module(cell.bench_dir, "reference",
                              cell.config["reference"])
    sample = sample_answers(driver.answers(), int(cell.traffic["check"]), seed)
    worst: dict[str, float] = {}
    failed_answers = 0
    for ans in sample:
        inp = driver.answer_input(ans)
        want = ref.reference(inp["x"], {**ans, **inp}, cell.config, precision)
        got = ans
        if stand_in is not None:
            got = stand_in(ref, inp["x"], {**ans, **inp}, cell.config)
        nums = ref.gaps({**got, **inp}, want)
        bad = any(not np.isfinite(v) or v > cell.limits[n]["limit"]
                  for n, v in nums.items() if n in cell.limits)
        failed_answers += int(bad)
        for n, v in nums.items():
            v = float(v) if np.isfinite(v) else float("inf")
            worst[n] = max(worst.get(n, 0.0), v)
        jax.block_until_ready(want)
        del want
    return worst, len(sample), failed_answers


def judge(cell, numbers: dict) -> tuple[bool, dict]:
    checks = {}
    ok = True
    for name, lim in cell.limits.items():
        if name not in numbers:
            ok = False
            checks[name] = {"value": None, "limit": lim["limit"]}
            continue
        v = numbers[name]
        checks[name] = {"value": v, "limit": lim["limit"]}
        ok = ok and v <= lim["limit"]
    return ok, checks


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops that took most time, and the idle time by the host
    span open when the device was idle (the innermost one), each summed
    over the window and averaged over the chips."""
    from . import xtrace

    lo, hi = xtrace.window(trace)
    ndev = max(len(trace["devices"]), 1)
    by_op: dict[str, float] = {}
    gaps: dict[str, float] = {}
    labels = xtrace.innermost([s for s in trace["host"]
                               if s[0] != "bench.window"], lo, hi)
    for dev, dev_ops in trace["devices"].items():
        for name, s, e, cls in dev_ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = f"{name} [{cls}]"
                by_op[key] = by_op.get(key, 0.0) + (e - s) * 1e-9 / ndev
        idle = xtrace.subtract([(lo, hi)], xtrace.ops(trace, dev))
        for label, ns in xtrace.label_time(idle, labels).items():
            gaps[label] = gaps.get(label, 0.0) + ns * 1e-9 / ndev
    order = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gorder = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in order],
            "idle_gaps": [[k, v] for k, v in gorder]}


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             *, t_start: float | None = None, require_tpu: bool = True,
             dump: str | None = None) -> dict:
    """One run of ``workload``; returns the result line as a dict (its
    ``checks`` key last)."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    cell = specmod.find_cell(root, workload)
    devices = devices_for(cell.chips, require_tpu)
    peaks = specmod.peaks(cell.bench_dir, devices[0].device_kind) \
        if (trace or require_tpu) else None
    work_dir = scratch_dir(root, workload)
    os.environ["REPRO_TUNE_CACHE"] = str(work_dir / "plans.json")
    cache = setup_jax(root)

    with CompileCounter() as counter:
        return _run(cell, root, seed, seconds, trace, t_start, require_tpu,
                    dump, devices, peaks, work_dir, cache, counter)


def _run(cell, root, seed, seconds, trace, t_start, require_tpu, dump,
         devices, peaks, work_dir, cache, counter) -> dict:
    import jax

    import repro

    workload = cell.name
    ctx_kw = {"interpret": None if not require_tpu else False,
              "compilation_cache": cache}
    # the program's own spans (repro.*) on the profiler's clock; a Trace
    # with no events is falsy, so test it against None
    tracer = repro.Trace(annotate=True) if trace else None
    spans = tracer if tracer is not None else nullcontext()
    prec = cell.config.get("matmul_precision", "highest")
    driver = specmod.make_driver(cell, seed, devices, ctx_kw)
    with jax.default_matmul_precision(prec), spans:
        driver.setup()
        jax.effects_barrier()
        setup_s = time.perf_counter() - t_start
    _say(f"set-up {setup_s:.3f} s")
    with jax.default_matmul_precision(prec):
        trace_dir = str(work_dir / "trace")
        if trace:
            # host spans (TraceAnnotation) on; Python call tracing off
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter.on = True
        t0 = time.perf_counter()
        try:
            with spans, jax.profiler.TraceAnnotation("bench.window"):
                while True:
                    with jax.profiler.TraceAnnotation(f"bench.{driver.unit}"):
                        driver.run_unit()
                    if time.perf_counter() - t0 >= seconds:
                        break
            window_s = time.perf_counter() - t0
        finally:
            counter.on = False
            if trace:
                jax.profiler.stop_trace()
    _say(f"window {window_s:.3f} s, {driver.units} {driver.unit}s' "
         f"decompositions, {counter.count} programs lowered, "
         f"{counter.backend} compiled by XLA")
    peak = memory_peak(devices)
    units = driver.units
    work = driver.work()
    driver.release()
    gc.collect()

    t_ref = time.perf_counter()
    with jax.default_matmul_precision(prec):
        numbers, checked, failed = compare(cell, driver, seed)
    _say(f"reference {time.perf_counter() - t_ref:.3f} s over {checked} "
         f"answers")
    correct, checks = judge(cell, numbers)
    correct = correct and units > 0 and failed == 0

    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": peak}
    run = RunRecord(setup_s=setup_s, window_s=window_s, units=units,
                    peak_bytes=peak, work=work, compiles=counter.count,
                    extra=driver.extra, peaks=peaks)
    breakdown_ = None
    if not trace:
        metrics = _read_metrics(cell, cell.end_to_end, run, required=True)
    else:
        from . import xtrace

        run.trace = tr = xtrace.load_xplane(trace_dir)
        if dump:
            os.makedirs(dump, exist_ok=True)
            xtrace.save(tr, os.path.join(dump, f"{workload}.trace.json.gz"))
            with open(os.path.join(dump, f"{workload}.work.json"), "w") as f:
                json.dump({"work": work, "units": units,
                           "compiles": counter.count}, f)
        metrics = _read_metrics(cell, cell.per_layer, run, required=False)
        lo, hi = xtrace.window(tr)
        busy = [xtrace.length(xtrace.union(xtrace.ops(tr, d)))
                for d in tr["devices"]]
        result_device["busy_s"] = (sum(busy) / len(busy) * 1e-9
                                   if busy else 0.0)
        result_device["window_s"] = (hi - lo) * 1e-9
        breakdown_ = breakdown(tr) if tr["devices"] else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    result = {"correct": bool(correct), "attempted": units,
              "failed": failed, "metrics": metrics, "device": result_device}
    if breakdown_:
        result["breakdown"] = breakdown_
    result["checks"] = checks
    return result


def _read_metrics(cell, entries: list, run: RunRecord,
                  required: bool) -> dict:
    """Each metric by its reader ``metrics/<name>.py``.  A per-layer
    reader that finds nothing to read returns ``None`` and its metric is
    left out; an end-to-end metric is always there."""
    out = {}
    for m in entries:
        mod = specmod.load_module(cell.bench_dir, "metrics", m["name"])
        v = mod.read(run)
        if v is None:
            if required:
                raise ValueError(f"end-to-end metric {m['name']!r} read "
                                 f"nothing")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The numbers compared beside their limits as the last lines on
    standard error, then the result as the last line on standard out."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
