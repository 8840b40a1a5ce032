"""Process-local metrics: counters, gauges, and histograms for the engine.

The repo's instrumentation used to be a bare module-global int in
``engine.execute`` (``_pallas_dispatches``) plus ad-hoc test plumbing.
This module replaces that with one :class:`MetricsRegistry` — a small,
dependency-free (no jax import) process-local registry every layer
writes to:

``engine.pallas_dispatches``        counter — kernel-path contractions
``engine.tensor_relayouts``         counter — tensor-sized transposes and
                                    pads the kernel path dispatches
``kernels.launcher_traces``         counter — Pallas kernel launchers
                                    traced (one per new static signature;
                                    over ``engine.pallas_dispatches`` it is
                                    the launch cache's miss share)
``tune.cache_hits`` / ``_misses``   counters — plan-cache resolution
``tune.candidates_measured``        counter — autotune measurements run
``trace.events_dropped``            counter — ring-buffer evictions
``distributed.sweeps``              counter — distributed CP-ALS sweeps run
``distributed.modeled_collective_bytes``  counter — their collective
                                    bytes by the Eq (12) sweep model

(A search's time and a distributed sweep's measured collective bytes
ride on the ``tune_search`` and ``*_sweep_collectives`` trace events.)

Reads are *snapshot-based*: measure a code region with

    before = registry().snapshot()
    ...work...
    delta = registry().delta(before)     # {"engine.pallas_dispatches": 3}

instead of the old reset-the-global-between-measurements footgun (two
interleaved measurements used to corrupt each other; snapshots are
immutable, so they cannot).

The old ``repro.engine.execute.pallas_dispatch_count()`` shim has been
removed; the registry is the only spelling (a ``repro.verify`` lint rule,
RV106, forbids reintroducing it).
"""

from __future__ import annotations

import threading
from types import MappingProxyType
from typing import Mapping

#: Canonical metric names (importable so call sites cannot typo them).
PALLAS_DISPATCHES = "engine.pallas_dispatches"
TENSOR_RELAYOUTS = "engine.tensor_relayouts"
LAUNCHER_TRACES = "kernels.launcher_traces"
TUNE_CACHE_HITS = "tune.cache_hits"
TUNE_CACHE_MISSES = "tune.cache_misses"
TUNE_CANDIDATES = "tune.candidates_measured"
TRACE_EVENTS_DROPPED = "trace.events_dropped"
DISTRIBUTED_SWEEPS = "distributed.sweeps"
DISTRIBUTED_MODELED_COLLECTIVE_BYTES = "distributed.modeled_collective_bytes"


class MetricsRegistry:
    """Counters / gauges / histograms behind one lock.

    Counters are monotone (``inc``), gauges are last-write-wins
    (``set_gauge``), histograms keep the raw observations (``observe``;
    summarized on export — the series here are short: one entry per
    search / sweep, not per request).  All methods are thread-safe and
    cheap enough to stay on even when nothing reads them — matching the
    always-on behavior of the old pallas dispatch global.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, list[float]] = {}

    # -- writes --------------------------------------------------------------
    def inc(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._hists.setdefault(name, []).append(value)

    # -- reads ---------------------------------------------------------------
    def counter(self, name: str) -> float:
        """Current value of one counter (0 if never incremented)."""
        with self._lock:
            return self._counters.get(name, 0)

    def gauge(self, name: str) -> float | None:
        with self._lock:
            return self._gauges.get(name)

    def histogram(self, name: str) -> tuple[float, ...]:
        """The raw observations recorded under ``name`` (a copy)."""
        with self._lock:
            return tuple(self._hists.get(name, ()))

    def snapshot(self) -> Mapping[str, float]:
        """An immutable point-in-time view of every counter.

        This is how a measurement brackets a code region — two concurrent
        measurements each hold their own snapshot, so neither can clobber
        the other (the reset-between-measurements footgun the old global
        had)."""
        with self._lock:
            return MappingProxyType(dict(self._counters))

    def delta(self, before: Mapping[str, float]) -> dict[str, float]:
        """Counter increments since ``before`` (a :meth:`snapshot`);
        zero-delta names are omitted."""
        now = self.snapshot()
        out: dict[str, float] = {}
        for name, value in now.items():
            d = value - before.get(name, 0)
            if d:
                out[name] = d
        return out

    def to_dict(self) -> dict:
        """Export everything (histograms summarized) — the shape the
        trace exporter and benchmark rows embed."""
        with self._lock:
            hists = {
                name: {
                    "count": len(vals),
                    "sum": sum(vals),
                    "min": min(vals) if vals else None,
                    "max": max(vals) if vals else None,
                }
                for name, vals in self._hists.items()
            }
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": hists,
            }

    def reset(self) -> None:
        """Clear everything. For test isolation only — measurement code
        must bracket with :meth:`snapshot`/:meth:`delta` instead."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-wide registry every engine layer writes to."""
    return _REGISTRY
