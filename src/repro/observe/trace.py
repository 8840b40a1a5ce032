"""Structured tracing: span events for every engine dispatch.

A :class:`Trace` is a context manager that captures *span events* — one
dict per engine dispatch / driver iteration / distributed sweep — into an
in-memory ring buffer, with a JSONL exporter (one event per line, stable
schema) and ``jax.profiler.TraceAnnotation`` spans (:func:`annotated`)
over the hot path, so the host work behind every device-idle gap has a
name in TPU profiler traces::

    ctx = repro.ExecutionContext.create(observe=True)
    with repro.Trace(path="run.jsonl") as t:
        repro.cp_als(x, rank=8, ctx=ctx)
    t.events                    # the recorded span dicts
    # run.jsonl: one JSON object per line, schema repro.observe.Span/2

Every event carries ``schema`` / ``seq`` / ``start_ns`` / ``end_ns`` /
``kind`` plus kind-specific fields.  ``start_ns``/``end_ns`` are
nanoseconds on the clock the profiler stamps its spans with (the host's
wall clock, ``time.time_ns``): a dispatch event spans the dispatch, a
driver iteration its sweep, an instant event has ``start_ns == end_ns``.
An ``.xplane.pb`` stores its spans relative to the ``profile_start_time``
of its ``Task Environment`` plane; add that to join the two.

Engine dispatch events (``kind`` in ``mttkrp`` /
``contract_partial`` / ``multi_ttm`` / ``fused_pair``) record the
resolved backend, the block plan, the modeled traffic in words
(``BlockPlan.eq10_words`` / ``MultiTTMPlan.model_words`` — the paper's
Eq (10) and its Multi-TTM analog), the memory-dependent sequential lower
bound (``seq_lb_memory``, clamped at 0), the dtype policy, and the
dispatch interval.  Driver events (``cp_als_iter`` / ``tucker_iter``)
record per-iteration fit / λ / convergence; distributed sweep events
(``cp_sweep_collectives`` / ``tucker_sweep_collectives``) record
HLO-measured collective bytes next to the sweep cost model.

Gating — the zero-overhead contract
-----------------------------------
Nothing is recorded unless a ``Trace`` is active (entering one pushes it
on a process-local stack).  While one is active:

* ``capture="all"`` (default): every engine call records events — an
  explicit ``with Trace():`` block is itself the opt-in.
* ``capture="observed"``: only calls whose
  ``ExecutionContext.observe`` is True record — per-context opt-in for
  tracing one workload inside a larger program.

Recording is *driver-side only*: when the operands are jax tracers (the
call is being traced into a jit/shard_map program) nothing runs — no
event, no annotation — so compiled HLO is byte-identical with observe
on or off, and shard_map sweep bodies stay collective-clean.

Recording never waits for the device: array-valued fields (a sweep's
weights, a batch's fits) stay device arrays on the event and are
converted once, when :attr:`Trace.events` or :meth:`Trace.export` reads
them, so a traced program syncs exactly where the untraced one does.

Spans
-----
:func:`annotated` is the one span helper of the hot path: a
``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` for a sweep)
opened while a ``Trace(annotate=True)`` is active and no jit/shard_map
program is being staged.  Names are prefixes by layer: drivers
``repro.cp_als*`` / ``repro.tucker*``; engine ``repro.mttkrp.*``,
``repro.multi_ttm.*``, ``repro.contract_partial*``, ``repro.fused_pair``
and, inside them, ``repro.engine.resolve`` / ``repro.engine.relayout``;
kernel launches ``repro.kernel.<name>`` (``<name>`` is also the
``pallas_call``'s ``name=``); serving ``repro.serve.*``.  The profiler
gives a device-idle stretch to the innermost span open in it.
"""

from __future__ import annotations

import json
import time
from collections import deque
from contextlib import nullcontext
from typing import Any, Iterable

from .metrics import TRACE_EVENTS_DROPPED, registry

SPAN_SCHEMA = "repro.observe.Span/2"

#: Keys every event carries, in emission order (the round-trip contract
#: tests pin; kind-specific fields follow these).
BASE_FIELDS = ("schema", "seq", "start_ns", "end_ns", "kind")

_ACTIVE: list["Trace"] = []


class Trace:
    """Record engine span events while active; export them as JSONL.

    ``capacity`` bounds the in-memory ring buffer (oldest events are
    evicted, counted under the ``trace.events_dropped`` metric);
    ``path`` exports the buffer as JSONL on clean exit;
    ``capture`` is ``"all"`` (record every engine call) or
    ``"observed"`` (record only ``ExecutionContext.observe=True`` calls);
    ``annotate`` opens the hot path's profiler spans (:func:`annotated`).
    """

    def __init__(
        self,
        capacity: int = 4096,
        *,
        path: str | None = None,
        capture: str = "all",
        annotate: bool = True,
    ) -> None:
        if capture not in ("all", "observed"):
            raise ValueError(
                f"capture must be 'all' (every engine call records while "
                f"this trace is active) or 'observed' (only "
                f"ExecutionContext.observe=True calls), got {capture!r}"
            )
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.path = path
        self.capture = capture
        self.annotate = annotate
        self._buf: deque[dict] = deque(maxlen=self.capacity)
        self._seq = 0

    # -- context management --------------------------------------------------
    def __enter__(self) -> "Trace":
        _ACTIVE.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _ACTIVE.remove(self)
        if self.path is not None and exc_type is None:
            self.export(self.path)

    # -- recording -----------------------------------------------------------
    def record(
        self, kind: str, *, start_ns: int | None = None, **fields: Any
    ) -> dict:
        """Append one span event (ring-buffered) and return it.  It ends
        now; ``start_ns`` (from :func:`now_ns`) gives its start, else it
        is an instant.  Device-array fields are kept as they are (no
        sync) until the events are read."""
        if len(self._buf) == self._buf.maxlen:
            registry().inc(TRACE_EVENTS_DROPPED)
        end_ns = now_ns()
        event = {
            "schema": SPAN_SCHEMA,
            "seq": self._seq,
            "start_ns": end_ns if start_ns is None else int(start_ns),
            "end_ns": end_ns,
            "kind": kind,
        }
        event.update(fields)
        self._seq += 1
        self._buf.append(event)
        return event

    @property
    def events(self) -> list[dict]:
        """The buffered span events, oldest first (a copy); array fields
        are converted to lists here, once per array."""
        for event in self._buf:
            for k, v in event.items():
                if hasattr(v, "__array__"):
                    event[k] = _to_json(v)
        return list(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    # -- export --------------------------------------------------------------
    def export(self, path: str) -> int:
        """Write the buffer as JSONL (one event per line); returns the
        number of events written."""
        events = self.events
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e, sort_keys=True) + "\n")
        return len(events)


def now_ns() -> int:
    """Nanoseconds on the profiler's clock (the host's wall clock)."""
    return time.time_ns()


def _to_json(value: Any) -> Any:
    """One device-to-host read of an array field: a list (or scalar) of
    Python numbers."""
    import numpy as np

    return np.asarray(value).tolist()


def current_trace() -> Trace | None:
    """The innermost active :class:`Trace`, or None."""
    return _ACTIVE[-1] if _ACTIVE else None


def load_trace(path: str) -> list[dict]:
    """Read a JSONL trace file back into its list of span events."""
    out: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# The wiring helpers the engine layers call
# ---------------------------------------------------------------------------

def _is_tracer(*arrays: Any) -> bool:
    import jax

    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def should_record(ctx_observe: bool, *arrays: Any) -> bool:
    """One cheap gate for every wiring site: is a trace active, does its
    capture policy admit this call, and are the operands concrete (under
    jit/shard_map tracing nothing may run)?"""
    t = current_trace()
    if t is None:
        return False
    if t.capture == "observed" and not ctx_observe:
        return False
    return not _is_tracer(*arrays)


def record_event(
    kind: str, *, start_ns: int | None = None, **fields: Any
) -> dict | None:
    """Record into the active trace (no-op without one)."""
    t = current_trace()
    if t is None:
        return None
    return t.record(kind, start_ns=start_ns, **fields)


_NO_SPAN = nullcontext()


def _staging() -> bool:
    """Is a jit/shard_map/make_jaxpr program being staged?  Eager
    ``vmap`` is not staging: its batch tracers run op by op on the
    device, so the host time they take is real and gets its span."""
    import jax

    return jax.core.unsafe_am_i_under_a_jit_DO_NOT_USE()


def annotated(name: str, *, step: int | None = None):
    """The profiler span ``name`` (a ``StepTraceAnnotation`` numbered
    ``step`` when given): entered only while a ``Trace(annotate=True)``
    is active and nothing is being staged into a compiled program, so
    compiled HLO is byte-identical with tracing on or off.  With no
    trace active it costs one list lookup."""
    if not _ACTIVE or not _ACTIVE[-1].annotate or _staging():
        return _NO_SPAN
    # host spans only: no jax.named_scope, which would also rename the
    # ops the eager calls inside it lower
    import jax

    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def summarize_events(events: Iterable[dict]) -> dict:
    """Aggregate a span-event stream into the summary benchmark rows
    embed: event count, total modeled words, total measured bytes (when
    any event carries them), total lower-bound words, and the
    measured-bytes / modeled-bytes optimality ratio when both sides are
    known."""
    n = 0
    modeled_words = 0.0
    modeled_bytes = 0.0
    measured_bytes = 0.0
    lower_bound_words = 0.0
    have_measured = False
    for e in events:
        n += 1
        mw = e.get("modeled_words")
        if mw is not None:
            modeled_words += float(mw)
            itemsize = float(e.get("itemsize", 4))
            modeled_bytes += float(mw) * itemsize
        lb = e.get("lower_bound_words")
        if lb is not None:
            lower_bound_words += float(lb)
        mb = e.get("measured_bytes")
        if mb is not None:
            measured_bytes += float(mb)
            have_measured = True
    summary = {
        "events": n,
        "modeled_words": modeled_words,
        "lower_bound_words": lower_bound_words,
        "measured_bytes": measured_bytes if have_measured else None,
        "optimality_ratio": (
            measured_bytes / modeled_bytes
            if have_measured and modeled_bytes > 0 else None
        ),
    }
    return summary
