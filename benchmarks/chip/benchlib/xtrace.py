"""From the profiler's trace to intervals, and the interval arithmetic the
per-layer readers share.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes,
with nothing but JAX, into a compact form that is also what the tests
read from a trimmed trace recorded on the chip:

    {"devices": {"<plane>": [[op label, start ns, end ns, class], ...]},
     "async":   {"<plane>": [...the spans of asynchronous ops...]},
     "host":    [[span name, start ns, end ns], ...]}

A device op's label is its HLO kind and result type (``copy
f32[1024,1024,1024]``); its class is ``"kernel"`` for a Pallas (Mosaic)
custom call, ``"collective"`` for an exchange between chips, else
``"other"``.  Control-flow ops (``while``, ``cond``) enclose the ops of
their bodies on the same line: the union of intervals counts that time
once.  Host
spans are the ``TraceAnnotation`` spans of the benchmark (``bench.*``) and
of the program (``repro.*``), on the same clock as the device ops.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Iterable

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
HOST_PREFIXES = ("bench.", "repro.")


def _split(text: str) -> tuple[str, str]:
    """(op kind, result type) of one trace op, whose name is the HLO
    instruction's text: ``%copy.2 = f32[1024,64]{0,1:T(8,128)} copy(...``
    gives ``("copy", "f32[1024,64]")``."""
    head, _, rest = text.partition(" = ")
    kind = head.strip().lstrip("%").split(".")[0]
    rtype = rest.split(" ")[0] if rest else ""
    if rtype.startswith("("):
        rtype = ""
    elif "{" in rtype:
        rtype = rtype[: rtype.index("{")]
    return kind, rtype


def op_class(text: str) -> str:
    """``"kernel"`` for a Pallas (Mosaic) kernel, ``"collective"`` for an
    exchange between chips, else ``"other"``."""
    kind, _ = _split(text)
    if 'custom_call_target="tpu_custom_call"' in text \
            or kind == "tpu_custom_call":
        return "kernel"
    if any(kind.startswith(c) for c in COLLECTIVES):
        return "collective"
    return "other"


def op_label(text: str) -> str:
    """A short, stable label: the op kind and its result type."""
    kind, rtype = _split(text)
    return f"{kind} {rtype}".strip() if rtype and len(rtype) < 60 else kind


def device_line(plane, name: str = "XLA Ops") -> object | None:
    """The line of a device plane that holds one event per XLA op
    (``"Async XLA Ops"``: the spans of asynchronous ones)."""
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def load_xplane(path: str) -> dict:
    """The compact trace of one profiler run (a ``.xplane.pb`` file, or
    the directory ``jax.profiler.start_trace`` was given)."""
    import jax

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list] = {}
    asyncs: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            for name, out in (("XLA Ops", devices),
                              ("Async XLA Ops", asyncs)):
                line = device_line(plane, name)
                if line is None:
                    continue
                ops = [[op_label(e.name), int(e.start_ns), int(e.end_ns),
                        op_class(e.name)] for e in line.events]
                out[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, int(e.start_ns), int(e.end_ns)])
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "async": asyncs, "host": host}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals: Iterable, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable) -> list[tuple[int, int]]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Iterable, b: Iterable) -> list[tuple[int, int]]:
    """The parts of the union of ``a`` not covered by the union of ``b``."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def innermost(spans: Iterable, lo: int, hi: int) -> list:
    """Cut ``[lo, hi)`` into ``(start, end, label)`` pieces, each labelled
    by the innermost host span open there (the one that started last), or
    ``"none"``."""
    import heapq

    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    starts = sorted(spans, key=lambda sp: sp[1])
    heap: list = []
    out, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][1] <= a:
            name, s, e = starts[i]
            heapq.heappush(heap, (-s, i, name, e))
            i += 1
        # the top is the span that started last; drop it once it ended
        while heap and heap[0][3] <= a:
            heapq.heappop(heap)
        label = heap[0][2] if heap else "none"
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def label_time(intervals: Iterable, pieces: list) -> dict:
    """Time of ``intervals`` under each label of ``pieces``."""
    out: dict[str, int] = {}
    j = 0
    for s, e in sorted(intervals):
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, label = pieces[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                out[label] = out.get(label, 0) + overlap
            k += 1
    return out


def window(trace: dict) -> tuple[int, int]:
    """The measured window: the benchmark's ``bench.window`` span."""
    spans = [s for s in trace["host"] if s[0] == "bench.window"]
    if not spans:
        raise ValueError("the trace has no bench.window span")
    return spans[0][1], spans[0][2]


def ops(trace: dict, device: str, classes: tuple[str, ...] | None = None,
        line: str = "devices"):
    """One device's op intervals inside the window, of the given classes
    (``line="async"``: the spans of its asynchronous ops)."""
    lo, hi = window(trace)
    return clip(((o[1], o[2]) for o in trace.get(line, {}).get(device, [])
                 if classes is None or o[3] in classes), lo, hi)


def per_device_mean(trace: dict, fn) -> float | None:
    """The mean over the traced devices of ``fn(device)``, skipping the
    devices where it finds nothing (None)."""
    vals = [v for d in sorted(trace["devices"])
            if (v := fn(d)) is not None]
    return sum(vals) / len(vals) if vals else None
