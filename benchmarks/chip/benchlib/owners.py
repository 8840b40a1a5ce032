"""Device-idle time by owner: the program span that was innermost while
the device was idle.

The program opens ``TraceAnnotation`` spans named by layer (``repro.*``,
see ``repro.observe.trace``); the benchmark's own are ``bench.*``.  Each
stretch of the window in which the device runs no op is owned by the
innermost span open there (``xtrace.innermost``), so a parent span keeps
the time no child covers.  A layer owns the idle time under the spans
whose names start with one of its prefixes; idle time under ``bench.*``
alone, or under no span, is unowned.  The layers' shares and the unowned
share add up to ``device_idle_share``.
"""

from __future__ import annotations

from . import xtrace

DRIVERS = ("repro.cp_als", "repro.cp_als_batched", "repro.tucker")
ENGINE = ("repro.mttkrp.", "repro.multi_ttm.", "repro.contract_partial",
          "repro.fused_pair", "repro.engine.")
KERNELS = ("repro.kernel.",)
SERVING = ("repro.serve.",)
PROGRAM = "repro."


def idle_by_owner(trace: dict, device: str) -> dict[str, int]:
    """Idle nanoseconds of ``device`` in the window, by the innermost host
    span open (``"none"`` where there is none)."""
    lo, hi = xtrace.window(trace)
    pieces = xtrace.innermost([s for s in trace["host"]
                               if s[0] != "bench.window"], lo, hi)
    idle = xtrace.subtract([(lo, hi)], xtrace.ops(trace, device))
    return xtrace.label_time(idle, pieces)


def idle_under(trace: dict, device: str, prefixes: tuple[str, ...]) -> int:
    """Idle nanoseconds of ``device`` owned by spans named ``prefixes*``."""
    return sum(ns for label, ns in idle_by_owner(trace, device).items()
               if label.startswith(prefixes))


def unowned(trace: dict, device: str) -> int:
    """Idle nanoseconds of ``device`` that no program span owns."""
    return sum(ns for label, ns in idle_by_owner(trace, device).items()
               if not label.startswith(PROGRAM))


def share(run, prefixes: tuple[str, ...]) -> float | None:
    """The layer's idle share of the window in %, averaged over the chips;
    ``None`` where the trace has no device or no span of the layer (a
    program that opens none)."""
    tr = run.trace
    if tr is None or not tr["devices"]:
        return None
    lo, hi = xtrace.window(tr)
    if not any(name.startswith(prefixes) and s < hi and e > lo
               for name, s, e in tr["host"]):
        return None
    return xtrace.per_device_mean(
        tr, lambda d: 100.0 * idle_under(tr, d, prefixes) / (hi - lo))
