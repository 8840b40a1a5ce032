"""``python -m repro.verify`` — the static verification gate.

Runs the five analyzers (plan verifier, kernel static analyzer, repo
lint, communication verifier, dtype-flow analyzer) and exits nonzero on
any finding, so CI can gate on it::

    PYTHONPATH=src python -m repro.verify              # all analyzers
    PYTHONPATH=src python -m repro.verify --only lint  # subset
    PYTHONPATH=src python -m repro.verify --comm --dtypes  # selectors
    PYTHONPATH=src python -m repro.verify --rules      # lint catalog
    PYTHONPATH=src python -m repro.verify --trace-out v.jsonl

``--comm`` / ``--dtypes`` are shorthand selectors for the distributed
analyzers (equivalent to ``--only comm,dtypes``); they compose with
each other and with ``--only``.

``--trace-out`` records one ``kind="static_verify"`` span event per
verdict (kernel, per-grid comm point, dtype program) plus one summary
event, in the standard ``repro.observe.Span/2`` schema, so
``python -m repro.observe.report`` tables static verdicts — including
the per-grid modeled/bound/measured byte columns — next to measured
bounds-audit rows.

Exit status: 0 = clean; 1 = at least one finding; 2 = bad usage.
"""

from __future__ import annotations

import argparse
import sys

from . import Finding

ANALYZERS = ("plans", "kernels", "lint", "comm", "dtypes")


def run(
    only: tuple[str, ...] = ANALYZERS,
    trace_out: str | None = None,
) -> tuple[list[Finding], list[dict]]:
    """Run the selected analyzers; returns (findings, verdicts) and
    optionally exports the verdicts as a JSONL trace. Every verdict
    dict carries an ``"analyzer"`` key (``"kernels"`` / ``"comm"`` /
    ``"dtypes"``)."""
    findings: list[Finding] = []
    verdicts: list[dict] = []
    if "plans" in only:
        from .plans import verify_plans

        findings += verify_plans()
    if "kernels" in only:
        from .kernels import verify_kernels

        kf, kv = verify_kernels()
        findings += kf
        verdicts += [{"analyzer": "kernels", **v} for v in kv]
    if "lint" in only:
        from .lint import lint_tree

        findings += lint_tree()
    if "comm" in only:
        from .comm import verify_comm

        cf, cv = verify_comm()
        findings += cf
        verdicts += cv
    if "dtypes" in only:
        from .dtypes import verify_dtypes

        df, dv = verify_dtypes()
        findings += df
        verdicts += dv
    if trace_out is not None:
        from ..observe.trace import Trace, record_event

        kernel_vs = [v for v in verdicts if v["analyzer"] == "kernels"]
        with Trace(path=trace_out):
            for v in verdicts:
                record_event("static_verify", **v)
            record_event(
                "static_verify",
                name="summary",
                analyzers=list(only),
                findings=len(findings),
                kernels_checked=len(kernel_vs),
                kernels_agreeing=sum(
                    1 for v in kernel_vs if v["agrees"]
                ),
                comm_points=sum(
                    1 for v in verdicts if v["analyzer"] == "comm"
                ),
                dtype_programs=sum(
                    1 for v in verdicts if v["analyzer"] == "dtypes"
                ),
            )
    return findings, verdicts


def _print_verdict(v: dict) -> None:
    mark = "ok" if v["agrees"] and not v.get("findings") else "FAIL"
    if v["analyzer"] == "kernels":
        print(
            f"kernel {v['name']}: grid={tuple(v['grid'])} "
            f"footprint={v['footprint_words']}w "
            f"claim={v['claimed_words']}w [{mark}]"
        )
    elif v["analyzer"] == "comm":
        if "measured_collective_bytes" in v:
            print(
                f"comm {v['name']}: shape={tuple(v['shape'])} "
                f"grid={tuple(v['grid'])} "
                f"bytes={v['measured_collective_bytes']} "
                f"model={v['modeled_words']}w "
                f"lb={v['lower_bound_words']}w [{mark}]"
            )
        else:
            print(f"comm {v['name']}: [{mark}]")
    else:  # dtypes
        print(
            f"dtypes {v['name']}: "
            f"{v['accumulations']} accumulation(s), "
            f"{v['narrow_accumulations']} narrow [{mark}]"
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.verify", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--only", default=None,
        help=f"comma-separated analyzers to run "
        f"(default: {','.join(ANALYZERS)})",
    )
    ap.add_argument(
        "--comm", action="store_true",
        help="run the AOT communication verifier (selector shorthand)",
    )
    ap.add_argument(
        "--dtypes", action="store_true",
        help="run the dtype-flow analyzer (selector shorthand)",
    )
    ap.add_argument(
        "--rules", action="store_true",
        help="print the lint rule catalog (markdown) and exit",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="FILE",
        help="write verdicts as kind=static_verify JSONL span "
        "events (repro.observe schema)",
    )
    args = ap.parse_args(argv)

    if args.rules:
        from .lint import rule_catalog

        print(rule_catalog())
        return 0

    selected: list[str] = []
    if args.only:
        selected += [
            a.strip() for a in args.only.split(",") if a.strip()
        ]
    if args.comm and "comm" not in selected:
        selected.append("comm")
    if args.dtypes and "dtypes" not in selected:
        selected.append("dtypes")
    bad = [a for a in selected if a not in ANALYZERS]
    if bad:
        print(
            f"verify: unknown analyzer(s) {bad}; "
            f"choose from {ANALYZERS}", file=sys.stderr,
        )
        return 2
    only = tuple(selected) if selected else tuple(ANALYZERS)

    findings, verdicts = run(only, trace_out=args.trace_out)
    for f in findings:
        print(f)
    for v in verdicts:
        _print_verdict(v)
    by = {
        a: sum(1 for v in verdicts if v["analyzer"] == a)
        for a in ("kernels", "comm", "dtypes")
    }
    print(
        f"verify: {len(findings)} finding(s) across "
        f"{', '.join(only)}; {by['kernels']} kernel(s), "
        f"{by['comm']} comm point(s), {by['dtypes']} dtype program(s)"
    )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
