"""Benchmark harness: one module per paper table/claim (DESIGN.md §7).

Default output is ``name,us_per_call,derived`` CSV on stdout. ``--json
PATH`` additionally writes a structured result file (schema-versioned,
stamped with ``--commit``/``--timestamp`` passed by the caller) — the
format the BENCH_*.json perf-trajectory files are built from.

``--context PATH`` runs the harness under a serialized
:class:`repro.ExecutionContext` (exported to ``REPRO_CONTEXT``, the seed
every driver's default path reads) and stamps that *ambient* context
JSON into every structured result row — a benchmark number without its
execution environment is not reproducible. Rows produced by modules that
deliberately pin a different fixed configuration for comparison (e.g.
``kernel_mttkrp``'s pallas rows, ``tune``'s per-backend timings) name
that configuration in their ``derived`` column; the recorded context is
the environment the *harness* ran under.

Usage:
    PYTHONPATH=src python -m benchmarks.run [module ...]
    PYTHONPATH=src python -m benchmarks.run --json out.json \\
        --commit "$(git rev-parse HEAD)" --timestamp "$(date -u +%s)" tune
    PYTHONPATH=src python -m benchmarks.run --context ctx.json --json out.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

MODULES = (
    "seq_blocked",      # Thm 6.1: Alg 2 attains the sequential bounds
    "seq_vs_matmul",    # §VI-A: Alg 2 vs matmul-baseline regimes
    "par_comm",         # §VI-B + Thm 6.2: Alg 3/4 vs Cor 4.2 vs matmul
    "cp_als",           # §VII: dimension-tree reuse + CP-ALS e2e
    "all_mode",         # engine: dimtree vs independent all-mode MTTKRP
    "kernel_mttkrp",    # Pallas Alg-2 kernel: correctness + traffic model
    "tune",             # autotuner: search, warm-cache replay, calibration
    "tucker",           # Multi-TTM backends + Tucker/HOOI (arXiv:2207.10437)
    "lm_step",          # §Roofline: per-cell terms from the dry-run
    "serve",            # serving layer: batched vs looped, queue flush
)

JSON_SCHEMA_VERSION = 1


def collect(want: set[str]) -> list[dict]:
    """Run the selected modules, returning structured rows (errors become
    rows too — a failing table must not kill the harness).

    Each module runs under an in-memory :class:`repro.observe.Trace`
    (``capture="all"``: the harness itself is the opt-in), and every row
    it produced is stamped with that module's trace summary — modeled
    Eq-10 words, measured bytes where a collective sweep or bounds audit
    recorded one, and the resulting optimality ratio — so a BENCH row
    carries its traffic story next to its wall time.
    """
    from repro.observe import Trace, summarize_events

    rows: list[dict] = []
    for modname in MODULES:
        if modname not in want:
            continue
        try:  # import inside: a module broken at import time is one
            # [ERROR] row, not a dead harness
            mod = __import__(f"benchmarks.{modname}", fromlist=["rows"])
            with Trace() as tr:
                mod_rows = [
                    {"name": name, "us_per_call": us, "derived": str(derived)}
                    for name, us, derived in mod.rows()
                ]
            summary = summarize_events(tr.events)
            for row in mod_rows:
                row["trace"] = summary
            rows.extend(mod_rows)
        except Exception as e:
            rows.append(
                {
                    "name": f"{modname}[ERROR]",
                    "us_per_call": 0.0,
                    "derived": f"{type(e).__name__}:{e}",
                }
            )
    return rows


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("modules", nargs="*", help=f"subset of {list(MODULES)}")
    ap.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write structured results to PATH (BENCH_*.json format)",
    )
    ap.add_argument(
        "--commit", default=None,
        help="commit id recorded in the JSON output (caller-provided)",
    )
    ap.add_argument(
        "--timestamp", default=None,
        help="timestamp recorded in the JSON output (caller-provided)",
    )
    ap.add_argument(
        "--context", metavar="PATH", default=None,
        help="run under this serialized repro.ExecutionContext (seeds "
        "REPRO_CONTEXT, the default every bare driver call reads) and "
        "record the ambient context in each JSON row",
    )
    args = ap.parse_args(argv)

    context_dict = None
    if args.context:
        from repro import ExecutionContext  # after PYTHONPATH=src

        ctx = ExecutionContext.load(args.context)  # validates eagerly
        context_dict = ctx.to_dict()
        os.environ["REPRO_CONTEXT"] = ctx.to_json()

    want = set(args.modules) or set(MODULES)
    unknown = want - set(MODULES)
    if unknown:
        print(
            f"unknown benchmark module(s): {sorted(unknown)}; "
            f"available: {list(MODULES)}",
            file=sys.stderr,
        )
        sys.exit(2)

    print("name,us_per_call,derived")
    sys.stdout.flush()
    rows = []
    for modname in MODULES:
        if modname not in want:
            continue
        for row in collect({modname}):
            rows.append(row)
            print(
                f"{row['name']},{row['us_per_call']:.1f},{row['derived']}"
            )
            sys.stdout.flush()

    if args.json:
        if context_dict is not None:
            for row in rows:  # every row records the ambient environment
                row["context"] = context_dict
        payload = {
            "schema": JSON_SCHEMA_VERSION,
            "commit": args.commit,
            "timestamp": args.timestamp,
            "modules": sorted(want),
            "context": context_dict,
            "results": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {len(rows)} results to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
