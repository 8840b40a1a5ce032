"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``.  Set-up
(data from the seed, one warm pass over the cell's shapes, compiles) is
``setup_s``; the window then runs whole solves (or server rounds) for
``--seconds`` and finishes the one in flight.  ``--trace 1`` profiles the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` with
``--trace 1``) and last ``checks``, each number compared beside its
limit; the same numbers are the last lines on standard error.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 2.  ``--dump DIR`` also writes the compact trace and the window's
work list there (how the tests' recorded traces were made).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    from benchlib import harness

    try:
        result = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, dump=args.dump)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
