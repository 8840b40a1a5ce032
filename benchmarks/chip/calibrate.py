"""Readings that set a cell's limits, at the cell's own size, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11,12,13 [--units 2] [--stand-ins control,sweeps1] \
        [--stand-in-seeds 3] [--out FILE]

For each seed, in one process: make the cell's data, run ``--units``
units of its traffic through the program (untimed), then compare their
answers with the plain reference: as the program produced them (the lower
readings) and, on the first ``--stand-in-seeds`` seeds, with each
stand-in of ``benchlib/faults.py`` in the program's place (the control and
planted faults: the upper readings).  Prints one JSON line per seed.  The
benchmark's own runs never run a stand-in.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from contextlib import nullcontext
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=2)
    ap.add_argument("--stand-ins", default="control")
    ap.add_argument("--stand-in-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import harness, spec

    cell = spec.find_cell(ROOT, args.workload)
    devices = harness.devices_for(cell.chips, require_tpu=True)
    work_dir = harness.scratch_dir(ROOT, args.workload + ".calibrate")
    os.environ["REPRO_TUNE_CACHE"] = str(work_dir / "plans.json")
    cache = harness.setup_jax(ROOT)
    with (open(args.out, "a") if args.out else nullcontext()) as out:
        _readings(args, cell, devices, cache, out)
    return 0


def _readings(args, cell, devices, cache, out) -> None:
    import jax

    from benchlib import faults, harness, spec

    prec = cell.config.get("matmul_precision", "highest")
    names = [s for s in args.stand_ins.split(",") if s]
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        drv = spec.make_driver(cell, seed, devices,
                               {"interpret": False,
                                "compilation_cache": cache})
        with jax.default_matmul_precision(prec):
            drv.setup()
            for _ in range(args.units):
                drv.run_unit()
        drv.release()
        gc.collect()
        line = {"workload": args.workload, "seed": seed}
        with jax.default_matmul_precision(prec):
            line["program"], line["answers"], _ = harness.compare(
                cell, drv, seed)
            for name in names if i < args.stand_in_seeds else ():
                line[name], _, _ = harness.compare(
                    cell, drv, seed, stand_in=faults.by_name(name))
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del drv
        gc.collect()


if __name__ == "__main__":
    sys.exit(main())
