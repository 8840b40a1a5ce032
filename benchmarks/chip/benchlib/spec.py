"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names the cells, configurations
and metrics.  Everything else is found by name under the benchmark's
directory (the first of ``paths``), so a new cell, configuration, traffic
mix or per-layer metric is added with new files alone:

- ``configs/<config>.json``   (the path ``BENCHMARK.json`` gives)
- ``traffic/<traffic>.json``  the mix's parameters
- ``drivers/<driver>.py``     the loop a mix names (its ``driver``)
- ``entries/<entry>.py``      how a configuration calls the program (its
                              ``entry``)
- ``reference/<name>.py``     the plain reference a configuration names
- ``limits/<workload>.json``  the numbers compared and their limits
- ``metrics/<metric>.py``     a metric's reader, end-to-end or per-layer
- ``peaks.json``              the chip's peaks by ``device_kind``
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path


@dataclass
class Cell:
    name: str
    bench_dir: Path
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def bench_dir(root: Path, spec: dict) -> Path:
    return Path(root) / spec["paths"][0]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` with its configuration, traffic mix and
    limits read from their files, and the metrics it reports."""
    root = Path(root)
    spec = load_spec(root)
    bdir = bench_dir(root, spec)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=workload, bench_dir=bdir, chips=int(w["chips"]),
        config=_read_json(root / cfg_entry["file"]),
        traffic=_read_json(bdir / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bdir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def make_driver(cell: Cell, seed: int, devices, ctx_kw: dict):
    """The cell's traffic driver over its configuration's entry."""
    mod = load_module(cell.bench_dir, "drivers", cell.traffic["driver"])
    entry = load_module(cell.bench_dir, "entries", cell.config["entry"])
    return mod.DRIVER(cell.config, entry, cell.traffic, seed, devices, ctx_kw)


def load_module(bench: Path, kind: str, name: str):
    """``<bench>/<kind>/<name>.py`` as a module (``drivers``, ``entries``,
    ``metrics``, ``reference``); ``benchlib`` is importable from it."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    lib = str(Path(__file__).resolve().parents[1])
    if lib not in sys.path:
        sys.path.insert(0, lib)
    mod_name = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(bench: Path, device_kind: str) -> dict:
    """The chip's peaks; a device kind not in the table is an error."""
    table = _read_json(Path(bench) / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])})")
    return table["devices"][device_kind]
