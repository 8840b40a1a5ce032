"""Shared set-up of the benchmark's tests: a checkout-like root at tiny
sizes, on the CPU, built from the committed files."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny stand-ins for the traffic mixes (same drivers, small shapes)
TINY_TRAFFIC = {
    "cube1024": {"driver": "solves", "shape": [16, 12, 8], "check": 2},
    "repeat": {"driver": "solves", "check": 2},
    "serve256x8": {"driver": "rounds", "batch": 2, "check": 3,
                   "shapes": [[16, 13, 9], [12, 16, 15], [9, 10, 16],
                              [14, 11, 12]]},
}
#: tiny configurations: what each changes, with its data alike
TINY_CONFIG = {
    "cp3-f32": {"rank": 4, "sweeps_per_solve": 3, "data": {"rank": 4}},
    "tucker4-hcci-f32": {"shape": [6, 10, 5, 12], "ranks": [3, 3, 2, 3],
                         "sweeps_per_solve": 2,
                         "data": {"core": [5, 6, 4, 6]}},
}


def make_root(tmp: Path) -> Path:
    """A root holding ``BENCHMARK.json`` and a ``bench/`` directory made
    from the committed benchmark, with tiny traffic and configurations,
    and the CPU added to the peaks table (for the tests only)."""
    root = Path(tmp) / "root"
    bench = root / "bench"
    bench.mkdir(parents=True)
    for d in ("drivers", "entries", "metrics", "reference", "configs",
              "limits"):
        shutil.copytree(CHIP / d, bench / d)
    (bench / "traffic").mkdir()
    for name, t in TINY_TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for name, over in TINY_CONFIG.items():
        p = bench / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg.update({k: v for k, v in over.items() if k != "data"})
        cfg["data"].update(over.get("data", {}))
        p.write_text(json.dumps(cfg))
    peaks = json.loads((CHIP / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (bench / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["paths"] = ["bench"]
    _add_unproven_cells(spec)
    for c in spec["configs"]:
        c["file"] = c["file"].replace(spec_path(), "bench")
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


#: cells whose files are committed and tested here, but which
#: ``BENCHMARK.json`` lists only once their limits are read on the chip
UNPROVEN = {
    "config": {"name": "tucker4-hcci-f32", "reduced": ["shape"],
               "source": "https://arxiv.org/abs/1510.06689",
               "file": "benchmarks/chip/configs/tucker4-hcci-f32.json",
               "why": "HOOI of an HCCI block"},
    "workload": {"name": "tucker4-hcci-f32.repeat", "config": "tucker4-hcci-f32",
                 "traffic": "repeat", "chips": 1, "why": "repeated HOOI solves"},
    "per_layer": {"name": "multi_ttm_roofline_share", "unit": "%",
                  "better": "higher", "source": "device_trace",
                  "layer": "kernels", "moves": "solve_s",
                  "workloads": ["tucker4-hcci-f32.repeat"]},
}


def _add_unproven_cells(spec: dict) -> None:
    if all(c["name"] != UNPROVEN["config"]["name"] for c in spec["configs"]):
        spec["configs"].append(dict(UNPROVEN["config"]))
        spec["workloads"].append(dict(UNPROVEN["workload"]))
        spec["per_layer"].append(dict(UNPROVEN["per_layer"]))


def spec_path() -> str:
    return json.loads((ROOT / "BENCHMARK.json").read_text())["paths"][0]


class JaxConfigGuard:
    """Restores what a run changes in the process: JAX's persistent
    compilation cache settings and the plan-cache variable."""

    KEYS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")

    def __enter__(self):
        import os

        import jax

        self.saved = {k: getattr(jax.config, k) for k in self.KEYS}
        self.env = os.environ.get("REPRO_TUNE_CACHE")
        return self

    def __exit__(self, *exc):
        import os

        import jax
        from jax.experimental.compilation_cache import compilation_cache

        for k, v in self.saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        if self.env is None:
            os.environ.pop("REPRO_TUNE_CACHE", None)
        else:
            os.environ["REPRO_TUNE_CACHE"] = self.env
        return False


def run(root: Path, workload: str, seed: int = 2**31 + 7, seconds=0.3,
        trace=False) -> dict:
    """One run of a cell at tiny size on the CPU (no look for a chip)."""
    from benchlib import harness

    with JaxConfigGuard():
        return harness.run_cell(root, workload, seed, seconds, trace,
                                require_tpu=False)
