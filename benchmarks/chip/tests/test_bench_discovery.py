"""A configuration, a traffic mix, a loop, a metric and a cell are added
by new files and new entries alone: the harness finds and runs them with
no edit to its code."""

import json

import pytest

from _bench_helpers import make_root, run
from benchlib import spec


def _add_cell(root):
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "cp3-f32.json").read_text())
    cfg.update(name="cp3-f32-r6", rank=6)
    cfg["data"]["rank"] = 6
    (bench / "configs" / "cp3-f32-r6.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "slab.json").write_text(json.dumps(
        {"driver": "solves", "shape": [20, 10, 8], "check": 1}))
    (bench / "limits" / "cp3-f32-r6.slab.json").write_text(
        (bench / "limits" / "cp3-f32.cube1024.json").read_text())
    (bench / "metrics" / "solves_in_window.py").write_text(
        "def read(run):\n    return float(run.units)\n")
    (bench / "metrics" / "program_spans.py").write_text(
        "def read(run):\n    return float(sum(s[0].startswith('repro.')"
        " for s in run.trace['host']))\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "cp3-f32-r6", "source": "https://example.org",
                         "file": "bench/configs/cp3-f32-r6.json",
                         "reduced": [], "why": "a test"})
    b["workloads"].append({"name": "cp3-f32-r6.slab", "config": "cp3-f32-r6",
                           "traffic": "slab", "chips": 1, "why": "a test"})
    for name in ("solves_in_window", "program_spans"):
        b["per_layer"].append({"name": name, "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "drivers", "moves": "solve_s",
                               "workloads": ["cp3-f32-r6.slab"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    _add_cell(root)
    cell = spec.find_cell(root, "cp3-f32-r6.slab")
    assert cell.config["rank"] == 6
    assert cell.traffic["shape"] == [20, 10, 8]
    assert "solves_in_window" in [m["name"] for m in cell.per_layer]
    # the other cells do not report a metric that lists only the new one
    other = spec.find_cell(root, "cp3-f32.cube1024")
    assert "solves_in_window" not in [m["name"] for m in other.per_layer]
    r = run(root, "cp3-f32-r6.slab")
    assert r["correct"], r["checks"]
    traced = run(root, "cp3-f32-r6.slab", trace=True)
    # no device trace on the CPU: the device readers find nothing to read
    # and are left out; the new reader finds its count
    assert traced["metrics"]["solves_in_window"]["value"] == \
        traced["attempted"]
    assert "device_idle_share" not in traced["metrics"]
    # the program's own spans are on the trace (repro.Trace is entered)
    assert traced["metrics"]["program_spans"]["value"] > 0
    assert traced["correct"]


TIMED_DRIVER = """
import time

import jax

from benchlib.driver import Driver, block


class Timed(Driver):
    def setup(self):
        self.ctx = self.context()
        self.x = block(self.entry.make_tensor(
            jax.random.fold_in(self.key, 0), tuple(self.traffic["shape"]),
            self.cfg))
        self.extra["latencies"] = []
        self._solve(-1)

    def _init(self, i):
        return self.entry.init(jax.random.fold_in(self.key, 7 + i),
                               tuple(self.x.shape), self.cfg)

    def _solve(self, i):
        return {"id": i, **self.entry.solve(self.x, self._init(i), self.cfg,
                                            self.ctx)}

    def run_unit(self):
        t = time.perf_counter()
        self.done.append(self._solve(self.units))
        self.extra["latencies"].append(time.perf_counter() - t)
        self.units += 1
        self.calls.extend(self.entry.work(self.x.shape, self.cfg))
        return 1

    def answer_input(self, answer):
        return {"x": self.x, "init": self._init(answer["id"])}


DRIVER = Timed
"""


def test_new_loop_and_end_to_end_metric_by_new_files(tmp_path):
    """A mix with a loop of its own (``drivers/<name>.py``) and an
    end-to-end metric that reads what that loop measured."""
    root = make_root(tmp_path)
    bench = root / "bench"
    (bench / "drivers" / "timed.py").write_text(TIMED_DRIVER)
    (bench / "traffic" / "timed8.json").write_text(json.dumps(
        {"driver": "timed", "shape": [12, 10, 8], "check": 1}))
    (bench / "limits" / "cp3-f32.timed8.json").write_text(
        (bench / "limits" / "cp3-f32.cube1024.json").read_text())
    (bench / "metrics" / "solve_max_s.py").write_text(
        "def read(run):\n    return max(run.extra['latencies'])\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "cp3-f32.timed8", "config": "cp3-f32",
                           "traffic": "timed8", "chips": 1, "why": "a test"})
    b["end_to_end"].append({"name": "solve_max_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["cp3-f32.timed8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    r = run(root, "cp3-f32.timed8")
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert set(m) == {"setup_s", "solve_s", "peak_hbm_gib", "solve_max_s"}
    assert 0 < m["solve_max_s"]["value"] <= m["solve_s"]["value"] * \
        r["attempted"]
    assert "solve_max_s" not in run(root, "cp3-f32.cube1024")["metrics"]


def test_unknown_names_are_errors(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(KeyError):
        spec.find_cell(root, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        spec.load_module(root / "bench", "metrics", "no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.load_module(root / "bench", "drivers", "no_such_loop")
