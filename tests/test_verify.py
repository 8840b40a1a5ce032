"""repro.verify: each analyzer catches its seeded known-bad input, and
the shipped tree verifies clean (the ISSUE's acceptance criteria).

Five sections mirror the five analyzers:

* plans   — a hand-built Eq-9-infeasible BlockPlan is flagged; the
  planner sweep over the default lattice emits nothing.
* kernels — hand-built captures with a coverage gap / OOB origin /
  torn accumulation run / footprint mismatch are each flagged; the five
  shipped kernels verify clean with footprints equal to the planner's
  ``kernel_block_words`` claims, and *no kernel is executed* (the
  dispatch counter is untouched).
* lint    — one fixture per RV rule (RV101 is the PR-6 falsy-cache bug,
  verbatim shape), the waiver comment works, and ``lint_tree()`` over
  the installed package is empty.
* comm    — fast-lane subset of the byte lattice traces byte-exact on
  an AbstractMesh (no devices, no dispatches); seeded known-bad inputs
  (an extra traced collective, a two-cycle permutation, an off-by-one
  consumer, a shifted reduce-scatter schedule, a suboptimal grid
  choice) fire their rules.
* dtypes  — the shipped backends accumulate fp32 under
  ``compute_dtype=bfloat16``; a plain bf16 contraction fixture fires
  ``narrow-accumulator``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.plan import BlockPlan, Memory, MultiTTMPlan
from repro.observe.metrics import PALLAS_DISPATCHES
from repro.observe import load_trace, registry
from repro.verify import Finding
from repro.verify.comm import (
    check_cp_sweep,
    check_consumer_schedule,
    check_grid_selection,
    check_mttkrp_stationary,
    check_program_bytes,
    check_reduce_scatter_schedule,
    check_ring_permutation,
    check_ring_schedules,
    check_tucker_sweep,
    mttkrp_model_bytes,
    trace_collectives,
    verify_comm,
)
from repro.verify.dtypes import check_accumulation, verify_dtypes
from repro.verify.kernels import (
    KernelCapture,
    SpecCapture,
    check_capture,
    kernel_cases,
    verify_kernels,
)
from repro.verify.lint import RULES, lint_source, lint_tree, rule_catalog
from repro.verify.plans import (
    check_batched_plans,
    check_block_plan,
    check_memory_itemsize,
    check_multi_ttm_plan,
    verify_plans,
)
from repro.verify.__main__ import main, run

VMEM = Memory.tpu_vmem(itemsize=4)


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

def test_eq9_infeasible_plan_is_flagged():
    """A block plan whose Eq-9 working set exceeds VMEM — while the
    all-ones plan fits — must be charged as infeasible-by-choice."""
    bad = BlockPlan(4096, (4096, 4096), 4096)
    assert not bad.fits(VMEM)
    fs = check_block_plan(bad, (8192, 8192, 8192), 4096, VMEM)
    assert "eq9-infeasible" in _rules(fs)


def test_eq9_not_charged_when_no_plan_fits():
    """A memory too small for even the all-ones plan is a property of
    the memory, not a planner bug: no finding."""
    tiny = Memory.abstract(2)
    plan = BlockPlan(1, (1, 1), 1)
    fs = check_block_plan(plan, (4, 4, 4), 2, tiny)
    assert "eq9-infeasible" not in _rules(fs)


def test_nonpositive_block_is_flagged():
    fs = check_block_plan(BlockPlan(0, (1, 1), 1), (4, 4, 4), 2, VMEM)
    assert _rules(fs) == {"nonpositive-block"}


def test_multi_ttm_infeasible_plan_is_flagged():
    bad = MultiTTMPlan(4096, (4096, 4096), (64, 64))
    assert not bad.fits(VMEM)
    fs = check_multi_ttm_plan(bad, (8192, 8192, 8192), (64, 64), VMEM)
    assert "eq9-infeasible" in _rules(fs)


def test_memory_itemsize_propagation_clean():
    assert check_memory_itemsize(VMEM) == []
    assert check_memory_itemsize(Memory.abstract(1000)) == []


def test_planner_sweep_is_clean():
    """Acceptance: choose_blocks / choose_sweep_blocks /
    choose_multi_ttm_blocks / best_uniform_block never emit a plan that
    fails any static check, across the whole default lattice."""
    assert verify_plans() == []


def test_batched_planner_sweep_is_clean():
    """batched_choose_blocks delegates to choose_blocks, so the batched
    plan equals the element plan (same blocks, same Eq-9 working set)
    for every B across the default lattice — by construction, and now
    by static proof."""
    assert check_batched_plans() == []


def test_batched_plan_divergence_is_flagged():
    """Known-bad fixture: a chooser that scales the rank tile with B
    (so the batched working set grows with the batch) is statically
    rejected for every B > 1."""
    from dataclasses import replace as _replace

    from repro.engine.plan import choose_blocks

    def bad_chooser(b, shape, rank, itemsize, memory=None):
        base = choose_blocks(shape, rank, itemsize, memory=memory)
        if b == 1:
            return base
        return _replace(base, block_r=base.block_r * b)

    findings = check_batched_plans(
        shapes=[(16, 14, 12)], ranks=[4], memories=[VMEM],
        batch_sizes=(1, 2, 4), chooser=bad_chooser,
    )
    assert len(findings) == 2  # B=2 and B=4 diverge; B=1 is the base
    assert all(f.rule == "batched-plan-divergence" for f in findings)
    assert all(f.analyzer == "plans" for f in findings)
    assert "B=2" in findings[0].detail or "B=2" in findings[0].subject


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _out_capture(grid, block, operand, index_map):
    spec = SpecCapture(block, index_map, operand)
    return KernelCapture(
        grid=grid, out_specs=(spec,), out_dtypes=("float32",),
    )


def test_coverage_gap_is_flagged():
    # 4 output blocks, a 2-step grid: half the output is never written.
    cap = _out_capture((2,), (4,), (16,), lambda i: (i,))
    fs = check_capture(cap, kernel="fixture")
    assert "coverage-gap" in _rules(fs)


def test_oob_origin_is_flagged():
    cap = _out_capture((2,), (4,), (8,), lambda i: (i + 1,))
    fs = check_capture(cap, kernel="fixture")
    assert "oob-origin" in _rules(fs)


def test_torn_accumulation_run_is_flagged():
    # grid (2, 2), output indexed by the *inner* dim only: block (0,) is
    # visited at steps 0 and 2 — the revisit is non-consecutive, so the
    # block would be written back twice.
    cap = _out_capture((2, 2), (3,), (6,), lambda i, j: (j,))
    fs = check_capture(cap, kernel="fixture")
    assert "noncontiguous-revisit" in _rules(fs)


def test_block_divisibility_is_flagged():
    cap = _out_capture((2,), (3,), (8,), lambda i: (i,))
    fs = check_capture(cap, kernel="fixture")
    assert "block-divisibility" in _rules(fs)


def test_index_map_arity_mismatch_is_flagged():
    cap = _out_capture((2,), (4,), (8,), lambda i: (i, 0))
    fs = check_capture(cap, kernel="fixture")
    assert "index-map" in _rules(fs)


def test_footprint_mismatch_is_flagged():
    cap = _out_capture((2,), (4,), (8,), lambda i: (i,))
    fs = check_capture(cap, kernel="fixture", claimed_block_words=9999)
    assert "footprint-mismatch" in _rules(fs)


def test_acc_dtype_violation_is_flagged():
    spec = SpecCapture((4,), lambda i: (i,), (8,))
    cap = KernelCapture(
        grid=(2,), out_specs=(spec,), out_dtypes=("bfloat16",),
    )
    fs = check_capture(cap, kernel="fixture")
    assert "acc-dtype" in _rules(fs)


def test_shipped_kernels_verify_clean_without_executing():
    """Acceptance: every shipped Pallas kernel's BlockSpec footprint
    equals the planner's kernel_block_words claim, schedules cover the
    output with contiguous accumulation runs, accumulators are fp32 —
    and the analysis never dispatches a kernel."""
    before = registry().counter(PALLAS_DISPATCHES)
    findings, verdicts = verify_kernels()
    assert findings == []
    names = {v["name"] for v in verdicts}
    assert names == {
        "mttkrp3", "mttkrpn", "mttkrp_partial", "multi_ttm", "fused_pair",
    }
    for v in verdicts:
        assert v["agrees"], v
        assert v["findings"] == 0, v
        assert v["footprint_words"] == v["claimed_words"], v
        # the working set the planner quotes = BlockSpec tiles + scratch
        assert v["working_set_words"] >= v["claimed_words"], v
        # multi-block grids: the schedule checks actually exercise
        # accumulation runs, not single-block trivia
        assert len([g for g in v["grid"] if g > 1]) >= 2, v
    assert registry().counter(PALLAS_DISPATCHES) == before


def test_verify_kernels_captures_after_launchers_are_warm():
    """A launcher traced before at a capture's own signature still
    reaches the recorder (its cache is cleared on entry), and a launch
    after the capture runs the real kernel again, not the recorded
    zeros (cleared on exit)."""
    def run(case):
        return case["fn"](*(
            jnp.ones(a.shape, a.dtype) for a in case["args"]
        ))

    warm = {case["name"]: run(case) for case in kernel_cases()}
    findings, verdicts = verify_kernels()
    assert findings == []
    assert {v["name"] for v in verdicts} == set(warm)
    for case in kernel_cases():
        again = run(case)
        jax.tree.map(np.testing.assert_array_equal, again, warm[case["name"]])
        assert all(bool(jnp.any(a != 0)) for a in jax.tree.leaves(again))


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

def test_rv101_falsy_cache_fixture():
    # the PR-6 bug, verbatim shape: an *empty* PlanCache is falsy
    src = (
        "def save(cal, cache=None):\n"
        "    (cache or default_cache()).put_calibration(cal)\n"
    )
    fs = lint_source(src, "tune/fixture.py")
    assert _rules(fs) == {"RV101"}


def test_rv101_is_not_flagged_on_none_check():
    src = (
        "def save(cal, cache=None):\n"
        "    dest = default_cache() if cache is None else cache\n"
        "    dest.put_calibration(cal)\n"
    )
    assert lint_source(src, "tune/fixture.py") == []


def test_rv102_tracer_branch_fixture():
    src = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.sum(x) > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    fs = lint_source(src, "engine/fixture.py")
    assert _rules(fs) == {"RV102"}
    # dtype inspection is static under tracing: allowlisted
    safe = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.issubdtype(x.dtype, jnp.floating):\n"
        "        return x\n"
        "    return -x\n"
    )
    assert lint_source(safe, "engine/fixture.py") == []
    # outside the traced layers the same code is fine
    assert lint_source(src, "analysis/fixture.py") == []


def test_rv103_jax_in_pure_math_fixture():
    src = "import jax\n"
    fs = lint_source(src, "engine/plan.py")
    assert _rules(fs) == {"RV103"}
    assert lint_source(src, "engine/other.py") == []


def test_rv104_mutable_default_fixture():
    fs = lint_source("def f(x=[]):\n    return x\n", "core/fixture.py")
    assert _rules(fs) == {"RV104"}
    fs = lint_source(
        "def f(x=make()):\n    return x\n", "core/fixture.py"
    )
    assert _rules(fs) == {"RV104"}


def test_rv105_wallclock_fixture():
    src = "import time\ndef f():\n    return time.perf_counter()\n"
    fs = lint_source(src, "core/fixture.py")
    assert _rules(fs) == {"RV105"}
    # measurement layers and the dispatch layer's span timing are exempt
    assert lint_source(src, "tune/fixture.py") == []
    assert lint_source(src, "engine/execute.py") == []
    assert lint_source(src, "engine/sweep.py") == []


def test_rv106_shim_reintroduction_fixture():
    fs = lint_source(
        "def pallas_dispatch_count():\n    return 0\n",
        "engine/fixture.py",
    )
    assert _rules(fs) == {"RV106"}
    fs = lint_source(
        "from repro.engine.execute import pallas_dispatch_count\n",
        "analysis/fixture.py",
    )
    assert _rules(fs) == {"RV106"}


def test_rv107_raw_collective_outside_distributed_fixture():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'i')\n"
    )
    fs = lint_source(src, "engine/fixture.py")
    assert _rules(fs) == {"RV107"}
    # distributed/ is the collective surface's sanctioned home
    assert lint_source(src, "distributed/fixture.py") == []
    # the from-import spelling is caught too
    imp = "from jax.lax import ppermute\n"
    assert _rules(lint_source(imp, "analysis/fixture.py")) == {"RV107"}
    assert lint_source(imp, "distributed/fixture.py") == []
    # non-collective lax usage outside distributed/ stays legal
    ok = "import jax\ndef f(x):\n    return jax.lax.exp(x)\n"
    assert lint_source(ok, "engine/fixture.py") == []


def test_rv108_axis_literal_fixture():
    src = "def axes():\n    return ('r', 'm0')\n"
    fs = lint_source(src, "distributed/fixture.py")
    assert _rules(fs) == {"RV108"} and len(fs) == 2
    # outside distributed/ the strings mean nothing mesh-related
    assert lint_source(src, "engine/fixture.py") == []
    # mesh.py is the axis-name home: the definitions live there
    assert lint_source(src, "distributed/mesh.py") == []
    # strings that aren't axis names are fine anywhere
    ok = "def f():\n    return ('ring', 'm10x')\n"
    assert lint_source(ok, "distributed/fixture.py") == []


def test_waiver_comment_suppresses_finding():
    src = (
        "import time\n"
        "def f():\n"
        "    return time.perf_counter()  # verify: allow=RV105\n"
    )
    assert lint_source(src, "core/fixture.py") == []
    # allow=all works too
    src_all = src.replace("allow=RV105", "allow=all")
    assert lint_source(src_all, "core/fixture.py") == []


def test_unparsable_module_is_a_finding():
    fs = lint_source("def broken(:\n", "core/fixture.py")
    assert [f.rule for f in fs] == ["syntax"]


def test_rule_catalog_lists_every_rule():
    cat = rule_catalog()
    for r in RULES:
        assert r.code in cat and r.name in cat


def test_lint_tree_is_clean():
    """Acceptance: the shipped package has zero lint findings."""
    assert lint_tree() == []


# ---------------------------------------------------------------------------
# comm: byte lattice (fast-lane subset), ring schedules, known-bad fixtures
# ---------------------------------------------------------------------------

def test_cp_sweep_point_is_byte_exact_both_overlaps():
    """Fast-lane single-process comm check: one CP lattice point traces
    byte-exact on the AbstractMesh in both overlap spellings, with no
    kernel dispatch (the nightly dist_worker proves the compiled HLO)."""
    before = registry().counter(PALLAS_DISPATCHES)
    for overlap in ("none", "ring"):
        fs, v = check_cp_sweep((8, 8, 8), 4, (2, 2, 2), overlap)
        assert fs == []
        assert v["agrees"] and v["measured_collective_bytes"] == int(
            v["modeled_words"] * v["itemsize"]
        )
        assert v["measured_collective_bytes"] >= int(
            v["lower_bound_words"] * v["itemsize"]
        )
        if overlap == "ring":
            # the ring spelling is all collective-permutes
            assert "collective-permute" in v["collectives"]
            assert "all-gather" not in v["collectives"]
        else:
            assert "all-gather" in v["collectives"]
    assert registry().counter(PALLAS_DISPATCHES) == before


def test_tucker_sweep_point_is_byte_exact():
    fs, v = check_tucker_sweep((16, 16, 16), (4, 3, 2), (2, 2, 2), "none")
    assert fs == [] and v["agrees"]
    assert v["measured_collective_bytes"] == int(
        v["modeled_words"] * v["itemsize"]
    )


def test_mttkrp_stationary_point_matches_eq12():
    dims, rank, grid, mode = (8, 8, 8), 4, (2, 2, 2), 1
    fs, v = check_mttkrp_stationary(dims, rank, grid, mode)
    assert fs == [] and v["agrees"]
    assert v["measured_collective_bytes"] == mttkrp_model_bytes(
        dims, rank, grid, mode
    )


def test_byte_model_mismatch_fires_on_extra_collective():
    """Known-bad program: a shard_map body with a collective the sweep
    model does not account for must be flagged, not absorbed."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.distributed.mesh import make_abstract_grid_mesh
    from repro.verify.comm import _sds

    mesh = make_abstract_grid_mesh((2, 2))
    fn = shard_map(
        lambda x: jax.lax.psum(x, ("m0", "m1")),
        mesh=mesh, in_specs=P("m0", "m1"), out_specs=P(),
    )
    summ = trace_collectives(fn, (_sds((8, 8)),), dict(mesh.shape))
    assert summ.ring_bytes > 0  # the psum was seen and costed
    fs = check_program_bytes("fixture", summ.ring_bytes, 0, 0)
    assert _rules(fs) == {"byte-model-mismatch"}


def test_below_lower_bound_fires():
    fs = check_program_bytes("fixture", 8, 8, 64)
    assert _rules(fs) == {"below-lower-bound"}


def test_shipped_ring_schedules_are_clean():
    for q in (1, 2, 3, 4, 5, 8):
        assert check_ring_schedules(q) == []


def test_two_cycle_permutation_is_flagged_as_deadlock():
    """The classic bug: stride-2 neighbor exchange on an even ring is
    two disjoint cycles — half the shards never circulate."""
    q = 4
    perm = [(i, (i + 2) % q) for i in range(q)]
    fs = check_ring_permutation(perm, q, "fixture")
    assert _rules(fs) == {"ring-deadlock"}
    assert "cycles" in fs[0].detail
    # a non-permutation (two sources, one destination) is also flagged
    fs = check_ring_permutation([(0, 1), (1, 1), (2, 3), (3, 0)], q, "f")
    assert _rules(fs) == {"ring-deadlock"}


def test_off_by_one_consumer_is_flagged():
    """A consumer reading the chunk one step early references data that
    has not arrived yet — a silent race on real async hardware."""
    fs = check_consumer_schedule(
        4, "fixture", source_fn=lambda me, t, q: (me - t - 1) % q
    )
    assert "read-before-arrival" in _rules(fs)


def test_wrong_reduce_scatter_schedule_is_flagged():
    """A sign-flipped chunk walk deposits the wrong blocks: processor j
    does not end up holding every contribution to block j."""
    fs = check_reduce_scatter_schedule(
        4, "fixture", chunk_fn=lambda me, t, q: (me + t + 1) % q
    )
    assert "ring-reduction-coverage" in _rules(fs)


def test_grid_suboptimal_fires_on_worse_choice(monkeypatch):
    import types

    import repro.distributed.grid_select as gs

    ref = gs.brute_force_stationary((8, 8, 8), 4, 8, mode=None)
    fake = types.SimpleNamespace(grid=(8, 1, 1), words=ref.words * 2 + 1)
    monkeypatch.setattr(
        gs, "select_stationary_grid", lambda *a, **k: fake
    )
    fs = check_grid_selection((8, 8, 8), 4, 8)
    assert _rules(fs) == {"grid-suboptimal"}


def test_verify_comm_subset_clean_without_executing():
    """A reduced lattice through the driver: zero findings, per-program
    verdicts byte-exact, dispatch counter untouched."""
    before = registry().counter(PALLAS_DISPATCHES)
    findings, verdicts = verify_comm(
        cp_cases=(((8, 8, 8), 4, (1, 2, 2)),),
        tucker_cases=(),
        mttkrp_cases=(((8, 8, 8), 4, (2, 2, 2), 0),),
        ring_sizes=(1, 2, 4),
    )
    assert findings == []
    byte_points = [v for v in verdicts
                   if "measured_collective_bytes" in v]
    assert len(byte_points) == 3  # cp x 2 overlaps + 1 mttkrp
    for v in byte_points:
        assert v["agrees"], v
    names = {v["name"] for v in verdicts}
    assert "ring_schedule" in names and "grid_selection" in names
    assert registry().counter(PALLAS_DISPATCHES) == before


# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

def test_narrow_accumulator_fixture_fires():
    """A plain bf16 contraction (no preferred_element_type) accumulates
    narrow — exactly the blocked_host bug this analyzer caught."""
    import jax
    import jax.numpy as jnp

    def bad(a, b):
        return jnp.einsum("ij,jk->ik", a, b)

    def good(a, b):
        return jnp.einsum(
            "ij,jk->ik", a, b, preferred_element_type=jnp.float32
        )

    a = jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)
    closed = jax.make_jaxpr(bad)(a, a)
    fs, sites = check_accumulation(closed, "fixture")
    assert sites and _rules(fs) == {"narrow-accumulator"}
    closed = jax.make_jaxpr(good)(a, a)
    fs, sites = check_accumulation(closed, "fixture")
    assert sites and fs == []


def test_verify_dtypes_clean_without_executing():
    """Acceptance: every backend accumulates fp32 under
    compute_dtype=bfloat16, proven by trace alone."""
    before = registry().counter(PALLAS_DISPATCHES)
    findings, verdicts = verify_dtypes()
    assert findings == []
    names = {v["name"] for v in verdicts}
    assert names == {
        "mttkrp/einsum", "mttkrp/blocked_host", "mttkrp/pallas",
        "multi_ttm/einsum", "multi_ttm/blocked_host", "multi_ttm/pallas",
    }
    for v in verdicts:
        assert v["agrees"] and v["narrow_accumulations"] == 0, v
        # the proof is vacuous unless accumulation sites were found
        assert v["accumulations"] > 0, v
    assert registry().counter(PALLAS_DISPATCHES) == before


# ---------------------------------------------------------------------------
# CLI + trace export
# ---------------------------------------------------------------------------

def test_finding_str_and_dict():
    f = Finding("lint", "RV101", "tune/x.py:3", "falsy or")
    assert str(f) == "[lint:RV101] tune/x.py:3: falsy or"
    assert f.to_dict()["rule"] == "RV101"


def test_cli_rules_exits_zero(capsys):
    assert main(["--rules"]) == 0
    out = capsys.readouterr().out
    assert "RV101" in out and "RV106" in out


def test_cli_unknown_analyzer_exits_two(capsys):
    assert main(["--only", "bogus"]) == 2


def test_cli_selectors_compose(monkeypatch):
    """--comm/--dtypes are selector shorthands; they union with --only
    and with each other (parsing only — the analyzers are stubbed)."""
    import repro.verify.__main__ as vm

    seen = {}

    def fake_run(only, trace_out=None):
        seen["only"] = only
        return [], []

    monkeypatch.setattr(vm, "run", fake_run)
    assert vm.main(["--comm", "--dtypes"]) == 0
    assert seen["only"] == ("comm", "dtypes")
    assert vm.main(["--only", "lint", "--comm"]) == 0
    assert seen["only"] == ("lint", "comm")
    assert vm.main(["--only", "comm", "--comm"]) == 0
    assert seen["only"] == ("comm",)  # no double-run
    assert vm.main([]) == 0
    assert seen["only"] == ("plans", "kernels", "lint", "comm", "dtypes")


def test_cli_dtypes_selector_end_to_end(capsys):
    assert main(["--dtypes"]) == 0
    out = capsys.readouterr().out
    assert "dtypes mttkrp/pallas" in out
    assert "0 finding(s) across dtypes" in out
    assert "6 dtype program(s)" in out


def test_cli_lint_clean_tree_exits_zero(capsys):
    assert main(["--only", "lint"]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_cli_findings_exit_one(monkeypatch, capsys):
    import repro.verify.lint as lint_mod

    monkeypatch.setattr(
        lint_mod, "lint_tree",
        lambda: [Finding("lint", "RV999", "x.py:1", "seeded")],
    )
    assert main(["--only", "lint"]) == 1
    assert "[lint:RV999]" in capsys.readouterr().out


def test_trace_export_schema(tmp_path):
    """--trace-out writes kind=static_verify events in the observe span
    schema, one per kernel verdict plus a summary row the report CLI
    can table (static_verify is in its DISPATCH_KINDS)."""
    from repro.observe.report import DISPATCH_KINDS, render_rows

    p = tmp_path / "verify.jsonl"
    findings, verdicts = run(("kernels",), trace_out=str(p))
    assert findings == []
    events = load_trace(str(p))
    sv = [e for e in events if e["kind"] == "static_verify"]
    assert len(sv) == len(verdicts) + 1  # one per kernel + summary
    summary = sv[-1]
    assert summary["name"] == "summary"
    assert summary["kernels_checked"] == len(verdicts)
    assert summary["kernels_agreeing"] == len(verdicts)
    assert summary["findings"] == 0
    assert "static_verify" in DISPATCH_KINDS
    rows, flagged = render_rows(events)
    assert len(rows) == len(sv) and flagged == 0


def test_comm_trace_export_carries_byte_columns(tmp_path):
    """--trace-out on the comm analyzer exports per-grid verdicts whose
    modeled/bound/measured columns the report CLI tables."""
    from repro.observe.report import render_rows

    p = tmp_path / "comm.jsonl"
    findings, verdicts = run(("comm",), trace_out=str(p))
    assert findings == []
    events = load_trace(str(p))
    sv = [e for e in events if e["kind"] == "static_verify"]
    assert len(sv) == len(verdicts) + 1
    byte_events = [
        e for e in sv if "measured_collective_bytes" in e
    ]
    assert len(byte_events) >= 16  # the full lattice, both overlaps
    for e in byte_events:
        assert e["measured_collective_bytes"] == int(
            e["modeled_words"] * e["itemsize"]
        )
    summary = sv[-1]
    assert summary["comm_points"] == len(verdicts)
    assert summary["findings"] == 0
    rows, flagged = render_rows(events)
    # byte-exact programs sit at exactly 1.00x model: nothing flags
    assert len(rows) == len(sv) and flagged == 0


def test_default_run_matches_cli_contract():
    """run() over all analyzers returns the same clean verdict the CI
    gate requires (python -m repro.verify exits 0 on this tree) —
    including the ISSUE's acceptance floor of >= 8 byte-exact lattice
    points per sweep family, in both overlap modes."""
    findings, verdicts = run()
    assert findings == []
    by: dict = {}
    for v in verdicts:
        by.setdefault(v["analyzer"], []).append(v)
    assert len(by["kernels"]) == 5
    assert len(by["dtypes"]) == 6
    cp = [v for v in by["comm"] if v["name"].startswith("cp_sweep")]
    tucker = [
        v for v in by["comm"] if v["name"].startswith("tucker_sweep")
    ]
    mttkrp = [
        v for v in by["comm"]
        if v["name"].startswith("mttkrp_stationary")
    ]
    assert len(cp) >= 8 and len(tucker) >= 8 and len(mttkrp) >= 4
    assert {v["overlap"] for v in cp} == {"none", "ring"}
    assert {v["overlap"] for v in tucker} == {"none", "ring"}
    for v in cp + tucker + mttkrp:
        assert v["measured_collective_bytes"] == int(
            v["modeled_words"] * v["itemsize"]
        ), v
