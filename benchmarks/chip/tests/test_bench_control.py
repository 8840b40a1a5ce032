"""The control: the plain reference computed one precision step down
(``high``, three bf16 passes, where the configurations state fp32 at
``highest``) and put in the program's place.

At the cells' own sizes on the chip the control fails every cell's limits
(its readings are in PERF.md).  At the size a test run holds, on the CPU,
the three-pass error is smaller than on the chip, so this test holds the
control to what it can show there: every compared number reads at least
three times what the program reads on the same inputs, and the CP cube's
numbers fail their limits."""

import pytest

from _bench_helpers import JaxConfigGuard, make_root

ONE_CHIP = ["cp3-f32.cube1024", "tucker4-hcci-f32.repeat",
            "cp3-f32.serve256x8"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_reads_worse_than_the_program(tmp_path, workload):
    import jax

    from benchlib import faults, harness, spec

    root = make_root(tmp_path)
    cell = spec.find_cell(root, workload)
    seed = 2**31 + 21
    with JaxConfigGuard():
        cache = harness.setup_jax(root)
        drv = spec.make_driver(cell, seed, jax.devices()[:1],
                               {"interpret": None,
                                "compilation_cache": cache})
        with jax.default_matmul_precision("highest"):
            drv.setup()
            drv.run_unit()
            drv.release()
            sound, _, _ = harness.compare(cell, drv, seed)
            ctrl, _, _ = harness.compare(cell, drv, seed,
                                         stand_in=faults.control)
    assert harness.judge(cell, sound)[0], sound
    for name in cell.limits:
        if name != "sweeps_gap":   # exact: the control runs every sweep
            assert ctrl[name] >= 3 * sound[name], (name, ctrl, sound)
    if workload == "cp3-f32.cube1024":
        assert not harness.judge(cell, ctrl)[0], ctrl
