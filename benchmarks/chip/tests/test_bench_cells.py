"""Each cell's traffic, entry and reference, end to end at a tiny size on
the CPU (Pallas kernels in interpret mode): the run is correct and prints
the cell's end-to-end metrics."""

import pytest

from _bench_helpers import make_root, run

ONE_CHIP = ["cp3-f32.cube1024", "tucker4-hcci-f32.repeat",
            "cp3-f32.serve256x8"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_is_correct_at_tiny_size(tmp_path, workload):
    r = run(make_root(tmp_path), workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "solve_s", "peak_hbm_gib"}
    assert r["metrics"]["solve_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    for c in r["checks"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1


def test_same_seed_same_inputs(tmp_path):
    """The seed makes the tensor and every solve's initial factors."""
    import jax
    import numpy as np

    from benchlib import data

    k = data.seed_key(2**31 + 99)
    a = data.low_rank_plus_noise(k, (6, 5, 4), 3, 0.1)
    b = data.low_rank_plus_noise(data.seed_key(2**31 + 99), (6, 5, 4), 3, 0.1)
    c = data.low_rank_plus_noise(data.seed_key(2**31 + 98), (6, 5, 4), 3, 0.1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))
    i1 = data.init_factors(jax.random.fold_in(k, 1), (6, 5, 4), 3)
    i2 = data.init_factors(jax.random.fold_in(k, 1), (6, 5, 4), 3)
    for p, q in zip(i1, i2):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


def test_rounds_serve_the_same_sizes_in_a_seeded_order():
    """Every seed serves the mix's shapes, in its own order; each extent
    rounds up to the one 256^3 bucket."""
    import json

    import numpy as np

    from _bench_helpers import CHIP

    t = json.loads((CHIP / "traffic" / "serve256x8.json").read_text())
    assert all(249 <= e <= 256 for s in t["shapes"] for e in s)
    assert len(t["shapes"]) % t["batch"] == 0

    def order(seed):
        return list(np.random.default_rng(seed).permutation(len(t["shapes"])))

    assert order(2**31 + 5) == order(2**31 + 5)
    assert order(2**31 + 5) != order(2**31 + 6)
    assert sorted(order(7)) == list(range(len(t["shapes"])))
