"""Pallas MTTKRP kernel vs pure-jnp oracle: shape/dtype sweeps (interpret
mode — kernel-body semantics executed on CPU), block-plan properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.engine.execute import mttkrp
from repro.kernels.common import clear_launcher_caches
from repro.kernels.mttkrp3 import mttkrp3_pallas
from repro.kernels.mttkrpn import mttkrp_partial_pallas, mttkrpn_pallas
from repro.kernels.multi_ttm import multi_ttm_keep_pallas
from repro.kernels.ops import (
    VMEM_BUDGET,
    BlockPlan,
    choose_blocks,
    mttkrp_pallas,
    mttkrp_traffic_model,
)
from repro.kernels.ref import mttkrp_ref
from repro.kernels.sweep import mttkrp_fused_pair_pallas
from repro.observe.metrics import LAUNCHER_TRACES, TENSOR_RELAYOUTS, registry

# interpret-mode kernel sweeps dominate the suite's wall time
pytestmark = pytest.mark.slow

SHAPES_3 = [
    (8, 8, 8),
    (16, 4, 32),
    (5, 7, 9),          # nothing aligned
    (1, 3, 2),          # degenerate
    (130, 6, 200),      # crosses block boundaries
    (64, 64, 64),
]
SHAPES_4 = [(4, 5, 6, 3), (9, 3, 3, 10), (8, 8, 8, 8)]


def _mk(dims, rank, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    kx, *kf = jax.random.split(key, len(dims) + 1)
    x = jax.random.normal(kx, dims, dtype)
    fs = [jax.random.normal(k, (d, rank), dtype) for k, d in zip(kf, dims)]
    return x, fs


# (dims, rank, mode, form): every shape of SHAPES_3 at three ranks in
# every mode (aligned shapes such as (8, 8, 8) and (64, 64, 64) run with
# no relayout, the others with one pad), the zero-padding case, and the
# batched vmap form with per-element and with shared factors
CASES_3 = [
    (dims, rank, mode, "single")
    for dims in SHAPES_3 for rank in (1, 4, 16) for mode in range(3)
] + [((7, 7, 7), 3, 1, "single")] + [
    (dims, 4, mode, form)
    for dims in ((8, 8, 8), (5, 12, 9)) for mode in range(3)
    for form in ("per_element", "shared")
]


@pytest.mark.parametrize("dims,rank,mode,form", CASES_3)
def test_kernel3_all_modes(dims, rank, mode, form):
    """The 3-way kernel reads X in its stored layout for every output
    mode: it matches the einsum MTTKRP, and at most one relayout (a pad,
    never a transpose) reaches X."""
    batch = 3
    if form == "single":
        x, fs = _mk(dims, rank)
    else:
        x, fs = _mk((batch,) + dims, rank, seed=mode)
        if form == "per_element":
            fs = [
                jax.random.normal(jax.random.PRNGKey(9 + k), (batch, d, rank))
                for k, d in enumerate(dims)
            ]
        else:
            fs = fs[1:]
    before = registry().snapshot()
    if form == "single":
        out = mttkrp_pallas(x, fs, mode, interpret=True)
        want = mttkrp_ref(x, fs, mode)
    else:
        axes = 0 if form == "per_element" else None
        out = jax.vmap(
            lambda xb, *fb: mttkrp_pallas(xb, fb, mode, interpret=True),
            in_axes=(0,) + (axes,) * 3,
        )(x, *fs)
        want = jnp.stack([
            mttkrp_ref(x[b], [f[b] if axes == 0 else f for f in fs], mode)
            for b in range(batch)
        ])
    assert registry().delta(before).get(TENSOR_RELAYOUTS, 0) <= 1
    assert out.shape == want.shape
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dims", SHAPES_4)
def test_kernel4_all_modes(dims):
    x, fs = _mk(dims, 5, seed=1)
    for mode in range(4):
        out = mttkrp_pallas(x, fs, mode, interpret=True)
        np.testing.assert_allclose(
            out, mttkrp_ref(x, fs, mode), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize(
    "dtype,rtol",
    [(jnp.float32, 2e-4), (jnp.bfloat16, 5e-2)],
)
def test_kernel_dtypes(dtype, rtol):
    x, fs = _mk((24, 16, 32), 8, seed=2, dtype=dtype)
    out = mttkrp_pallas(x, fs, 0, interpret=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        out.astype(jnp.float32), mttkrp_ref(x, fs, 0), rtol=rtol, atol=rtol
    )


def test_kernel_explicit_plans():
    """Sweep explicit block plans (the kernel must be correct for any
    feasible tiling, not just the auto-chosen one)."""
    x, fs = _mk((32, 24, 40), 12, seed=3)
    for plan in [
        BlockPlan(8, (8, 128), 128),
        BlockPlan(16, (8, 128), 128),
        BlockPlan(32, (16, 128), 128),
        BlockPlan(128, (8, 256), 128),
    ]:
        out = mttkrp_pallas(x, fs, 0, interpret=True, plan=plan)
        np.testing.assert_allclose(
            out, mttkrp_ref(x, fs, 0), rtol=2e-4, atol=2e-4
        )


@settings(max_examples=20, deadline=None)
@given(
    d1=st.integers(1, 40),
    d2=st.integers(1, 24),
    d3=st.integers(1, 40),
    rank=st.integers(1, 20),
    seed=st.integers(0, 1000),
)
def test_property_kernel_any_shape(d1, d2, d3, rank, seed):
    x, fs = _mk((d1, d2, d3), rank, seed=seed)
    mode = seed % 3
    out = mttkrp_pallas(x, fs, mode, interpret=True)
    np.testing.assert_allclose(
        out, mttkrp_ref(x, fs, mode), rtol=5e-4, atol=5e-4
    )


@settings(max_examples=30, deadline=None)
@given(
    d1=st.integers(1, 4096),
    d2=st.integers(1, 4096),
    d3=st.integers(1, 4096),
    rank=st.integers(1, 2048),
)
def test_property_block_plan_fits_vmem(d1, d2, d3, rank):
    """Eq-9 analogue: the chosen working set always fits the VMEM budget and
    blocks respect TPU alignment floors — or cover the full (sub-unit)
    extent, in which case the padded array is its own size and alignment
    is moot (the degenerate-input fix)."""
    plan = choose_blocks((d1, d2, d3), rank)
    assert plan.working_set_words() * 4 <= VMEM_BUDGET
    assert plan.block_i % 8 == 0 or plan.block_i >= d1
    assert plan.block_r % 128 == 0 or plan.block_r >= rank


def test_traffic_model_tensor_dominated():
    """For small R the kernel is tensor-read dominated (reads X ~once),
    matching the paper's sequential analysis O(I + NIR/M^{1-1/N})."""
    dims, rank = (512, 512, 512), 64
    plan = choose_blocks(dims, rank)
    m = mttkrp_traffic_model(dims, rank, plan)
    x_bytes = 512 ** 3 * 4
    assert m["x_bytes"] == x_bytes  # exactly one pass (gr == 1)
    assert m["total_bytes"] < 1.5 * x_bytes


def test_traffic_model_rank_tiling():
    """Large R forces r-tiling: tensor re-read once per r-tile."""
    dims, rank = (256, 256, 256), 2048
    plan = choose_blocks(dims, rank)
    m = mttkrp_traffic_model(dims, rank, plan)
    gr = -(-2048 // plan.block_r)
    assert m["x_bytes"] == 256 ** 3 * 4 * gr


def test_kernel_jit_compatible():
    x, fs = _mk((16, 16, 16), 4, seed=5)

    @jax.jit
    def f(x, f1, f2):
        return mttkrp_pallas(x, [None, f1, f2], 0, interpret=True)

    out = f(x, fs[1], fs[2])
    np.testing.assert_allclose(out, mttkrp_ref(x, fs, 0), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Each launcher is traced, lowered and compiled once per static signature
# ---------------------------------------------------------------------------

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

LAUNCHERS = [
    "mttkrp3-0", "mttkrp3-1", "mttkrp3-2",
    "mttkrpn", "mttkrp_partial", "multi_ttm", "sweep",
]


@pytest.fixture
def lowerings():
    """How many programs JAX has lowered since the test started."""
    seen = []

    def listen(event, secs, **kwargs):
        if event == LOWERING_EVENT:
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield lambda: len(seen)
    jax.monitoring.unregister_event_duration_listener(listen)


def _traces(before) -> float:
    return registry().delta(before).get(LAUNCHER_TRACES, 0)


def _launch(name, rows, block_i):
    """``(launcher, operands, static keywords)`` of one pre-padded launch
    with ``rows`` output rows tiled ``block_i`` at a time."""
    def rnd(*shape):
        return jax.random.normal(jax.random.PRNGKey(sum(shape)), shape)

    if name.startswith("mttkrp3"):
        mode = int(name[-1])
        shape, blocks = [8, 8, 8], [8, 8, 8]
        shape[mode], blocks[mode] = rows, block_i
        fs = [rnd(shape[k], 4) for k in range(3) if k != mode]
        return mttkrp3_pallas, (rnd(*shape), fs, mode), dict(
            blocks=blocks, block_r=4
        )
    tiles = dict(block_i=block_i, block_contract=(4, 4), block_r=4)
    if name == "mttkrpn":
        tiles["block_contract"] = (4, 4, 4)
        return mttkrpn_pallas, (rnd(rows, 4, 4, 4), [rnd(4, 4)] * 3), tiles
    if name == "mttkrp_partial":
        return mttkrp_partial_pallas, (
            rnd(rows, 4, 4, 4), [rnd(4, 4)] * 2
        ), tiles
    if name == "multi_ttm":
        del tiles["block_r"]
        return multi_ttm_keep_pallas, (
            rnd(rows, 4, 4), [rnd(4, 2), rnd(4, 3)]
        ), tiles
    return mttkrp_fused_pair_pallas, (rnd(rows, 4, 4), [rnd(4, 4)] * 2), tiles


@pytest.mark.parametrize("name", LAUNCHERS)
def test_launcher_built_once_per_signature(name, lowerings):
    """A second eager call at one signature lowers nothing and returns
    what the first did; a new shape, then new blocks, trace the launcher
    once more each."""
    clear_launcher_caches()
    fn, args, kw = _launch(name, 8, 8)
    before = registry().snapshot()
    start = lowerings()
    first = jax.block_until_ready(fn(*args, interpret=True, **kw))
    assert lowerings() > start
    assert _traces(before) == 1
    lowered = lowerings()
    second = fn(*args, interpret=True, **kw)
    assert lowerings() == lowered
    assert _traces(before) == 1
    jax.tree.map(np.testing.assert_array_equal, first, second)
    for want, (rows, block_i) in ((2, (16, 8)), (3, (8, 4))):
        fn, args, kw = _launch(name, rows, block_i)
        fn(*args, interpret=True, **kw)
        assert _traces(before) == want


def test_batched_launch_built_once(lowerings):
    """The served path's shape: the engine's eager ``vmap`` over a
    leading batch axis reuses the batched launch program."""
    ctx = repro.ExecutionContext.create(backend="pallas", interpret=True)
    x, fs = _mk((3, 8, 8, 8), 4, seed=11)
    fs = fs[1:]
    clear_launcher_caches()
    first = jax.block_until_ready(mttkrp(x, fs, 1, ctx=ctx))
    lowered = lowerings()
    before = registry().snapshot()
    second = mttkrp(x, fs, 1, ctx=ctx)
    assert lowerings() == lowered
    assert _traces(before) == 0
    np.testing.assert_array_equal(first, second)
    want = jnp.stack([mttkrp_ref(x[b], fs, 1) for b in range(3)])
    np.testing.assert_allclose(second, want, rtol=2e-4, atol=2e-4)


def test_launcher_traces_count_one_per_signature():
    """``kernels.launcher_traces`` counts the launcher's traces: N eager
    calls at one signature count 1."""
    x, fs = _mk((8, 16, 8), 4, seed=12)
    clear_launcher_caches()
    before = registry().snapshot()
    for _ in range(5):
        mttkrp_pallas(x, fs, 1, interpret=True)
    assert _traces(before) == 1
