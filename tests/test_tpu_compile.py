"""Ahead-of-time compiles of every Pallas kernel for a TPU v5e.

Each test lowers one kernel wrapper with ``interpret=False`` against a
*described* ``v5e:2x2`` topology and compiles it with the TPU compiler
installed next to jax; nothing runs.  That catches what interpret mode
cannot: Mosaic's layout refusals (a reshape across the (sublane, lane)
tile, a block shape off the (8, 128) grid), scoped-VMEM exhaustion, and
programs that do not fit the chip's HBM.  Shapes are those
``chip_smoke.py`` runs on the chip.

The topology is described inside a module fixture, never at import
time, so only the test worker given this file loads the TPU library.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.sweep import fused_pair_canonical_pallas


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2 slice, with the persistent
    compile cache off (a compile for a described chip is written to it
    but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for f32 arrays of ``shapes`` (tuples; a list of
    tuples becomes a list of arrays) on the described chip; returns the
    compiled text."""
    def sds(s):
        if isinstance(s, list):
            return [sds(t) for t in s]
        return jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=chip)

    compiled = jax.jit(fn).lower(*[sds(s) for s in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


def _factors(shape, rank, skip=None):
    return [(s, rank) for k, s in enumerate(shape) if k != skip]


def test_mttkrp3_compiles(chip):
    shape, rank = (1024, 1024, 1024), 64
    _compile(
        chip,
        lambda x, fs: ops.mttkrp_canonical_pallas(
            x, fs, interpret=False, variant="specialized"
        ),
        shape, _factors(shape, rank, skip=0),
    )


@pytest.mark.parametrize("mode", [0, 1, 2])
@pytest.mark.parametrize("shape", [(1024, 1024, 1024), (8, 256, 256, 256)])
def test_mttkrp3_reads_x_in_place(chip, shape, mode):
    """The jitted engine MTTKRP of every mode, alone and batched (a
    leading B = 8 with per-element factors, as the server's bucket runs
    it), holds no tensor-sized temporary: X reaches the kernel without a
    transposed or padded copy."""
    import repro

    ctx = repro.ExecutionContext.create(backend="pallas", interpret=False)
    rank = 64
    lead = shape[:-3]

    def sds(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)

    compiled = jax.jit(
        lambda x, fs: repro.mttkrp(x, fs, mode, ctx=ctx)
    ).lower(
        sds(shape), [sds(lead + (s, rank)) for s in shape[-3:]]
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    tensor_bytes = 4 * math.prod(shape)
    element_bytes = 4 * math.prod(shape[-3:])
    assert compiled.memory_analysis().temp_size_in_bytes < element_bytes // 8
    assert compiled.memory_analysis().output_size_in_bytes < tensor_bytes // 64


@pytest.mark.parametrize(
    "shape,rank,mode",
    [((256, 256, 256, 32), 32, m) for m in range(4)]
    + [((64, 32, 32, 32, 16), 16, m) for m in range(5)],
)
def test_mttkrpn_compiles(chip, shape, rank, mode):
    _compile(
        chip,
        functools.partial(
            lambda x, fs, mode: ops.mttkrp_pallas(
                x, fs, mode, interpret=False, variant="generic"
            ),
            mode=mode,
        ),
        shape, _factors(shape, rank),
    )


def test_partial_kernel_compiles(chip):
    """A dimension-tree node (1024, 1024, 64): the rank-augmented tile
    once ran out of scoped VMEM."""
    node, rank = (1024, 1024), 64
    _compile(
        chip,
        lambda n, fs: ops.mttkrp_partial_canonical_pallas(
            n, fs, interpret=False
        ),
        node + (rank,), _factors(node, rank, skip=0),
    )


@pytest.mark.parametrize(
    "shape,rank", [((1024, 1024, 1024), 64), ((256, 256, 128, 32), 32)]
)
def test_fused_pair_compiles(chip, shape, rank):
    _compile(
        chip,
        lambda x, fs: fused_pair_canonical_pallas(x, fs, interpret=False),
        shape, _factors(shape, rank, skip=0),
    )


@pytest.mark.parametrize(
    "shape,ranks",
    [((1024, 1024, 1024), (32, 32, 32)), ((256, 256, 256, 32), (16, 16, 16, 8))],
)
@pytest.mark.parametrize("keep", ["first", "last", "core"])
def test_multi_ttm_compiles(chip, shape, ranks, keep):
    """Through the engine: kept mode 0, kept mode N-1 (a transpose, the
    kernel, and the inverse transpose), and the full core (the kernel on
    the trailing N-1 modes, then one small matmul)."""
    import repro

    ctx = repro.ExecutionContext.create(backend="pallas", interpret=False)
    k = {"first": 0, "last": len(shape) - 1, "core": None}[keep]
    _compile(
        chip,
        lambda x, ms: repro.multi_ttm(x, ms, k, ctx=ctx),
        shape, [(s, r) for s, r in zip(shape, ranks)],
    )
