"""The jax mesh and ``shard_map`` calls every module of this repo shares.

All mesh and ``shard_map`` construction goes through here, so the axis
types and the replication-check policy are set in one place.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = True):
    """``jax.shard_map`` with ``check_rep`` naming its ``check_vma`` check
    (the static varying-manual-axes analysis that proves ``out_specs``
    replication).  Bodies that hold a ``pallas_call`` pass ``False``: its
    ``out_shape`` carries no manual-axes type, which the check rejects."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep,
    )


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when XLA reports
    nothing)."""
    return dict(compiled.cost_analysis() or {})


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(names), axis_types=(AxisType.Auto,) * len(names)
    )


def make_abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """Device-free ``jax.sharding.AbstractMesh``.

    An abstract mesh carries only the logical grid — enough to trace a
    ``shard_map`` program with ``jax.make_jaxpr`` on a single-device host
    (the AOT path ``repro.verify.comm`` uses), never to run it.
    """
    return AbstractMesh(tuple(shape), tuple(names))
