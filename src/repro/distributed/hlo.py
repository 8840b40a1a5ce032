"""Collective extraction from compiled HLO *and* from jaxprs.

Two front ends, one byte-accounting currency (:class:`CollectiveOp` /
:class:`CollectiveSummary`):

* :func:`parse_collectives` extracts every collective op (all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute) from
  ``compiled.as_text()`` — the dynamic path ``tests/dist_worker.py``
  measures on real multi-device meshes.
* :func:`jaxpr_collectives` walks a ``jax.make_jaxpr`` trace of a
  shard_map program for the same primitives — the static path
  (``repro.verify.comm``): it needs no devices at all (an
  ``AbstractMesh`` suffices), so the byte model is provable on a
  single-CPU CI host without compiling or spawning anything.

Bytes are accounted two ways:

* ``operand_bytes`` — sum of operand sizes (the roofline-term convention);
* ``ring_bytes``    — per-device link traffic under ring/bucket algorithms
                      (the paper's §V-C3 model): all-gather (q-1)·w_in,
                      reduce-scatter (q-1)·w_out, all-reduce 2(q-1)/q·w,
                      all-to-all (q-1)/q·w, collective-permute w.

SPMD HLO is a per-device program, so operand shapes are per-device shards —
exactly the paper's "w = max_p nnz" local sizes; inside a shard_map jaxpr
the avals are the same per-shard shapes, which is why both front ends
agree to the byte (``tests/test_verify.py`` pins a few points of each
against the other via the sweep model).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Iterable, Mapping

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\((.*)$"
)
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string (possibly a tuple)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveOp:
    kind: str
    name: str
    operand_bytes: int
    output_bytes: int
    group_size: int

    @property
    def ring_bytes(self) -> int:
        q = max(self.group_size, 1)
        if self.kind == "all-gather":
            return (q - 1) * self.operand_bytes
        if self.kind == "reduce-scatter":
            return (q - 1) * self.output_bytes
        if self.kind == "all-reduce":
            return int(2 * (q - 1) / q * self.operand_bytes)
        if self.kind == "all-to-all":
            return int((q - 1) / q * self.operand_bytes)
        return self.operand_bytes  # collective-permute: one hop


@dataclass
class CollectiveSummary:
    ops: list[CollectiveOp] = field(default_factory=list)

    @property
    def operand_bytes(self) -> int:
        return sum(o.operand_bytes for o in self.ops)

    @property
    def ring_bytes(self) -> int:
        return sum(o.ring_bytes for o in self.ops)

    def by_kind(self) -> dict[str, dict[str, int]]:
        out: dict[str, dict[str, int]] = {}
        for o in self.ops:
            d = out.setdefault(o.kind, {"count": 0, "operand_bytes": 0,
                                        "ring_bytes": 0})
            d["count"] += 1
            d["operand_bytes"] += o.operand_bytes
            d["ring_bytes"] += o.ring_bytes
        return out


def parse_collectives(hlo_text: str) -> CollectiveSummary:
    """Parse collective ops out of (stable-)HLO module text.

    Handles sync and async (``-start``/``-done`` — only starts counted),
    brace and iota replica-group formats, tuple shapes, and resolves operand
    sizes through the instruction table.
    """
    sizes: dict[str, int] = {}
    summary = CollectiveSummary()
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, out_shape, opcode, rest = m.groups()
        sizes[name] = _shape_bytes(out_shape)
        base = opcode
        is_start = False
        if base.endswith("-start"):
            base, is_start = base[:-6], True
        elif base.endswith("-done"):
            continue  # counted at -start
        if base not in COLLECTIVE_KINDS:
            continue
        # resolve operand sizes from %references on the line
        operand_names = re.findall(r"%([\w\.\-]+)", rest.split("),")[0])
        operand_bytes = sum(sizes.get(n, 0) for n in operand_names)
        if operand_bytes == 0:
            # operands printed with inline shapes (unoptimized HLO)
            operand_bytes = _shape_bytes(rest.split(")")[0])
        # group size
        q = 1
        mg = _GROUPS_BRACE_RE.search(line)
        if mg:
            q = len(mg.group(1).split(","))
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                q = int(mi.group(2))
            elif base == "collective-permute":
                q = 2
        out_bytes = sizes[name]
        if is_start and out_bytes == 0:
            out_bytes = operand_bytes
        summary.ops.append(
            CollectiveOp(base, name, operand_bytes, out_bytes, q)
        )
    return summary


def collective_bytes(compiled_or_text: Any) -> int:
    """Prompt-convention collective bytes: sum of operand sizes."""
    text = (
        compiled_or_text
        if isinstance(compiled_or_text, str)
        else compiled_or_text.as_text()
    )
    return parse_collectives(text).operand_bytes


# --------------------------------------------------------------------------
# Jaxpr front end (the static path)
# --------------------------------------------------------------------------

#: jaxpr primitive name -> HLO collective kind. ``psum`` maps to
#: all-reduce (under shard_map it lowers to one); ``psum_invariant`` is
#: the form a ``psum`` takes under shard_map's ``check_vma=True`` — same
#: collective, same bytes; ``ppermute`` to
#: collective-permute. ``pmean`` has no primitive of its own (it traces
#: to psum + divide), so the map is complete for this repo's programs.
JAXPR_COLLECTIVE_PRIMS: dict[str, str] = {
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "psum": "all-reduce",
    "psum_invariant": "all-reduce",
    "ppermute": "collective-permute",
    "all_to_all": "all-to-all",
}


def _aval_bytes(avals: Iterable[Any]) -> int:
    total = 0
    for aval in avals:
        if not hasattr(aval, "shape"):  # e.g. AbstractToken
            continue
        import jax.numpy as jnp  # local: keep the HLO path jax-light

        total += int(math.prod(aval.shape)) * jnp.dtype(aval.dtype).itemsize
    return total


def _group_size(prim: str, params: Mapping[str, Any],
                axis_sizes: Mapping[str, int]) -> int:
    if prim in ("all_gather", "reduce_scatter", "all_to_all"):
        return int(params["axis_size"])
    if prim in ("psum", "psum_invariant"):
        q = 1
        for a in params.get("axes", ()):
            if isinstance(a, str):
                q *= int(axis_sizes.get(a, 1))
        return q
    return 2  # ppermute: group size is unused by its ring_bytes rule


def _walk_jaxpr(jaxpr: Any, axis_sizes: Mapping[str, int],
                ops: list[CollectiveOp], repeat: int) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in JAXPR_COLLECTIVE_PRIMS:
            op = CollectiveOp(
                JAXPR_COLLECTIVE_PRIMS[prim],
                prim,
                _aval_bytes(v.aval for v in eqn.invars),
                _aval_bytes(v.aval for v in eqn.outvars),
                _group_size(prim, eqn.params, axis_sizes),
            )
            ops.extend([op] * repeat)
        # recurse into nested jaxprs (pjit/shard_map/cond/scan params
        # carry ClosedJaxpr, raw Jaxpr, or sequences of either)
        inner_repeat = repeat
        if prim == "scan":
            inner_repeat = repeat * int(eqn.params.get("length", 1))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(sub, "jaxpr"):  # ClosedJaxpr
                    _walk_jaxpr(sub.jaxpr, axis_sizes, ops, inner_repeat)
                elif hasattr(sub, "eqns"):  # raw Jaxpr
                    _walk_jaxpr(sub, axis_sizes, ops, inner_repeat)


def jaxpr_collectives(closed_jaxpr: Any,
                      axis_sizes: Mapping[str, int]) -> CollectiveSummary:
    """Every collective primitive in a (closed) jaxpr, recursively.

    ``axis_sizes`` maps mesh axis names to sizes (``dict(mesh.shape)``) —
    needed because a ``psum`` eqn records axis *names*, not sizes. Avals
    inside a shard_map body are per-shard, so the resulting
    :class:`CollectiveSummary` uses exactly the same "w = local words"
    convention as the HLO front end, and ``ring_bytes`` is directly
    comparable to the §V-C3 sweep models. ``scan`` bodies are counted
    ``length`` times; this repo's sweep programs are fully unrolled, so
    the multiplier is exercised only defensively.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    summary = CollectiveSummary()
    _walk_jaxpr(jaxpr, axis_sizes, summary.ops, 1)
    return summary
