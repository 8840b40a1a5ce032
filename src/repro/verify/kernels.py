"""Static Pallas kernel analysis: prove BlockSpec properties, run nothing.

The analyzer never executes a kernel body.  It monkeypatches
``pallas_call`` with a recorder that captures ``(grid, in_specs,
out_specs, out_shape, operand shapes)`` and returns zeros of the output
aval, then traces each shipped kernel wrapper under ``jax.eval_shape``
— so the capture costs one abstract trace, no FLOPs, no memory traffic.

Because every grid dimension and every BlockSpec index map in this repo
is *static* (plain Python over grid indices), the maps can be evaluated
concretely over the full grid.  That turns schedule claims into theorems
checked by enumeration:

* **coverage** — every output block is visited; no block origin is out
  of bounds (origins are in block units, Pallas "blocked indexing");
* **write-once** — an output block's visits form one contiguous run in
  grid iteration order (last dimension innermost), i.e. the
  output-stationary accumulation completes before the block is written
  back, and each block is written back exactly once;
* **footprint** — the summed VMEM words of all BlockSpec tiles equal the
  planner's :meth:`~repro.engine.plan.BlockPlan.kernel_block_words`
  claim (the BlockSpec share of the Eq-9 working set; the in-kernel
  weight scratch is ``weight_scratch_words``);
* **accumulator dtype** — the kernel output aval stays fp32 even when
  the inputs are bf16 (the mixed-precision policy's invariant).
"""

from __future__ import annotations

import itertools
import math
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Sequence

from . import Finding


@dataclass(frozen=True)
class SpecCapture:
    """One captured BlockSpec: static block shape + its index map."""

    block_shape: tuple[int, ...]
    index_map: Callable[..., tuple[int, ...]]
    operand_shape: tuple[int, ...]


@dataclass
class KernelCapture:
    """Everything one ``pallas_call`` declared, captured without running."""

    grid: tuple[int, ...]
    in_specs: tuple[SpecCapture, ...] = ()
    out_specs: tuple[SpecCapture, ...] = ()
    out_dtypes: tuple[Any, ...] = ()
    name: str = "pallas_call"
    extras: dict = field(default_factory=dict)

    @property
    def block_footprint_words(self) -> int:
        """Summed VMEM words of every operand + output tile — the
        BlockSpec share of the Eq-9 working set."""
        return sum(
            math.prod(s.block_shape)
            for s in self.in_specs + self.out_specs
        )


@contextmanager
def capture_pallas_calls() -> Iterator[list[KernelCapture]]:
    """Patch ``jax.experimental.pallas.pallas_call`` with a recorder that
    returns zeros of the declared output aval (trace under
    ``jax.eval_shape`` so nothing materializes).  Yields the capture
    list; restores the real ``pallas_call`` on exit.

    The kernel launchers keep one trace per signature, so their caches
    are cleared on entry (a launcher traced before would never reach the
    recorder) and on exit (no later call may reuse a recorded trace)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ..kernels.common import clear_launcher_caches

    records: list[KernelCapture] = []
    real = pl.pallas_call

    def fake_pallas_call(
        kernel: Callable,
        *,
        grid: Sequence[int],
        in_specs: Sequence[Any],
        out_specs: Any,
        out_shape: Any,
        **kwargs: Any,
    ) -> Callable:
        outs = out_shape if isinstance(out_shape, (tuple, list)) \
            else (out_shape,)
        ospecs = out_specs if isinstance(out_specs, (tuple, list)) \
            else (out_specs,)

        def runner(*operands: Any) -> Any:
            records.append(KernelCapture(
                grid=tuple(int(g) for g in grid),
                in_specs=tuple(
                    SpecCapture(
                        tuple(int(b) for b in s.block_shape),
                        s.index_map,
                        tuple(int(d) for d in op.shape),
                    )
                    for s, op in zip(in_specs, operands)
                ),
                out_specs=tuple(
                    SpecCapture(
                        tuple(int(b) for b in s.block_shape),
                        s.index_map,
                        tuple(int(d) for d in o.shape),
                    )
                    for s, o in zip(ospecs, outs)
                ),
                out_dtypes=tuple(
                    jnp.dtype(o.dtype).name for o in outs
                ),
                name=getattr(kernel, "__name__", repr(kernel)),
            ))
            zeros = tuple(jnp.zeros(o.shape, o.dtype) for o in outs)
            return zeros if isinstance(out_shape, (tuple, list)) \
                else zeros[0]

        return runner

    clear_launcher_caches()
    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = real
        clear_launcher_caches()


def _iter_grid(grid: tuple[int, ...]) -> Iterator[tuple[int, ...]]:
    # Pallas iterates the grid row-major: last dimension innermost.
    return itertools.product(*(range(g) for g in grid))


def _check_spec(
    cap: KernelCapture,
    spec: SpecCapture,
    *,
    kernel: str,
    role: str,
    require_coverage: bool,
) -> list[Finding]:
    """Evaluate one spec's index map over the full grid: in-bounds block
    origins always; for outputs additionally coverage + contiguous
    visit runs (accumulate-then-write-once)."""
    out: list[Finding] = []
    sub = f"{kernel}:{role}"
    nblocks = []
    for d, (extent, b) in enumerate(zip(spec.operand_shape, spec.block_shape)):
        if b < 1 or extent % b != 0:
            out.append(Finding(
                "kernels", "block-divisibility", sub,
                f"dim {d}: block {b} does not tile the (padded) operand "
                f"extent {extent}",
            ))
            return out
        nblocks.append(extent // b)

    visits: dict[tuple[int, ...], list[int]] = {}
    for step, idx in enumerate(_iter_grid(cap.grid)):
        try:
            origin = tuple(int(v) for v in spec.index_map(*idx))
        except Exception as e:  # non-static or arity-broken index map
            out.append(Finding(
                "kernels", "index-map", sub,
                f"index map failed on grid index {idx}: {e!r}",
            ))
            return out
        if len(origin) != len(spec.block_shape):
            out.append(Finding(
                "kernels", "index-map", sub,
                f"index map returned {len(origin)} coords for a "
                f"{len(spec.block_shape)}-dim block at grid index {idx}",
            ))
            return out
        if any(not 0 <= o < n for o, n in zip(origin, nblocks)):
            out.append(Finding(
                "kernels", "oob-origin", sub,
                f"grid index {idx} maps to block origin {origin} outside "
                f"the {tuple(nblocks)} block grid",
            ))
            return out
        visits.setdefault(origin, []).append(step)

    if require_coverage:
        missing = [
            o for o in itertools.product(*(range(n) for n in nblocks))
            if o not in visits
        ]
        if missing:
            out.append(Finding(
                "kernels", "coverage-gap", sub,
                f"{len(missing)} of {math.prod(nblocks)} output blocks "
                f"never written (first missing: {missing[0]})",
            ))
        for origin, steps in visits.items():
            if steps[-1] - steps[0] != len(steps) - 1:
                out.append(Finding(
                    "kernels", "noncontiguous-revisit", sub,
                    f"output block {origin} is revisited at "
                    f"non-consecutive grid steps (first gap after step "
                    f"{steps[0]}): the accumulation run is torn, so the "
                    f"block is written back more than once",
                ))
                break
    return out


def check_capture(
    cap: KernelCapture,
    *,
    kernel: str,
    claimed_block_words: int | None = None,
    expect_acc_dtype: str = "float32",
) -> list[Finding]:
    """All static checks for one captured ``pallas_call``."""
    out: list[Finding] = []
    if any(g < 1 for g in cap.grid):
        out.append(Finding(
            "kernels", "grid", kernel, f"degenerate grid {cap.grid}",
        ))
        return out
    for i, spec in enumerate(cap.in_specs):
        out += _check_spec(
            cap, spec, kernel=kernel, role=f"in[{i}]",
            require_coverage=False,
        )
    for i, spec in enumerate(cap.out_specs):
        out += _check_spec(
            cap, spec, kernel=kernel, role=f"out[{i}]",
            require_coverage=True,
        )
    for i, dt in enumerate(cap.out_dtypes):
        if dt != expect_acc_dtype:
            out.append(Finding(
                "kernels", "acc-dtype", f"{kernel}:out[{i}]",
                f"accumulator dtype is {dt}, policy requires "
                f"{expect_acc_dtype}",
            ))
    if claimed_block_words is not None:
        got = cap.block_footprint_words
        if got != claimed_block_words:
            out.append(Finding(
                "kernels", "footprint-mismatch", kernel,
                f"BlockSpec footprint {got} words != planner claim "
                f"{claimed_block_words} words "
                f"(kernel_block_words)",
            ))
    return out


# ---------------------------------------------------------------------------
# The shipped-kernel catalog
# ---------------------------------------------------------------------------

def _capture_one(fn: Callable, *args: Any) -> KernelCapture:
    """Trace ``fn(*args)`` under ``jax.eval_shape`` with the recorder
    patched in; exactly one ``pallas_call`` must fire."""
    import jax

    with capture_pallas_calls() as records:
        jax.eval_shape(fn, *args)
    if len(records) != 1:
        raise AssertionError(
            f"expected exactly one pallas_call, captured {len(records)}"
        )
    return records[0]


def kernel_cases() -> list[dict]:
    """One entry per shipped Pallas kernel: a traceable wrapper call on a
    bf16 problem sized to give a multi-block grid, plus the planner's
    ``kernel_block_words`` claim the captured footprint must equal.

    The 3-way case routes through ``choose_blocks`` against a small
    *abstract* memory (the production path); the others pin explicit
    block sizes chosen so every grid dimension — including the innermost
    contraction sweeps — has more than one block, exercising the
    coverage and accumulation-run checks for real."""
    import jax
    import jax.numpy as jnp

    from ..engine.plan import (
        BlockPlan,
        Memory,
        MultiTTMPlan,
        choose_blocks,
        fused_pair_kernel_block_words,
    )
    from ..kernels import ops, sweep

    def sds(*shape: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    cases: list[dict] = []

    shape, rank = (24, 10, 12), 7
    plan3 = choose_blocks(shape, rank, memory=Memory.abstract(768))
    cases.append({
        "name": "mttkrp3",
        "fn": lambda x, a, b: ops.mttkrp_canonical_pallas(
            x, [a, b], plan=plan3, interpret=True
        ),
        "args": (sds(*shape), sds(shape[1], rank), sds(shape[2], rank)),
        "claim": plan3.kernel_block_words(),
        "plan": plan3,
    })

    shape4, rank4 = (8, 4, 5, 6), 5
    plan4 = BlockPlan(4, (2, 5, 3), 2)
    cases.append({
        "name": "mttkrpn",
        "fn": lambda x, f1, f2, f3: ops.mttkrp_canonical_pallas(
            x, [f1, f2, f3], plan=plan4, interpret=True, variant="generic"
        ),
        "args": (
            sds(*shape4),
            sds(shape4[1], rank4), sds(shape4[2], rank4),
            sds(shape4[3], rank4),
        ),
        "claim": plan4.kernel_block_words(),
        "plan": plan4,
    })

    pshape, prank = (12, 4, 6), 5
    pplan = BlockPlan(6, (2, 3), 2, x_has_rank=True)
    cases.append({
        "name": "mttkrp_partial",
        "fn": lambda node, f1, f2: ops.mttkrp_partial_canonical_pallas(
            node, [f1, f2], plan=pplan, interpret=True
        ),
        "args": (
            sds(*pshape, prank),
            sds(pshape[1], prank), sds(pshape[2], prank),
        ),
        "claim": pplan.kernel_block_words(),
        "plan": pplan,
    })

    tshape, tranks = (16, 6, 10), (3, 2)
    tplan = MultiTTMPlan(8, (3, 5), tranks)
    cases.append({
        "name": "multi_ttm",
        "fn": lambda x, m1, m2: ops.multi_ttm_canonical_pallas(
            x, [m1, m2], plan=tplan, interpret=True
        ),
        "args": (
            sds(*tshape),
            sds(tshape[1], tranks[0]), sds(tshape[2], tranks[1]),
        ),
        "claim": tplan.kernel_block_words(),
        "plan": tplan,
    })

    sshape, srank = (12, 6, 8), 5
    splan = BlockPlan(4, (3, 4), 2)
    cases.append({
        "name": "fused_pair",
        "fn": lambda x, f1, f2: sweep.fused_pair_canonical_pallas(
            x, [f1, f2], plan=splan, interpret=True
        ),
        "args": (
            sds(*sshape), sds(sshape[1], srank), sds(sshape[2], srank),
        ),
        "claim": fused_pair_kernel_block_words(splan),
        "plan": splan,
    })
    return cases


def verify_kernels() -> tuple[list[Finding], list[dict]]:
    """Statically verify every shipped Pallas kernel.

    Returns ``(findings, verdicts)`` — one verdict dict per kernel with
    the captured grid, the BlockSpec footprint, the planner claim, and
    whether they agree; suitable for ``kind="static_verify"`` trace
    events.  No kernel is ever executed (the capture runs under
    ``jax.eval_shape`` with ``pallas_call`` replaced)."""
    findings: list[Finding] = []
    verdicts: list[dict] = []
    for case in kernel_cases():
        name = case["name"]
        try:
            cap = _capture_one(case["fn"], *case["args"])
        except Exception as e:
            findings.append(Finding(
                "kernels", "capture-failed", name,
                f"tracing the wrapper under eval_shape failed: {e!r}",
            ))
            continue
        fs = check_capture(
            cap, kernel=name, claimed_block_words=case["claim"],
        )
        findings += fs
        verdicts.append({
            "name": name,
            "grid": list(cap.grid),
            "footprint_words": cap.block_footprint_words,
            "claimed_words": case["claim"],
            "working_set_words": case["claim"]
            + case["plan"].weight_scratch_words(),
            "agrees": cap.block_footprint_words == case["claim"],
            "findings": len(fs),
        })
    return findings, verdicts
