"""ExecutionContext: the one immutable configuration object for the stack.

The paper's thesis is that a single machine description — fast-memory size
M, processor count P, the processor grid — determines the optimal schedule
for *every* MTTKRP in a CP run (Eq 9/10 sequentially, Eq 12/16 in
parallel).  Three PRs in, that machine description had fragmented into a
kwarg soup: every driver (``engine.execute.mttkrp``, ``contract_partial``,
the dimension tree, ``cp_als``/``cp_gradient``, Algorithms 3/4, the
distributed sweep) re-declared and re-validated
``backend/memory/interpret/tune/mesh/grid/procs`` with drifting
error messages.  This module replaces all of that:

* :class:`ExecutionContext` — a frozen, hashable dataclass bundling the
  full execution environment: backend choice, :class:`~.plan.Memory`,
  dtype policy, ``interpret``, the tuning policy (``tune`` + plan-cache
  handle), and a :class:`Distribution` sub-config (grid/procs/mesh,
  ``overlap``).  Built once, validated once (eagerly, in
  ``__post_init__`` — so every construction path validates), consumed
  everywhere.
* :meth:`ExecutionContext.create` — the single constructor from flat
  options; *all* option validation lives here (one error-message
  catalog, see :func:`check_backend` and friends).
* :meth:`ExecutionContext.for_problem` — eager ``"auto"`` resolution:
  the processor grid is selected once (via
  :func:`repro.distributed.grid_select.choose_cp_grid`) and the per-mode
  plan decisions are resolved once against the tune cache, so drivers
  *replay* decisions instead of re-deriving them per mode/iteration.
* :meth:`ExecutionContext.to_json` / :meth:`~ExecutionContext.from_json`
  — a tuned/validated setup is a portable artifact: benchmarks record
  it, ``REPRO_CONTEXT`` (a path or an inline JSON string) seeds the
  default context of a fresh process, and ``from_json(to_json(ctx))``
  reproduces the identical plan resolutions.

Layering: this module may import :mod:`.plan` at module scope; everything
else (tune cache, grid selection, meshes) is imported inside methods so
``core``/``distributed``/``tune`` can keep their call-time-only imports of
the engine package.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Any, Mapping, Sequence

from .plan import BlockPlan, Memory, MultiTTMPlan

SCHEMA = "repro.ExecutionContext/1"
ENV_CONTEXT = "REPRO_CONTEXT"

#: Concrete executors plus the autotuner-resolved pseudo-backend.
VALID_BACKENDS = ("einsum", "blocked_host", "pallas", "auto")


# ---------------------------------------------------------------------------
# The validation catalog: ONE home for every option error in the stack
# ---------------------------------------------------------------------------

def check_backend(backend: str) -> None:
    """The single backend validator (replaces ``execute._check_backend``
    and the per-driver copies). Lists the valid values."""
    if backend not in VALID_BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of "
            f"{VALID_BACKENDS} (einsum/blocked_host/pallas run directly, "
            f"'auto' resolves through the tune cache)"
        )


def _err_tune_distributed() -> ValueError:
    return ValueError(
        "tune=True is not supported on the distributed path "
        "(nothing can be measured under the shard_map trace); "
        "pre-tune the local shard shapes with "
        "mttkrp(..., ctx=ExecutionContext.create(backend='auto', "
        "tune=True)), then run distributed with backend='auto' to "
        "replay the cache"
    )


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Distribution:
    """The parallel-machine description (§V): processor grid, count, the
    optional rank-axis extent ``p0`` (Algorithm 4), and the collective
    schedule ``overlap``.

    ``mesh`` is a process-local device handle: it is excluded from
    equality/hash/serialization (a context round-trips through JSON by its
    *grid*; the mesh is rebuilt on the target process, where the device
    topology may differ).
    """

    grid: tuple[int, ...] | None = None
    procs: int | None = None
    p0: int = 1
    overlap: str = "none"
    mesh: Any = field(default=None, compare=False, repr=False)

    def __post_init__(self):
        if self.overlap not in ("none", "ring"):
            raise ValueError(
                f"overlap must be 'none' or 'ring' (ring = ppermute-chunked "
                f"collectives overlapping the local MTTKRP), got "
                f"{self.overlap!r}"
            )
        if self.grid is not None:
            object.__setattr__(self, "grid", tuple(int(g) for g in self.grid))
            from ..distributed.mesh import validate_grid  # layer cycle

            # device-count fit is checked when the mesh is built (the
            # context itself must stay portable across machines)
            validate_grid(self.grid, self.p0, check_devices=False)
        if self.procs is not None and self.procs < 1:
            raise ValueError(f"procs must be >= 1, got {self.procs}")
        if self.p0 < 1:
            raise ValueError(f"p0 must be >= 1, got {self.p0}")

    def to_dict(self) -> dict:
        return {
            "grid": list(self.grid) if self.grid is not None else None,
            "procs": self.procs,
            "p0": self.p0,
            "overlap": self.overlap,
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "Distribution":
        grid = d.get("grid")
        return cls(
            grid=tuple(grid) if grid is not None else None,
            procs=d.get("procs"),
            p0=int(d.get("p0", 1)),
            overlap=str(d.get("overlap", "none")),
        )


@dataclass(frozen=True)
class ProblemSpec:
    """The (shape, rank, dtype) a context's decisions were resolved for.

    ``rank`` is the CP rank (int) or — for a Multi-TTM/Tucker problem —
    the tuple of per-mode Tucker ranks ``(R_1, ..., R_N)``."""

    shape: tuple[int, ...]
    rank: int | tuple[int, ...]
    dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(int(s) for s in self.shape))
        if isinstance(self.rank, (tuple, list)):
            object.__setattr__(
                self, "rank", tuple(int(r) for r in self.rank)
            )

    @property
    def is_multi_ttm(self) -> bool:
        return isinstance(self.rank, tuple)

    def to_dict(self) -> dict:
        rank = list(self.rank) if isinstance(self.rank, tuple) else self.rank
        return {"shape": list(self.shape), "rank": rank,
                "dtype": self.dtype}

    @classmethod
    def from_dict(cls, d: Mapping) -> "ProblemSpec":
        rank = d["rank"]
        rank = tuple(int(r) for r in rank) if isinstance(rank, list) \
            else int(rank)
        return cls(tuple(d["shape"]), rank, str(d["dtype"]))


@dataclass(frozen=True)
class PlanDecision:
    """One replayed ``backend="auto"`` resolution: how mode ``mode`` of the
    pinned problem runs (backend, exact BlockPlan, kernel variant,
    host-blocking size), and whether it came from the tune cache."""

    mode: int
    backend: str
    plan: BlockPlan | MultiTTMPlan | None = None
    variant: str | None = None
    block: int | None = None
    cache_hit: bool = False

    def __post_init__(self):
        # a decision is a RESOLVED choice: only concrete executors are
        # legal (a corrupt/hand-edited "auto" here would otherwise fall
        # through the dispatch layer into the pallas branch)
        if self.backend not in ("einsum", "blocked_host", "pallas"):
            raise ValueError(
                f"PlanDecision backend must be a concrete executor "
                f"(einsum/blocked_host/pallas), got {self.backend!r}"
            )

    def to_dict(self) -> dict:
        # single source of plan (de)serialization: the tune cache's
        # (pinned decisions and cache entries must never drift apart)
        from ..tune.cache import plan_to_dict  # layer cycle

        return {
            "mode": self.mode,
            "backend": self.backend,
            "plan": plan_to_dict(self.plan) if self.plan is not None
            else None,
            "variant": self.variant,
            "block": self.block,
            "cache_hit": self.cache_hit,
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "PlanDecision":
        from ..tune.cache import plan_from_dict  # layer cycle

        plan = d.get("plan")
        return cls(
            mode=int(d["mode"]),
            backend=str(d["backend"]),
            plan=plan_from_dict(plan) if plan is not None else None,
            variant=d.get("variant"),
            block=d.get("block"),
            cache_hit=bool(d.get("cache_hit", False)),
        )


# ---------------------------------------------------------------------------
# The context
# ---------------------------------------------------------------------------

#: The checkout's own persistent compile-cache directory (git-ignored).
#: The server, ``examples/serve.py`` and ``chip_smoke.py`` point their
#: contexts' ``compilation_cache`` here; it is a fixed path, because the
#: path is part of what makes a cached program hit again.
CHECKOUT_COMPILATION_CACHE = str(
    Path(__file__).resolve().parents[3] / ".cache" / "jax"
)


@dataclass(frozen=True)
class ExecutionContext:
    """The full execution environment, as one immutable, hashable value.

    Prefer the constructors: :meth:`create` (validate everything eagerly),
    :meth:`for_problem` (additionally resolve every ``"auto"`` choice —
    grid, per-mode plans — exactly once), :meth:`from_json` /
    :meth:`from_env` (replay a recorded setup).  Direct construction also
    validates (``__post_init__``), so an invalid context cannot exist.
    """

    backend: str = "einsum"
    memory: Memory | None = None
    out_dtype: str | None = None
    compute_dtype: str | None = None
    interpret: bool | None = None
    tune: bool = False
    cache_path: str | None = None
    distribution: Distribution | None = None
    problem: ProblemSpec | None = None
    decisions: tuple[PlanDecision, ...] = ()
    #: Opt this context's driver calls into the observability layer
    #: (span events into the active repro.observe.Trace, per-sweep
    #: collective-bytes measurement on the distributed drivers). Off by
    #: default: the False path adds no ops and no trace-unsafe work, so
    #: compiled HLO is identical to a pre-observability build.
    observe: bool = False
    #: Directory for JAX's persistent compilation cache
    #: (``jax.experimental.compilation_cache``). When set, drivers and the
    #: serving layer call :meth:`ensure_compilation_cache` before their
    #: first dispatch, so a *second* process serving the same buckets
    #: warm-starts: XLA reloads the compiled programs from disk instead of
    #: recompiling (the cold/warm split ``benchmarks/serve.py`` measures).
    #: None (the default) leaves the process-global JAX config untouched.
    compilation_cache: str | None = None

    # -- eager validation (every construction path runs this) --------------
    def __post_init__(self):
        check_backend(self.backend)
        if self.memory is not None and not isinstance(self.memory, Memory):
            raise ValueError(
                f"memory must be a repro.Memory (e.g. Memory.tpu_vmem() or "
                f"Memory.abstract(words)), got {type(self.memory).__name__}"
            )
        if self.out_dtype is not None:
            import jax.numpy as jnp

            try:
                jnp.dtype(self.out_dtype)
            except TypeError as e:
                raise ValueError(
                    f"out_dtype {self.out_dtype!r} is not a dtype: {e}"
                ) from None
        if self.compute_dtype is not None:
            import jax.numpy as jnp

            try:
                dt = jnp.dtype(self.compute_dtype)
            except TypeError as e:
                raise ValueError(
                    f"compute_dtype {self.compute_dtype!r} is not a dtype: "
                    f"{e}"
                ) from None
            if not jnp.issubdtype(dt, jnp.floating):
                raise ValueError(
                    f"compute_dtype must be a float dtype (inputs are cast "
                    f"to it; accumulation stays fp32), got "
                    f"{self.compute_dtype!r}"
                )
        if self.tune and self.is_distributed:
            raise _err_tune_distributed()
        if self.tune and self.backend != "auto":
            raise ValueError(
                f"tune=True requires backend='auto' (the search persists "
                f"winners the auto path replays); got "
                f"backend={self.backend!r}"
            )
        object.__setattr__(self, "decisions", tuple(self.decisions))
        if self.decisions and self.problem is None:
            raise ValueError(
                "decisions without a problem spec: use for_problem(...) "
                "to pin plan resolutions"
            )
        if self.compilation_cache is not None and not isinstance(
            self.compilation_cache, str
        ):
            raise ValueError(
                f"compilation_cache must be a directory path (str) or "
                f"None, got {type(self.compilation_cache).__name__}"
            )

    def ensure_compilation_cache(self) -> str | None:
        """Point JAX's persistent compilation cache at this context's
        ``compilation_cache`` directory (no-op when the field is None).

        ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX already reads
        it, so no directory is configured here and that one is returned.
        Otherwise this sets the process-global JAX config — cache dir plus
        the two thresholds that would otherwise skip small programs — so
        every compile after this call is written to (and on a warm start,
        read from) the directory.  Idempotent; returns the directory
        actually in use.  A fresh process then pays no recompiles for
        buckets an earlier process already served.
        """
        if self.compilation_cache is None:
            return None
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env_dir:
            return env_dir
        os.makedirs(self.compilation_cache, exist_ok=True)
        already = (
            jax.config.jax_compilation_cache_dir == self.compilation_cache
        )
        jax.config.update("jax_compilation_cache_dir", self.compilation_cache)
        if not already:
            # the persistent-cache singleton is memoized at the process's
            # FIRST compile; without a reset, a dir configured after that
            # compile is silently ignored
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc,
            )

            _cc.reset_cache()
        return self.compilation_cache

    # -- constructors -------------------------------------------------------
    @classmethod
    def create(
        cls,
        backend: str = "einsum",
        *,
        memory: Memory | None = None,
        out_dtype=None,
        compute_dtype=None,
        interpret: bool | None = None,
        tune: bool = False,
        cache_path: str | None = None,
        distributed: bool = False,
        mesh=None,
        grid: Sequence[int] | None = None,
        procs: int | None = None,
        p0: int = 1,
        overlap: str = "none",
        observe: bool = False,
        compilation_cache: str | None = None,
    ) -> "ExecutionContext":
        """Build and eagerly validate a context — THE constructor.

        Any of ``distributed=True`` / ``mesh`` / ``grid`` / ``procs``
        selects the distributed path (a :class:`Distribution` sub-config
        is attached); an explicit ``mesh`` wins over ``grid`` wins over
        automatic Eq (12) selection for ``procs`` processors.
        """
        dist = None
        if distributed or mesh is not None or grid is not None \
                or procs is not None or overlap != "none":
            if mesh is not None and grid is None:
                # derive the grid from the mesh axes (m0..m{N-1}, opt. r)
                names = [n for n in mesh.axis_names if n != "r"]
                grid = tuple(mesh.shape[n] for n in names)
                if "r" in mesh.axis_names:
                    p0 = mesh.shape["r"]
            dist = Distribution(
                grid=tuple(grid) if grid is not None else None,
                procs=procs, p0=p0, overlap=overlap,
                mesh=mesh,
            )
        if out_dtype is not None and not isinstance(out_dtype, str):
            import jax.numpy as jnp

            out_dtype = jnp.dtype(out_dtype).name
        if compute_dtype is not None and not isinstance(compute_dtype, str):
            import jax.numpy as jnp

            compute_dtype = jnp.dtype(compute_dtype).name
        return cls(
            backend=backend, memory=memory, out_dtype=out_dtype,
            compute_dtype=compute_dtype, interpret=interpret, tune=tune,
            cache_path=cache_path, distribution=dist,
            observe=bool(observe), compilation_cache=compilation_cache,
        )

    @classmethod
    def for_problem(
        cls,
        shape: Sequence[int],
        rank: int,
        dtype="float32",
        **kwargs,
    ) -> "ExecutionContext":
        """:meth:`create` + resolve every ``"auto"`` choice for the given
        problem, exactly once: the grid (Eq 12 sweep-optimal via
        ``choose_cp_grid``) and — for ``backend="auto"`` without ``tune``
        — the per-mode plan decisions from the tune cache (miss →
        analytic model-best). Drivers then *replay* these decisions
        instead of re-deriving them per mode/iteration. With
        ``tune=True`` decisions stay unpinned: the empirical search runs
        at the first driver call on concrete data and persists winners
        the cache then replays.

        ``rank`` may also be the tuple of per-mode Tucker ranks, pinning
        a Multi-TTM/Tucker problem (see :meth:`resolve_for`)."""
        return cls.create(**kwargs).resolve_for(shape, rank, dtype)

    def resolve_for(self, shape, rank, dtype="float32") \
            -> "ExecutionContext":
        """Pin this context to one problem: validate grid-vs-extent
        feasibility, select an unresolved grid, check memory-vs-plan
        feasibility, and resolve the per-mode ``"auto"`` decisions.

        ``rank`` is the CP rank (int) or the tuple of per-mode Tucker
        ranks — the latter pins a Multi-TTM/Tucker problem instead: the
        grid comes from the Multi-TTM sweep objective
        (``choose_tucker_grid``) and the ``"auto"`` decisions are the
        per-kept-mode ``kind="multi_ttm"`` resolutions (one per HOOI
        mode update plus one for the full core, keyed ``mode=-1``)."""
        import jax.numpy as jnp

        shape = tuple(int(s) for s in shape)
        dtype_name = jnp.dtype(dtype).name
        is_tucker = isinstance(rank, (tuple, list))
        rank = tuple(int(r) for r in rank) if is_tucker else int(rank)
        problem = ProblemSpec(shape, rank, dtype_name)
        if is_tucker and len(rank) != len(shape):
            raise ValueError(
                f"Tucker ranks {rank} must give one rank per tensor mode "
                f"({len(shape)} for shape {shape})"
            )
        dist = self.distribution
        if dist is not None and is_tucker:
            from ..distributed.grid_select import choose_tucker_grid
            from ..distributed.mesh import validate_tucker_grid

            grid = dist.grid
            if grid is None:
                procs = dist.procs
                if procs is None:
                    import jax

                    procs = len(jax.devices())
                grid = choose_tucker_grid(shape, rank, procs).grid
            validate_tucker_grid(grid, dims=shape, check_devices=False)
            dist = replace(dist, grid=tuple(grid))
        elif dist is not None:
            from ..distributed.grid_select import choose_cp_grid
            from ..distributed.mesh import validate_grid

            grid = dist.grid
            if grid is None:
                procs = dist.procs
                if procs is None:
                    import jax

                    procs = len(jax.devices())
                grid = choose_cp_grid(shape, rank, procs).grid
            validate_grid(
                grid, dist.p0, dims=shape, rank=rank, check_devices=False
            )
            dist = replace(dist, grid=tuple(grid))
        decisions: tuple[PlanDecision, ...] = ()
        if is_tucker and self.backend == "auto" and not self.tune \
                and dist is None:
            from ..tune.search import resolve_multi_ttm  # layer cycle

            cache = self.plan_cache()
            out = []
            for keep_key in (-1,) + tuple(range(len(shape))):
                lead = 0 if keep_key == -1 else keep_key
                canon = (shape[lead],) + tuple(
                    s for k, s in enumerate(shape) if k != lead
                )
                contracted = tuple(
                    r for k, r in enumerate(rank) if k != keep_key
                )
                r = resolve_multi_ttm(
                    canon, contracted, keep_key, jnp.dtype(dtype_name),
                    self.memory, cache=cache,
                )
                out.append(PlanDecision(
                    keep_key, r.backend, r.plan, r.variant, r.block,
                    r.cache_hit,
                ))
            decisions = tuple(out)
            return replace(
                self, distribution=dist, problem=problem,
                decisions=decisions,
            )
        if is_tucker:
            if self.memory is not None:
                # the budget must admit SOME plan for EVERY Multi-TTM the
                # Tucker/HOOI workload runs: each kept mode (whose kernel
                # contracts the other N-1 ranks) and the full core
                from .plan import choose_multi_ttm_blocks

                for keep_key in (-1,) + tuple(range(len(shape))):
                    lead = 0 if keep_key == -1 else keep_key
                    canon = (shape[lead],) + tuple(
                        s for k, s in enumerate(shape) if k != lead
                    )
                    kernel_ranks = tuple(
                        r for k, r in enumerate(rank) if k != lead
                    )
                    plan = choose_multi_ttm_blocks(
                        canon, kernel_ranks, self.memory.itemsize,
                        memory=self.memory,
                    )
                    if not plan.fits(self.memory):
                        what = (
                            "the full core" if keep_key == -1
                            else f"the keep={keep_key} HOOI update"
                        )
                        raise ValueError(
                            f"memory budget {self.memory.budget_bytes}B "
                            f"admits no feasible Multi-TTM plan for "
                            f"{what} of shape={shape}, ranks={rank} "
                            f"(minimal working set "
                            f"{plan.working_set_words() * self.memory.itemsize}"
                            f"B); raise the budget or shrink the ranks"
                        )
            return replace(
                self, distribution=dist, problem=problem,
                decisions=decisions,
            )
        if self.backend == "auto" and not self.tune and dist is None:
            # tune=True deliberately pins NOTHING: the empirical search
            # needs concrete data to measure, so it runs at the first
            # driver call (engine.execute's live path) and later calls
            # replay the persisted winner from the cache. Pinning here
            # would freeze the un-tuned model-best and the search would
            # silently never happen. Distributed contexts pin only the
            # grid: their engine work runs on per-SHARD shapes inside
            # shard_map, so global-shape decisions could never replay.
            from ..tune.search import resolve  # layer cycle

            cache = self.plan_cache()
            out = []
            for mode in range(len(shape)):
                perm = (shape[mode],) + tuple(
                    s for k, s in enumerate(shape) if k != mode
                )
                r = resolve(
                    perm, rank, mode, jnp.dtype(dtype_name), self.memory,
                    cache=cache,
                )
                out.append(PlanDecision(
                    mode, r.backend, r.plan, r.variant, r.block,
                    r.cache_hit,
                ))
            decisions = tuple(out)
        elif self.memory is not None:
            # memory-vs-plan feasibility: the budget must admit SOME plan
            from .plan import choose_blocks

            plan = choose_blocks(
                shape, rank, self.memory.itemsize, memory=self.memory
            )
            if not plan.fits(self.memory):
                raise ValueError(
                    f"memory budget {self.memory.budget_bytes}B admits no "
                    f"Eq-9-feasible plan for shape={shape}, rank={rank} "
                    f"(minimal working set "
                    f"{plan.working_set_words() * self.memory.itemsize}B); "
                    f"raise the budget or shrink the rank"
                )
        return replace(
            self, distribution=dist, problem=problem, decisions=decisions
        )

    # -- queries -------------------------------------------------------------
    @property
    def is_distributed(self) -> bool:
        return self.distribution is not None

    def decision_for(self, shape, rank: int, mode: int, dtype=None) \
            -> PlanDecision | None:
        """The pinned ``"auto"`` decision for ``mode`` — or None when this
        context was not resolved for exactly this (shape, rank, dtype).
        The dtype is part of the identity: a plan blocked for 4-byte items
        must not replay on 8-byte data (Eq-9 working set doubles)."""
        if self.problem is None:
            return None
        if self.problem.shape != tuple(shape) or self.problem.rank != rank:
            return None
        if dtype is not None:
            import jax.numpy as jnp

            if jnp.dtype(dtype).name != self.problem.dtype:
                return None
        for d in self.decisions:
            if d.mode == mode:
                return d
        return None

    def plan_cache(self):
        """The tune-cache handle this context reads/writes
        (``cache_path`` override, else the process default)."""
        from ..tune.cache import PlanCache, default_cache  # layer cycle

        if self.cache_path is not None:
            return PlanCache(self.cache_path)
        return default_cache()

    def local(self) -> "ExecutionContext":
        """The per-shard view of a distributed context: same engine knobs,
        no distribution (the collectives are owned by the sweep driver;
        inside each shard the problem is exactly the sequential one)."""
        if self.distribution is None:
            return self
        return replace(
            self, distribution=None, problem=None, decisions=()
        )

    def build_mesh(self, shape=None, rank: int | None = None):
        """The device mesh for the distributed path (explicit mesh wins;
        else built from the resolved grid — this is where device-count
        feasibility is enforced, since it is machine-local)."""
        if self.distribution is None:
            raise ValueError(
                "build_mesh() on a non-distributed context; pass "
                "distributed=True / grid= / procs= to create()"
            )
        if self.distribution.mesh is not None:
            return self.distribution.mesh
        if self.distribution.grid is None:
            raise ValueError(
                "no grid resolved yet: call resolve_for(shape, rank) / "
                "for_problem(...) first, or pass grid= explicitly"
            )
        from ..distributed.mesh import make_grid_mesh

        return make_grid_mesh(
            self.distribution.grid, p0=self.distribution.p0,
            dims=shape, rank=rank,
        )

    def tensor_sharding(self, shape, rank: int):
        """The ``jax.sharding.NamedSharding`` that :func:`repro.cp_als`
        on this distributed context gives a tensor of ``shape`` at CP
        ``rank``: X block-distributed over the resolved grid's mesh
        (:meth:`build_mesh`, ``tensor_spec``; the grid the driver itself
        resolves).  Make or load X in it, one block per device, and the
        driver uses it where it is: no copy, no gather.  A tensor too
        large for one device never has to exist on one."""
        if self.distribution is None:
            raise ValueError(
                "tensor_sharding() on a non-distributed context; pass "
                "distributed=True / grid= / procs= to create()"
            )
        from jax.sharding import NamedSharding

        from ..distributed.cp_als_parallel import resolve_cp_mesh
        from ..distributed.mttkrp_parallel import tensor_spec

        _, mesh, _ = resolve_cp_mesh(self, shape, int(rank))
        return NamedSharding(mesh, tensor_spec(len(shape)))

    def build_abstract_mesh(self):
        """Device-free twin of :meth:`build_mesh`: an ``AbstractMesh``
        over the same grid. Enough to *trace* the distributed sweep
        (``jax.make_jaxpr``) with no devices at all — the static
        communication verifier (``repro.verify.comm``) analyzes grids
        far larger than the host this way. Never resolvable to devices;
        running a program built on it raises inside jax."""
        if self.distribution is None:
            raise ValueError(
                "build_abstract_mesh() on a non-distributed context; pass "
                "distributed=True / grid= / procs= to create()"
            )
        if self.distribution.grid is None:
            raise ValueError(
                "no grid resolved yet: call resolve_for(shape, rank) / "
                "for_problem(...) first, or pass grid= explicitly"
            )
        from ..distributed.mesh import make_abstract_grid_mesh

        return make_abstract_grid_mesh(
            self.distribution.grid, p0=self.distribution.p0
        )

    # -- serialization -------------------------------------------------------
    def to_dict(self) -> dict:
        mem = None
        if self.memory is not None:
            mem = {
                "budget_bytes": self.memory.budget_bytes,
                "lane": self.memory.lane,
                "sublane": self.memory.sublane,
                "itemsize": self.memory.itemsize,
            }
        return {
            "schema": SCHEMA,
            "backend": self.backend,
            "memory": mem,
            "out_dtype": self.out_dtype,
            "compute_dtype": self.compute_dtype,
            "interpret": self.interpret,
            "tune": self.tune,
            "cache_path": self.cache_path,
            "distribution": (
                self.distribution.to_dict()
                if self.distribution is not None else None
            ),
            "problem": (
                self.problem.to_dict() if self.problem is not None else None
            ),
            "decisions": [d.to_dict() for d in self.decisions],
            "observe": self.observe,
            "compilation_cache": self.compilation_cache,
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "ExecutionContext":
        schema = d.get("schema", SCHEMA)
        if schema != SCHEMA:
            raise ValueError(
                f"unsupported ExecutionContext schema {schema!r} "
                f"(this build reads {SCHEMA!r})"
            )
        mem = d.get("memory")
        if mem is not None:
            mem = Memory(
                budget_bytes=int(mem["budget_bytes"]),
                lane=int(mem.get("lane", 1)),
                sublane=int(mem.get("sublane", 1)),
                itemsize=int(mem.get("itemsize", 4)),
            )
        dist = d.get("distribution")
        prob = d.get("problem")
        return cls(
            backend=str(d.get("backend", "einsum")),
            memory=mem,
            out_dtype=d.get("out_dtype"),
            compute_dtype=d.get("compute_dtype"),
            interpret=d.get("interpret"),
            tune=bool(d.get("tune", False)),
            cache_path=d.get("cache_path"),
            distribution=(
                Distribution.from_dict(dist) if dist is not None else None
            ),
            problem=ProblemSpec.from_dict(prob) if prob is not None else None,
            decisions=tuple(
                PlanDecision.from_dict(x) for x in d.get("decisions", ())
            ),
            # absent in pre-observability JSON: old artifacts stay loadable
            observe=bool(d.get("observe", False)),
            # absent in pre-serving JSON: old artifacts stay loadable
            compilation_cache=d.get("compilation_cache"),
        )

    def to_json(self, *, indent: int | None = None) -> str:
        """Serialize (portably — no device handles) for recording in
        benchmark rows, files, or ``REPRO_CONTEXT``."""
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ExecutionContext":
        """Inverse of :meth:`to_json`: ``from_json(ctx.to_json()) == ctx``
        (the mesh handle, which is process-local, excepted)."""
        return cls.from_dict(json.loads(s))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(indent=1))

    @classmethod
    def load(cls, path: str) -> "ExecutionContext":
        with open(path) as f:
            return cls.from_json(f.read())

    @classmethod
    def from_env(cls) -> "ExecutionContext | None":
        """The ``REPRO_CONTEXT`` seed: a path to a context JSON file, or
        the JSON text itself. None when the variable is unset."""
        raw = os.environ.get(ENV_CONTEXT)
        if not raw:
            return None
        if os.path.exists(raw):
            return cls.load(raw)
        return cls.from_json(raw)

    @classmethod
    def default(cls) -> "ExecutionContext":
        """What a driver uses when handed no ``ctx``: the
        ``REPRO_CONTEXT`` seed if set, else the stock einsum context.
        Memoized on the raw env value — bare driver calls in
        hot loops must not re-read files or re-parse JSON."""
        raw = os.environ.get(ENV_CONTEXT) or ""
        cached = _DEFAULT_MEMO.get(raw)
        if cached is None:
            cached = cls.from_env() or cls()
            _DEFAULT_MEMO.clear()  # env changed: old seeds are stale
            _DEFAULT_MEMO[raw] = cached
        return cached


# memo for ExecutionContext.default(), keyed by the raw REPRO_CONTEXT value
_DEFAULT_MEMO: dict[str, "ExecutionContext"] = {}
