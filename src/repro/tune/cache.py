"""Persistent plan cache: tuned BlockPlans keyed by the full problem.

One JSON file holds every tuned decision on this machine. A cache entry
records *everything* ``engine.execute.mttkrp`` needs to replay the winner
without re-searching: backend, kernel variant, and the exact
:class:`~repro.engine.plan.BlockPlan` (round-tripped field-for-field, so a
warm cache reproduces the tuned plan bit-identically).

Keying
------
``cache_key`` folds in shape, rank, mode, dtype, the Memory descriptor
(budget/lane/sublane/itemsize), the contraction kind (full MTTKRP vs
rank-augmented partial), the execution platform (a winner measured on CPU
must never be replayed on TPU, and vice versa), and the jax version — a
change to any of these is a different tuning problem, so it simply
misses. ``SCHEMA_VERSION`` is part of the on-disk envelope: bumping it
(or loading a file written by a different version) invalidates the whole
file rather than risking stale plans.

Robustness
----------
A corrupted, truncated, or wrong-schema cache file must never take the
engine down: loads fall back to an empty cache (the caller then re-plans
analytically) and the next ``put`` rewrites the file atomically.

The path resolves, in order: explicit argument, ``REPRO_TUNE_CACHE`` env
var, ``~/.cache/repro-mttkrp/plans.json``.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time
from dataclasses import asdict, dataclass, field
from typing import Iterator, Sequence

import jax

from ..engine.plan import BlockPlan, Memory, MultiTTMPlan

SCHEMA_VERSION = 1
ENV_CACHE_PATH = "REPRO_TUNE_CACHE"
DEFAULT_CACHE_PATH = os.path.join(
    "~", ".cache", "repro-mttkrp", "plans.json"
)


def resolve_cache_path(path: str | None = None) -> str:
    """Explicit path > ``$REPRO_TUNE_CACHE`` > the default user cache."""
    if path is None:
        path = os.environ.get(ENV_CACHE_PATH) or DEFAULT_CACHE_PATH
    return os.path.expanduser(path)


# ---------------------------------------------------------------------------
# BlockPlan (de)serialization — exact round-trip
# ---------------------------------------------------------------------------

def plan_to_dict(plan: BlockPlan | MultiTTMPlan) -> dict:
    if isinstance(plan, MultiTTMPlan):
        return {
            "block_i": plan.block_i,
            "block_contract": list(plan.block_contract),
            "ranks": list(plan.ranks),
        }
    return {
        "block_i": plan.block_i,
        "block_contract": list(plan.block_contract),
        "block_r": plan.block_r,
        "x_has_rank": plan.x_has_rank,
    }


def plan_from_dict(d: dict) -> BlockPlan | MultiTTMPlan:
    if "ranks" in d:  # Multi-TTM plans carry the per-mode Tucker ranks
        return MultiTTMPlan(
            block_i=int(d["block_i"]),
            block_contract=tuple(int(c) for c in d["block_contract"]),
            ranks=tuple(int(r) for r in d["ranks"]),
        )
    return BlockPlan(
        block_i=int(d["block_i"]),
        block_contract=tuple(int(c) for c in d["block_contract"]),
        block_r=int(d["block_r"]),
        x_has_rank=bool(d.get("x_has_rank", False)),
    )


def memory_tag(memory: Memory) -> str:
    return (
        f"{memory.budget_bytes}:{memory.lane}:{memory.sublane}"
        f":{memory.itemsize}"
    )


def cache_key(
    shape: Sequence[int],
    rank: int | Sequence[int],
    mode: int,
    dtype,
    memory: Memory,
    *,
    kind: str = "mttkrp",
) -> str:
    """The tuning-problem identity; every field that changes the answer.

    ``rank`` is the CP rank (int) or — for ``kind="multi_ttm"`` — the
    tuple of per-mode Tucker ranks (tagged ``r1xr2x...``); ``mode`` is
    the output/kept mode (``-1`` = full Tucker core, no kept mode).  A
    3-way MTTKRP key is tagged ``layout=stored``: its specialized kernel
    reads X in place, so an entry tuned against a transposed copy of X
    (untagged) misses instead of replaying its plan."""
    shape_tag = "x".join(str(int(s)) for s in shape)
    if isinstance(rank, (tuple, list)):
        rank_tag = "x".join(str(int(r)) for r in rank)
    else:
        rank_tag = str(int(rank))
    layout = "|layout=stored" if kind == "mttkrp" and len(shape) == 3 else ""
    return (
        f"{kind}|shape={shape_tag}|rank={rank_tag}|mode={int(mode)}"
        f"|dtype={jax.numpy.dtype(dtype).name}|mem={memory_tag(memory)}"
        f"{layout}|platform={jax.default_backend()}|jax={jax.__version__}"
    )


@dataclass
class CacheEntry:
    """One tuned decision: how to run this contraction, and why."""

    backend: str
    plan: dict | None = None  # plan_to_dict payload; None for einsum
    variant: str | None = None  # pallas kernel variant (specialized/generic)
    block: int | None = None  # blocked_host uniform block
    metric: str = "walltime"
    score: float = float("nan")  # winning score (us or modeled bytes)
    walltime_us: float = float("nan")
    modeled_bytes: int | None = None
    timestamp: float = 0.0
    meta: dict = field(default_factory=dict)

    def to_plan(self) -> BlockPlan | None:
        return plan_from_dict(self.plan) if self.plan is not None else None

    @classmethod
    def from_dict(cls, d: dict) -> "CacheEntry":
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in d.items() if k in known})


class PlanCache:
    """On-disk JSON plan cache with in-process memoization.

    The file layout is a versioned envelope::

        {"schema": 1, "entries": {key: entry...}, "calibration": {...}}

    Loads are lazy and forgiving (any parse/schema problem yields an empty
    cache); writes go through a same-directory temp file + ``os.replace``
    so a crash mid-write can never leave a half-written cache behind.
    """

    def __init__(self, path: str | None = None):
        self.path = resolve_cache_path(path)
        self._entries: dict[str, CacheEntry] | None = None
        self._calibration: dict | None = None

    # -- load/store --------------------------------------------------------
    def _load(self) -> dict[str, CacheEntry]:
        if self._entries is not None:
            return self._entries
        entries: dict[str, CacheEntry] = {}
        calibration: dict | None = None
        try:
            with open(self.path) as f:
                raw = json.load(f)
            if (
                isinstance(raw, dict)
                and raw.get("schema") == SCHEMA_VERSION
                and isinstance(raw.get("entries"), dict)
            ):
                for k, v in raw["entries"].items():
                    try:
                        entries[k] = CacheEntry.from_dict(v)
                    except (TypeError, KeyError, ValueError):
                        continue  # skip one bad entry, keep the rest
                cal = raw.get("calibration")
                calibration = cal if isinstance(cal, dict) else None
            # wrong schema / shape: treated as empty (full invalidation)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError):
            pass  # missing or corrupted file: start empty, never crash
        self._entries = entries
        self._calibration = calibration
        return entries

    def _flush(self) -> None:
        entries = self._load()
        payload = {
            "schema": SCHEMA_VERSION,
            "jax": jax.__version__,
            "entries": {k: asdict(e) for k, e in entries.items()},
        }
        if self._calibration is not None:
            payload["calibration"] = self._calibration
        d = os.path.dirname(self.path) or "."
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass  # read-only filesystem etc.: in-process cache still works

    # -- entries -----------------------------------------------------------
    def get(self, key: str) -> CacheEntry | None:
        return self._load().get(key)

    def put(self, key: str, entry: CacheEntry, persist: bool = True) -> None:
        if not entry.timestamp:
            entry.timestamp = time.time()
        self._load()[key] = entry
        if persist:
            self._flush()

    def invalidate(self, key: str) -> None:
        self._load().pop(key, None)
        self._flush()

    def clear(self) -> None:
        self._entries = {}
        self._calibration = None
        self._flush()

    def keys(self) -> list[str]:
        return sorted(self._load())

    def __len__(self) -> int:
        return len(self._load())

    # -- calibration section ----------------------------------------------
    def get_calibration(self) -> dict | None:
        self._load()
        return self._calibration

    def put_calibration(self, cal: dict) -> None:
        self._load()
        self._calibration = cal
        self._flush()


# process-wide default caches, one per resolved path (so tests can redirect
# via REPRO_TUNE_CACHE / monkeypatch and get a fresh instance)
_DEFAULT_CACHES: dict[str, PlanCache] = {}


def default_cache() -> PlanCache:
    path = resolve_cache_path()
    cache = _DEFAULT_CACHES.get(path)
    if cache is None:
        cache = _DEFAULT_CACHES[path] = PlanCache(path)
    return cache


@contextlib.contextmanager
def isolated_cache() -> Iterator[str]:
    """Redirect the default cache to a throwaway temp file for the scope
    (benchmarks and demos must never pollute the user's plan cache).
    Restores ``REPRO_TUNE_CACHE`` and removes the file on exit."""
    fd, tmp = tempfile.mkstemp(prefix="repro-tune-", suffix=".json")
    os.close(fd)
    prev = os.environ.get(ENV_CACHE_PATH)
    os.environ[ENV_CACHE_PATH] = tmp
    try:
        yield tmp
    finally:
        if prev is None:
            os.environ.pop(ENV_CACHE_PATH, None)
        else:
            os.environ[ENV_CACHE_PATH] = prev
        try:
            os.unlink(tmp)
        except OSError:
            pass
