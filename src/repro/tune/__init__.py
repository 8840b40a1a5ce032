"""Autotuning subsystem: empirical plan search, persistent plan cache, and
a calibrated cost model behind ``backend="auto"``.

The analytic planner (:mod:`repro.engine.plan`) decides blocking from the
paper's Eq-9/Eq-10 model alone; on real hardware the model's constant
factors are off by machine-dependent amounts (the gap Hayashi et al. close
with empirical tuning). This package closes it in three parts:

    cache     — persistent on-disk JSON plan cache, keyed by the full
                problem descriptor (kind, shape, rank, mode, dtype,
                Memory, execution platform, jax version) with schema
                versioning and in-process memoization.
                ``REPRO_TUNE_CACHE`` overrides the path.
    search    — candidate generation (perturbed ``choose_blocks`` plans,
                the paper's uniform-b plan, both kernel variants, all
                three executors) and the measurement loop that times each
                candidate through ``engine.execute.mttkrp``.
    calibrate — fits per-machine bandwidth/overhead coefficients so
                ``BlockPlan.traffic_model`` predictions can be scored
                against measurements (model-vs-measured error report).

``engine.execute.mttkrp`` under a ``backend="auto"`` context resolves
through :func:`repro.tune.search.resolve`: cache hit → the tuned plan,
exactly as persisted; miss → the analytic model-best plan (plus
``tune=True`` to search empirically and persist the winner).
"""

from .cache import (
    SCHEMA_VERSION,
    CacheEntry,
    PlanCache,
    cache_key,
    default_cache,
    isolated_cache,
    plan_from_dict,
    plan_to_dict,
)
from .calibrate import Calibration, calibrate, calibration_report
from .search import (  # NB: the search *function* stays module-qualified
    Candidate,         # (repro.tune.search.search) so the submodule name
    Measurement,       # isn't shadowed on the package
    TuneResult,
    generate_candidates,
    resolve,
    resolve_multi_ttm,
    tune_mttkrp,
    tune_multi_ttm,
    tune_partial,
)
from . import cache, calibrate, search  # noqa: F401  (submodule access)

__all__ = [
    "SCHEMA_VERSION",
    "CacheEntry",
    "PlanCache",
    "cache_key",
    "default_cache",
    "isolated_cache",
    "plan_from_dict",
    "plan_to_dict",
    "Calibration",
    "calibrate",
    "calibration_report",
    "Candidate",
    "Measurement",
    "TuneResult",
    "generate_candidates",
    "resolve",
    "resolve_multi_ttm",
    "tune_mttkrp",
    "tune_multi_ttm",
    "tune_partial",
    "search",  # the submodule (repro.tune.search)
]
