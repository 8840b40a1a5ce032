"""The stable public API surface (`import repro`) and its one way to
configure an execution (``ctx=``).

Run in the CI fast lane as the API-stability gate: a PR that changes
``repro.__all__``, drops a docstring, or brings back a per-call option
keyword fails here before anything else.
"""

import dataclasses
import warnings

import jax
import pytest

import repro

# the documented surface (docs/API.md) — change BOTH deliberately
DOCUMENTED_SURFACE = [
    "ExecutionContext",
    "Distribution",
    "Memory",
    "BlockPlan",
    "MultiTTMPlan",
    "mttkrp",
    "contract_partial",
    "multi_ttm",
    "cp_als",
    "cp_als_batched",
    "cp_gradient",
    "CPResult",
    "BatchedCPResult",
    "tucker_hooi",
    "tucker_hooi_batched",
    "TuckerResult",
    "BatchedTuckerResult",
    "select_grid",
    "select_tucker_grid",
    "Trace",
]


def _problem(dims=(6, 5, 4), rank=3):
    x = jax.random.normal(jax.random.PRNGKey(0), dims)
    fs = [
        jax.random.normal(jax.random.PRNGKey(k + 1), (d, rank))
        for k, d in enumerate(dims)
    ]
    return x, fs


# ---------------------------------------------------------------------------
# surface shape
# ---------------------------------------------------------------------------

def test_all_matches_documented_surface():
    assert list(repro.__all__) == DOCUMENTED_SURFACE


def test_every_export_exists_and_is_documented():
    for name in repro.__all__:
        obj = getattr(repro, name)  # raises AttributeError on a bad export
        assert obj.__doc__ and obj.__doc__.strip(), (
            f"repro.{name} has no docstring"
        )


def test_every_exported_callable_has_docstring():
    for name in repro.__all__:
        obj = getattr(repro, name)
        if callable(obj):
            assert obj.__doc__ and len(obj.__doc__.strip()) > 20, (
                f"repro.{name} is exported but under-documented"
            )


def test_package_has_version_and_module_doc():
    assert repro.__doc__ and "ExecutionContext" in repro.__doc__
    assert isinstance(repro.__version__, str) and repro.__version__


def test_multi_ttm_surface_is_documented():
    """Docstring-presence audit over the full new Multi-TTM/Tucker
    surface, one level below the frozen top-level exports."""
    from repro.core import bounds, tucker
    from repro.distributed import grid_select, tucker_parallel
    from repro.engine import execute, plan
    from repro.kernels import multi_ttm as multi_ttm_kernel
    from repro.tune import search

    audited = [
        execute.multi_ttm,
        plan.MultiTTMPlan,
        plan.choose_multi_ttm_blocks,
        plan.uniform_multi_ttm_plan,
        tucker.tucker_hooi,
        tucker.hosvd_init,
        tucker.ttm,
        tucker.TuckerResult,
        bounds.multi_ttm_seq_lb,
        bounds.multi_ttm_blocked_cost,
        bounds.par_multi_ttm_cost,
        grid_select.select_tucker_grid,
        grid_select.choose_tucker_grid,
        grid_select.multi_ttm_sweep_words,
        tucker_parallel.multi_ttm_stationary,
        tucker_parallel.build_tucker_sweep,
        tucker_parallel.tucker_hooi_parallel,
        multi_ttm_kernel.multi_ttm_keep_pallas,
        search.tune_multi_ttm,
        search.resolve_multi_ttm,
    ]
    from repro.observe import bounds_audit, metrics, trace

    audited += [
        trace.Trace,
        trace.summarize_events,
        metrics.MetricsRegistry,
        metrics.registry,
        bounds_audit.AuditRow,
        bounds_audit.audit_mttkrp,
        bounds_audit.audit_multi_ttm,
    ]
    for obj in audited:
        assert obj.__doc__ and len(obj.__doc__.strip()) > 20, (
            f"{obj.__module__}.{obj.__qualname__} is under-documented"
        )


# ---------------------------------------------------------------------------
# ctx= is the only configuration argument
# ---------------------------------------------------------------------------

def _removed_option_cases():
    """(entry point, positional args, kwargs, expected TypeError text).

    The positional arguments are never used: the call must be refused
    when it binds, before any computation."""
    from repro.core import dimension_tree as core_tree
    from repro.distributed.cp_als_parallel import (
        build_cp_sweep,
        cp_als_parallel,
    )
    from repro.distributed.mttkrp_parallel import (
        engine_local_fn,
        mttkrp_general,
        mttkrp_stationary,
    )
    from repro.engine import execute, tree

    engine4 = ("backend", "memory", "interpret", "tune")
    table = [
        ("mttkrp", execute.mttkrp, (None, None, 0), engine4),
        ("contract_partial", execute.contract_partial,
         (None, None, (0,), (0,), False), engine4),
        ("tree.all_mode_mttkrp", tree.all_mode_mttkrp, (None, None),
         engine4),
        ("tree.dimtree_als_sweep", tree.dimtree_als_sweep,
         (None, None, None), engine4),
        ("cp_als", repro.cp_als, (None, 2),
         engine4 + ("distributed", "mesh", "grid", "procs",
                    "use_dimension_tree")),
        ("cp_gradient", repro.cp_gradient, (None, 2), engine4),
        ("core.all_mode_mttkrp_dimtree", core_tree.all_mode_mttkrp_dimtree,
         (None, None), ("backend", "memory", "interpret")),
        ("core.dimtree_als_sweep", core_tree.dimtree_als_sweep,
         (None, None, None), ("backend", "memory", "interpret")),
        ("engine_local_fn", engine_local_fn, (),
         ("backend", "memory", "interpret")),
        ("mttkrp_stationary", mttkrp_stationary, (None, 0, 3),
         ("backend", "interpret")),
        ("mttkrp_general", mttkrp_general, (None, 0, 3),
         ("backend", "interpret")),
        ("build_cp_sweep", build_cp_sweep, (None, 3),
         ("backend", "interpret", "memory")),
        ("cp_als_parallel", cp_als_parallel, (None, 2),
         ("backend", "interpret", "memory", "grid", "mesh", "procs")),
    ]
    cases = [
        pytest.param(
            fn, args, {kw: True}, f"unexpected keyword argument '{kw}'",
            id=f"{name}-{kw}",
        )
        for name, fn, args, kws in table
        for kw in kws
    ]
    # the old positional spelling engine_local_fn("einsum")
    cases.append(pytest.param(
        engine_local_fn, ("einsum",), {}, "positional argument",
        id="engine_local_fn-positional-backend",
    ))
    return cases


@pytest.mark.parametrize("fn,args,kwargs,match", _removed_option_cases())
def test_removed_option_kwargs_raise_typeerror(fn, args, kwargs, match):
    with pytest.raises(TypeError, match=match):
        fn(*args, **kwargs)


def test_ctx_path_is_warning_free():
    x, fs = _problem()
    ctx = repro.ExecutionContext.create(backend="einsum")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        repro.mttkrp(x, fs, 0, ctx=ctx)
        repro.cp_als(x, 2, n_iters=1, ctx=ctx)
        repro.cp_gradient(x, 2, n_iters=1, ctx=ctx)


def test_default_call_is_warning_free():
    x, fs = _problem()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        repro.mttkrp(x, fs, 0)
        repro.cp_als(x, 2, n_iters=1)


# ---------------------------------------------------------------------------
# the unified validator (one error catalog, actionable messages)
# ---------------------------------------------------------------------------

def test_unknown_backend_lists_valid_values():
    with pytest.raises(ValueError) as e:
        repro.ExecutionContext.create(backend="cuda")
    msg = str(e.value)
    for valid in ("einsum", "blocked_host", "pallas", "auto"):
        assert valid in msg


def test_driver_and_context_raise_the_same_backend_error():
    """A driver only derives contexts (``dataclasses.replace``, as
    ``ctx.local()`` does); a derived context validates like a created
    one, so no driver can run an unknown backend or word its error
    differently."""
    with pytest.raises(ValueError) as via_ctx:
        repro.ExecutionContext.create(backend="nope")
    with pytest.raises(ValueError) as via_driver:
        dataclasses.replace(repro.ExecutionContext.create(), backend="nope")
    assert str(via_ctx.value) == str(via_driver.value)
