"""compiles_per_solve (programs/solve): the programs JAX lowered anew in
the window (its own ``/jax/core/compile/jaxpr_to_mlir_module_duration``
event, whether the executable then came from the compiler or from the
persistent cache), per decomposition completed."""


def read(run):
    if run.units == 0:
        return None
    return run.compiles / run.units
