"""Stand-ins for the program that must read as not correct: the control
and planted faults, each computed by the plain reference in the
program's place.  ``calibrate.py`` reads them at a cell's own size on the
chip (the upper ends of the limits); the tests plant the same faults in
the timed path at a tiny size.

- ``control``: the reference one precision step below the configuration's
  (its ``control_precision``);
- ``sweeps<k>``: the reference stopped after ``k`` sweeps instead of the
  configuration's count (a solve that leaves out sweeps);
- ``stale_mode0``: CP-ALS whose mode-0 update uses the MTTKRP of the sweep
  before, from the second sweep on (an update from stale factors).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def control(ref, x, answer, cfg):
    return ref.reference(x, answer, cfg, cfg["control_precision"])


def sweeps(k: int):
    def fault(ref, x, answer, cfg):
        return ref.reference(x, answer, {**cfg, "sweeps_per_solve": k},
                             "highest")
    fault.__name__ = f"sweeps{k}"
    return fault


def stale_mode0(ref, x, answer, cfg):
    """CP-ALS as the reference does it, but mode 0 is updated from the
    MTTKRP that the previous sweep computed for it."""
    factors = [jnp.asarray(f) for f in answer["init"]]
    n, rank = len(factors), factors[0].shape[1]
    hi = "highest"
    with jax.default_matmul_precision(hi):
        grams = [f.T @ f for f in factors]
        normx = jnp.sqrt(jnp.sum(jnp.square(x)))
        stale, fits = None, []
        for _ in range(int(cfg["sweeps_per_solve"])):
            for mode in range(n):
                b = ref.mttkrp(x, factors, mode, hi)
                if mode == 0:
                    b, stale = (b if stale is None else stale), b
                gamma = jnp.ones_like(grams[0])
                for k, g in enumerate(grams):
                    if k != mode:
                        gamma = gamma * g
                ridge = 1e-5 * jnp.trace(gamma) / rank + 1e-12
                a = b @ jnp.linalg.inv(gamma + ridge * jnp.eye(rank))
                weights = jnp.maximum(jnp.linalg.norm(a, axis=0), 1e-30)
                a = a / weights
                factors[mode], grams[mode] = a, a.T @ a
            inner = jnp.sum(b * (a * weights))
            had = jnp.ones_like(grams[0])
            for g in grams:
                had = had * g
            recon = jnp.sum(had * jnp.outer(weights, weights))
            err = jnp.maximum(normx**2 - 2 * inner + recon, 0.0)
            fits.append(1.0 - jnp.sqrt(err) / normx)
    return {"factors": factors, "weights": weights,
            "fits": np.asarray(jnp.stack(fits)), "x": x, "sweep_fits": True}


def by_name(name: str):
    if name.startswith("sweeps"):
        return sweeps(int(name[len("sweeps"):]))
    return {"control": control, "stale_mode0": stale_mode0}[name]
