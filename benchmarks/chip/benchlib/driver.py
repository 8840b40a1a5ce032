"""What every traffic driver shares.

A traffic mix (``traffic/<mix>.json``) names its ``driver``, a module
``drivers/<driver>.py`` that defines ``DRIVER``, a subclass of
:class:`Driver`: how the window drives the program.  A configuration
names its ``entry``, a module ``entries/<entry>.py``: how the program is
called for one decomposition, how its tensor is made from the seed, and
the kernel-level work one decomposition asks for.  The harness finds both
by those names, so a new loop or a new algorithm is a new file.

An entry module defines:

- ``make_tensor(key, shape, cfg)``: the tensor, made on the device;
- ``init(key, shape, cfg)``: a decomposition's initial state drawn from
  ``key`` (``None`` where the entry starts from the tensor alone);
- ``solve(x, init, cfg, ctx)``: one decomposition through the program,
  finished on the device; a dict with ``fits`` and the answer's arrays;
- ``work(shape, cfg)``: the MTTKRPs or Multi-TTMs one decomposition asks
  for, as ``call(...)`` dicts the roofline readers count.
"""

from __future__ import annotations

import jax

from . import data


def block(tree):
    jax.block_until_ready(tree)
    return tree


def call(kind: str, shape, count: int, **kw) -> dict:
    """One kind of kernel-level call, ``count`` times, at ``shape``."""
    return {"kind": kind, "shape": [int(s) for s in shape],
            "count": int(count), **kw}


class Driver:
    """Common state: the configuration, its entry module, the mix, the
    key and the devices.  A subclass defines ``setup`` (set-up, with the
    warm pass over the window's shapes), ``run_unit`` (one unit of the
    window; returns the decompositions it completed) and
    ``answer_input`` (what the reference needs for one answer)."""

    unit = "solve"

    def __init__(self, cfg: dict, entry, traffic: dict, seed: int, devices,
                 ctx_kw: dict):
        self.cfg = cfg
        self.entry = entry
        self.traffic = traffic
        self.seed = int(seed)
        self.key = data.seed_key(seed)
        self.devices = devices
        self.ctx_kw = ctx_kw
        self.units = 0          # decompositions completed in the window
        self.calls: list[dict] = []
        self.done: list[dict] = []
        #: what a driver measures beyond units and time, for end-to-end
        #: readers (``run.extra``)
        self.extra: dict = {}

    def context(self):
        """The program's execution context, as the configuration states."""
        import repro

        return repro.ExecutionContext.create(backend=self.cfg["backend"],
                                             **self.ctx_kw)

    def work(self) -> list[dict]:
        return list(self.calls)

    def answers(self) -> list[dict]:
        return self.done

    def release(self) -> None:
        """Drop the program's objects before the reference runs."""
        self.ctx = None
