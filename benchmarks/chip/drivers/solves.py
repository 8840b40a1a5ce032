"""``solves``: one tensor made in set-up, of the mix's ``shape`` or, where
the mix gives none, of the configuration's; the window
runs whole decompositions on it, each one call of the configuration's
entry to its fixed number of sweeps with ``tol=0``, from a fresh initial
state drawn from the seed (where the entry takes one)."""

from __future__ import annotations

import time

import jax

from benchlib import say
from benchlib.driver import Driver, block


class Solves(Driver):

    def setup(self) -> None:
        shape = self.traffic.get("shape") or self.cfg["shape"]
        shape = tuple(int(s) for s in shape)
        self.ctx = self.context()
        self.x = block(self.entry.make_tensor(jax.random.fold_in(self.key, 0),
                                              shape, self.cfg))
        t = time.perf_counter()
        # one warm solve on the window's shapes, not counted
        self._solve(-1)
        say(f"warm solve {time.perf_counter() - t:.3f} s")

    def _init(self, i: int):
        return self.entry.init(jax.random.fold_in(self.key, 1000 + i),
                               tuple(self.x.shape), self.cfg)

    def _solve(self, i: int) -> dict:
        out = self.entry.solve(self.x, self._init(i), self.cfg, self.ctx)
        return {"id": i, **out}

    def run_unit(self) -> int:
        self.done.append(self._solve(self.units))
        self.units += 1
        self.calls.extend(self.entry.work(self.x.shape, self.cfg))
        return 1

    def answer_input(self, answer: dict) -> dict:
        """The tensor and the same initial state."""
        return {"x": self.x, "init": self._init(answer["id"])}


DRIVER = Solves
