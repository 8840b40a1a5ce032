"""``rounds``: a closed loop through ``DecompositionServer``.

Set-up makes a pool of one tensor for each of the mix's ``shapes``, in an
order drawn from the seed, and runs warm rounds until every pool tensor
has been served once.  Each round of the window submits the next
``batch`` of them and calls ``flush``.  Every seed serves the same sizes.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchlib import say
from benchlib.driver import Driver, block


class Rounds(Driver):

    unit = "round"

    def setup(self) -> None:
        from repro.launch.serve import DecompositionServer

        cfg, t = self.cfg, self.traffic
        rng = np.random.default_rng(self.seed)
        order = rng.permutation(len(t["shapes"]))
        self.shapes = [tuple(int(e) for e in t["shapes"][i]) for i in order]
        full = tuple(max(s[k] for s in self.shapes)
                     for k in range(len(self.shapes[0])))
        self.pool = []
        for m, shp in enumerate(self.shapes):
            x = self.entry.make_tensor(jax.random.fold_in(self.key, 100 + m),
                                       full, cfg)
            self.pool.append(x[tuple(slice(0, e) for e in shp)])
        block(self.pool)
        say(f"pool of {len(self.pool)} made")
        self.ctx = self.context()
        self.server = DecompositionServer(
            self.ctx, n_iters=int(cfg["sweeps_per_solve"]), tol=0.0)
        self.submitted = 0
        self.round_no = 0
        batch, pool = int(t["batch"]), len(self.shapes)
        t0 = time.perf_counter()
        for _ in range(-(-pool // batch)):
            self._round(keep=False)
        say(f"warm rounds {time.perf_counter() - t0:.3f} s")

    def _round(self, keep: bool) -> int:
        batch, pool = int(self.traffic["batch"]), len(self.shapes)
        ids = []
        for b in range(batch):
            m = (self.round_no * batch + b) % pool
            self.submitted += 1
            rid = f"r{self.round_no}.{b}"
            self.server.submit(self.pool[m], int(self.cfg["rank"]),
                               request_id=rid)
            # the server seeds request k (counting from 1) with PRNGKey(k)
            ids.append((rid, m, self.submitted))
        out = self.server.flush()
        block([(out[r].factors, out[r].weights) for r, _, _ in ids])
        self.round_no += 1
        if keep:
            for rid, m, k in ids:
                r = out[rid]
                self.done.append({"id": rid, "pool": m, "init_seed": k,
                                  "factors": r.factors, "weights": r.weights,
                                  "fits": [r.fit]})
        return len(ids)

    def run_unit(self) -> int:
        n = self._round(keep=True)
        self.units += n
        for a in self.done[-n:]:
            self.calls.extend(self.entry.work(self.shapes[a["pool"]],
                                              self.cfg))
        return n

    def answer_input(self, answer: dict) -> dict:
        x = self.pool[answer["pool"]]
        init = self.entry.init(jax.random.PRNGKey(answer["init_seed"]),
                               tuple(x.shape), self.cfg)
        return {"x": x, "init": init}

    def release(self) -> None:
        self.server = None
        self.ctx = None


DRIVER = Rounds
