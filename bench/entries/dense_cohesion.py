"""Dense PaLD from a distance matrix: ``pald.cohesion(D, **call)`` on a
device-resident D, then ``analysis.communities`` of the host copy of C.

Kept: every job's host C and its communities.  Compared: the whole C with
the plain reference (``c_err``) and the communities with the reference's
strong ties (``comm_faults``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import data, reference as R
from bench.job import Job, components, rel_err

PROGRAM = ("repro.core.pald", "cohesion")
RESULT = "dense"


class DenseCohesion(Job):
    has_post = True

    def __init__(self, ctx):
        super().__init__(ctx)
        from repro.core import analysis, pald

        cfg = ctx.config
        self.D = data.generator(cfg["generator"])(ctx.n, seed=ctx.seed,
                                                  **cfg.get("params", {}))
        self.Dd = jax.device_put(self.D, ctx.devices[0])
        self.kw = ctx.traffic["call"]
        self._pald, self._communities = pald, analysis.communities
        self.kept: list[dict] = []

    def explain(self) -> dict:
        ex = self._pald.plan(self.Dd, **self.kw).explain()
        return {k: ex.get(k) for k in ("method", "schedule", "impl", "block",
                                       "block_z", "block_source",
                                       "degradations")}

    def call(self):
        return self._pald.cohesion(self.Dd, **self.kw)

    def post(self, C):
        Ch = np.asarray(C)
        return Ch, self._communities(Ch)

    def keep(self, i, out, host):
        self.kept.append(dict(C=host[0], communities=host[1]))

    def collect(self) -> list[dict]:
        return self.kept

    def release(self):
        self.Dd = None

    def reference(self, dtype=jnp.float32) -> dict:
        return dict(C=R.dense_cohesion(self.D, dtype=dtype,
                                       device=self.ctx.devices[0]))

    def as_kept(self, ref) -> list[dict]:
        certain, _ = R.dense_strong_pairs(ref["C"], 0.0)
        return [dict(C=ref["C"], communities=components(self.n, certain))]

    def compare(self, kept, ref) -> dict:
        certain, possible = R.dense_strong_pairs(ref["C"], self.band)
        return dict(
            c_err=max(rel_err(a["C"], ref["C"]) for a in kept),
            comm_faults=max(R.partition_faults(self.n, a["communities"],
                                               certain, possible)
                            for a in kept))


build = DenseCohesion
