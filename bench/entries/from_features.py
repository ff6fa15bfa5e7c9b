"""The public feature facade: ``pald.from_features(X, **call)``, which plans
per call and returns the dense (n, n) C users get.

Kept: the last job's C, and the row sums (local depths) of one earlier job
drawn from the seed, taken on the device as that job ends.  Compared with
the plain k-NN reference placed densely: the whole last C (``c_err``: the
entries ``scatter_dense`` places and the zeros around them) and the drawn
job's local depths (``depth_err``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import data, reference as R
from bench.job import Job, rel_err

PROGRAM = ("repro.core.pald", "from_features")
RESULT = "dense"


_row_sums = jax.jit(lambda C: jnp.sum(C, axis=1))


class FromFeatures(Job):
    def __init__(self, ctx):
        super().__init__(ctx)
        from repro.core import pald

        cfg = ctx.config
        self.X, _ = data.generator(cfg["generator"])(
            ctx.n, ctx.seed, d=cfg["d"], **cfg.get("params", {}))
        self.Xd = jax.device_put(self.X, ctx.devices[0])
        self.kw = ctx.traffic["call"]
        self._pald = pald
        self.last = None
        self.depths = None

    def explain(self) -> dict:
        ex = self._pald.plan(self.Xd, kind="features", **self.kw).explain()
        return {k: ex.get(k) for k in ("method", "impl", "block",
                                       "block_source", "select_block",
                                       "select_tile", "select_source",
                                       "degradations")}

    def call(self):
        return self._pald.from_features(self.Xd, **self.kw)

    def warm(self, out, host):
        _row_sums(out).block_until_ready()

    def keep(self, i, out, host):
        if i == self.sample:
            self.depths = _row_sums(out)
        self.last = out

    def collect(self) -> list[dict]:
        kept = [dict(C=np.asarray(self.last))]
        if self.depths is not None:
            kept.append(dict(depths=np.asarray(self.depths, np.float64)))
        return kept

    def release(self):
        self.Xd = self.last = self.depths = None

    def reference(self, dtype=jnp.float32) -> dict:
        return R.knn_cohesion(self.X, self.kw["k"], dtype=dtype,
                              devices=self.ctx.devices[:1])

    def _dense(self, ref) -> np.ndarray:
        n = self.n
        C = np.zeros((n, n), np.float32)
        C[np.arange(n)[:, None], ref["indices"]] = ref["values"][:, 1:]
        C[np.arange(n), np.arange(n)] = ref["values"][:, 0]
        return C

    def as_kept(self, ref) -> list[dict]:
        return [dict(C=self._dense(ref)),
                dict(depths=ref["values"].sum(axis=1))]

    def compare(self, kept, ref) -> dict:
        out = dict(c_err=0.0, depth_err=0.0)
        dense = self._dense(ref)
        for a in kept:
            if "C" in a:
                out["c_err"] = max(out["c_err"], rel_err(a["C"], dense))
            if "depths" in a:
                out["depth_err"] = max(out["depth_err"], rel_err(
                    a["depths"], ref["values"].sum(axis=1)))
        return out


build = FromFeatures
