"""Sparse k-NN PaLD from features: ``ops.select_cohere(X, **call)`` (the
streaming selection and the knn values kernels on a chip), then
``knn.communities`` of the host copy of the values.

Kept: every job's communities, and the neighbors, distances and values of
the last job and of one drawn from the seed.
Compared with the plain k-NN reference: neighbor lists (``idx_rows``),
their distances (``dist_err``), the values (``val_err``) and the
communities (``comm_faults``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import data, reference as R
from bench.job import Job, knn_arrays, knn_control_kept, knn_numbers

PROGRAM = ("repro.kernels.ops", "select_cohere")
RESULT = "knn"


class SelectCohere(Job):
    has_post = True

    def __init__(self, ctx):
        super().__init__(ctx)
        from repro.core import knn
        from repro.kernels import ops

        cfg = ctx.config
        self.X, _ = data.generator(cfg["generator"])(
            ctx.n, ctx.seed, d=cfg["d"], **cfg.get("params", {}))
        self.Xd = jax.device_put(self.X, ctx.devices[0])
        self.kw = ctx.traffic["call"]
        self._ops, self._knn = ops, knn
        self.comms: list = []
        self.sampled = self.last = None

    def call(self):
        return self._ops.select_cohere(self.Xd, **self.kw)

    def post(self, out):
        graph, vals = out
        return self._knn.communities(graph, np.asarray(vals))

    def keep(self, i, out, host):
        self.comms.append(host)
        if i == self.sample:    # to the host now: no extra device memory
            self.sampled = knn_arrays(out)
        self.last = out

    def collect(self) -> list[dict]:
        return ([knn_arrays(self.last)]
                + ([self.sampled] if self.sampled else [])
                + [dict(communities=c) for c in self.comms])

    def release(self):
        self.Xd = self.last = None

    def reference(self, dtype=jnp.float32) -> dict:
        return R.knn_cohesion(self.X, self.kw["k"], dtype=dtype,
                              devices=self.ctx.devices[:1])

    def as_kept(self, ref) -> list[dict]:
        return knn_control_kept(ref)

    def compare(self, kept, ref) -> dict:
        return knn_numbers(kept, ref, self.band)


build = SelectCohere
