"""Mesh-sharded k-NN PaLD from features:
``distributed_knn.pald_knn_sharded(X, mesh, **call)`` on X put once,
row-sharded over the configuration's mesh of the cell's devices.  No host
stage: the result stays row-sharded on the chips.

Kept: the neighbors, distances and values of the last job and of one drawn
from the seed, the drawn one copied to the host as its job ends.  Compared
with the plain k-NN reference, its rows spread over all of the cell's
devices: every row's neighbor list (``idx_rows``), distances (``dist_err``)
and values (``val_err``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import data, reference as R
from bench.job import Job, knn_arrays, knn_numbers

PROGRAM = ("repro.core.distributed_knn", "pald_knn_sharded")
RESULT = "knn"


class KnnSharded(Job):
    def __init__(self, ctx):
        super().__init__(ctx)
        from repro.core import distributed_knn

        cfg = ctx.config
        spec = cfg["mesh"]
        self.mesh = Mesh(mesh_utils.create_device_mesh(
            tuple(spec["shape"]), devices=ctx.devices), tuple(spec["axes"]))
        self.X, _ = data.generator(cfg["generator"])(
            ctx.n, ctx.seed, d=cfg["d"], **cfg.get("params", {}))
        self.Xs = jax.device_put(self.X, NamedSharding(
            self.mesh, P(tuple(spec["rows"]), None)))
        self.kw = ctx.traffic["call"]
        self._dknn = distributed_knn
        self.sampled = self.last = None

    def explain(self) -> dict:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return {"mesh": sizes, "strategy": self.kw.get("strategy"),
                "comm": self._dknn.comm_estimate(
                    self.kw.get("strategy", "auto"), n=self.n,
                    d=self.ctx.config["d"], k=self.kw["k"],
                    p=self.mesh.devices.size,
                    pr=self.mesh.devices.size // self.mesh.devices.shape[-1],
                    pc=self.mesh.devices.shape[-1])}

    def call(self):
        return self._dknn.pald_knn_sharded(self.Xs, self.mesh, **self.kw)

    def keep(self, i, out, host):
        if i == self.sample:    # to the host now: no extra device memory
            self.sampled = knn_arrays(out)
        self.last = out

    def collect(self) -> list[dict]:
        return [knn_arrays(self.last)] + ([self.sampled] if self.sampled
                                          else [])

    def release(self):
        self.Xs = self.last = None

    @property
    def work(self) -> dict:
        return dict(super().work, p=len(self.ctx.devices))

    def reference(self, dtype=jnp.float32) -> dict:
        return R.knn_cohesion(self.X, self.kw["k"], dtype=dtype,
                              devices=self.ctx.devices)

    def as_kept(self, ref) -> list[dict]:
        return [ref]

    def compare(self, kept, ref) -> dict:
        return knn_numbers(kept, ref, self.band)


build = KnnSharded
