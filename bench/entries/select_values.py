"""Sparse k-NN PaLD from features with no host stage:
``ops.select_cohere(X, **call)``, the selection and knn values kernels on a
chip and the neighbor cube between them.

Kept: the neighbors, distances and values of the last job and of one drawn
from the seed.  Compared with the plain k-NN reference: neighbor lists
(``idx_rows``), their distances (``dist_err``) and the values
(``val_err``).  ``explain()`` gives the neighbor cube's layout as the
program derives it: rows a chunk, chunks, and the bytes a chunk stages.
"""
from __future__ import annotations

from bench import discover
from bench.job import knn_arrays

_base = discover.module("entries", "select_cohere")
PROGRAM = _base.PROGRAM
RESULT = _base.RESULT


class SelectValues(_base.SelectCohere):
    has_post = False

    def explain(self) -> dict:
        layout = getattr(self._ops, "knn_cube_layout", None)
        if layout is None:      # the cell also runs against programs without it
            return {}
        return {"cube": layout(self.n, self.kw["k"], self.ctx.config["d"])}

    def post(self, out):
        return None

    def keep(self, i, out, host):
        if i == self.sample:
            self.sampled = knn_arrays(out)
        self.last = out

    def collect(self) -> list[dict]:
        return ([knn_arrays(self.last)]
                + ([self.sampled] if self.sampled else []))

    def as_kept(self, ref) -> list[dict]:
        return [ref]


build = SelectValues
