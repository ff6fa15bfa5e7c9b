"""What every entry's job gives the harness, and the comparisons they share.

An entry (``bench/entries/<entry>.py``) has ``build(ctx) -> Job``.  The
harness calls ``call()`` back to back through the window (and ``post(out)``
after each call, when the job has a host stage), hands each result to
``keep``, then ``collect()``s the kept answers as numpy, ``release()``s the
program's state, and compares them with ``reference(float32)``.  The
control is ``reference(bfloat16)`` put through ``as_kept`` in the
program's place.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference as R


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    devices: list
    n: int


class Job:
    has_post = False

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.n = ctx.n
        self.band = ctx.traffic.get("band", 0.0)
        # the earlier job whose whole answer is kept beside the last one's
        self.sample = int(np.random.default_rng(ctx.seed).integers(
            ctx.traffic.get("sample_jobs", 2)))

    def post(self, out):
        return None

    def warm(self, out, host) -> None:
        """Compile whatever ``keep`` runs on the device, in set-up."""

    def explain(self) -> dict:
        return {}

    @property
    def work(self) -> dict:
        """The sizes the kernels' work counts take (n, d, k)."""
        return dict(n=self.n, d=self.ctx.config.get("d"),
                    k=self.ctx.traffic.get("call", {}).get("k"))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| over the largest |want|."""
    scale = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))) / scale


def knn_numbers(kept: list[dict], ref: dict, band: float) -> dict:
    """Worst over the kept answers: rows whose neighbor list differs from
    the reference's, relative error of the neighbor distances and of the
    sparse values, and points placed against the reference's strong
    ties."""
    out = dict(idx_rows=0, dist_err=0.0, val_err=0.0, comm_faults=0)
    certain, possible = R.knn_strong_pairs(ref["indices"], ref["values"],
                                           band)
    d = ref["distances"]
    for a in kept:
        if "indices" in a:
            out["idx_rows"] = max(out["idx_rows"], int(
                (a["indices"] != ref["indices"]).any(axis=1).sum()))
            out["dist_err"] = max(out["dist_err"], float(np.max(
                np.abs(a["distances"] - d) / np.maximum(d, 1e-30))))
            out["val_err"] = max(out["val_err"], rel_err(a["values"],
                                                         ref["values"]))
        if "communities" in a:
            out["comm_faults"] = max(out["comm_faults"], R.partition_faults(
                len(d), a["communities"], certain, possible))
    return out


def knn_arrays(out) -> dict:
    """A k-NN job's (graph, values) as numpy."""
    graph, vals = out
    return dict(indices=np.asarray(graph.indices),
                distances=np.asarray(graph.distances, np.float64),
                values=np.asarray(vals, np.float64))


def knn_control_kept(ref: dict) -> list[dict]:
    """A k-NN reference in the program's place, with its own communities."""
    certain, _ = R.knn_strong_pairs(ref["indices"], ref["values"], 0.0)
    return [dict(ref, communities=components(len(ref["indices"]), certain))]


def components(n: int, pairs) -> list[list[int]]:
    labels = R.component_labels(n, *pairs)
    order = np.argsort(labels, kind="stable")
    cuts = np.flatnonzero(np.diff(labels[order])) + 1
    return [g.tolist() for g in np.split(order, cuts)]
