"""What the benchmark's CPU tests take from a cell's own files, so that a
new configuration or entry joins them with no edit here: the size a
configuration runs at on the CPU, and the program function an entry drives
with the form of its result."""
import importlib

from bench import discover
from bench.run import ROOT

# for a configuration without ``test_n``, by its generator; small, since
# the Pallas kernels run in interpret mode on the CPU
GENERATOR_N = {"collaboration_graph": 160, "clusters": 512}
# "dense": one array; "knn": a (NeighborGraph, values) pair
RESULTS = ("dense", "knn")


def tiny_n(man, cell, root=ROOT) -> int:
    """The configuration's ``test_n``, else its generator's default."""
    cfg = discover.config(man, cell, root)
    return cfg.get("test_n") or GENERATOR_N[cfg["generator"]]


def program(cell, root=ROOT):
    """(module, attribute, result form) of the program function that the
    cell's entry declares it drives (its ``PROGRAM`` and ``RESULT``)."""
    entry = discover.module(
        "entries", discover.traffic(cell["traffic"], root)["entry"], root)
    mod, name = entry.PROGRAM
    return importlib.import_module(mod), name, entry.RESULT
