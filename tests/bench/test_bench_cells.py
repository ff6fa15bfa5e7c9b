"""Each cell's inputs, job and comparison at a tiny size on the CPU: the
program's answers pass every limit, and the control (the reference in
bfloat16, in the program's place) fails one."""
import jax
import pytest

from bench import control, discover, run
from tiny import tiny_n

MAN = discover.manifest()
SEED = 2**33 + 17


def _devices(cell):
    return jax.devices()[:cell["chips"]]


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_program_passes_every_limit(cell):
    r = run.run_cell(MAN, cell, SEED, 0.2, False, _devices(cell),
                     n=tiny_n(MAN, cell), log=lambda s: None)
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in
                                 discover.end_to_end(MAN, cell)}


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_control_fails_a_limit(cell):
    rows = control.readings(MAN, cell, SEED + 1, True, _devices(cell),
                            n=tiny_n(MAN, cell))
    limits = discover.traffic(cell["traffic"])["limits"]
    assert all(rows["program"][k] <= v for k, v in limits.items())
    assert any(rows["control"][k] > v for k, v in limits.items())
