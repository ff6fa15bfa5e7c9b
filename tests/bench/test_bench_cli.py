"""The command refuses to run without a chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench.run import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grqc-dense-tri",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_no_chip_exits_nonzero_without_a_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
    assert "TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
