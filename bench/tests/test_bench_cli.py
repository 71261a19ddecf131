"""``bench/run.py`` exits 1 and prints no result where JAX finds no TPU, in
the repository and in a directory that holds only the benchmark's files."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness


@pytest.mark.parametrize("bare", [False, True])
def test_no_chip_no_result(bare, tmp_path):
    root = harness.ROOT
    if bare:
        shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
        for p in harness.load_spec()["paths"]:
            shutil.copytree(os.path.join(root, p), tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
        root = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    workload = harness.load_spec()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        workload, "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
