"""With no TPU the benchmark's commands exit nonzero and print no result:
they never fall back to the CPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("argv", [
    ["bench/run_cell.py", "--workload", "aol.first-term", "--seed", "1",
     "--seconds", "1", "--trace", "0"],
    ["bench/sweep.py", "--config", "qac-aol", "--traffic", "first-term",
     "--seeds", "1", "--rates", "1"],
    ["bench/control.py", "--workload", "aol.first-term", "--seeds", "1",
     "--seconds", "1"],
], ids=["run_cell", "sweep", "control"])
def test_no_tpu_means_no_result(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
