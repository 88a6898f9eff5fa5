"""Without a TPU the command exits non-zero and prints no result."""
import os
import subprocess
import sys

from conftest import ROOT


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "dense768.levels", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"device"' not in p.stdout
