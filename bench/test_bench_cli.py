"""Without a TPU the run command prints no result and fails (CPU)."""
import os
import pathlib
import subprocess
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "relayout-qwen3-1.7b-kv", "--seed", str(2**32 + 3), "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout
