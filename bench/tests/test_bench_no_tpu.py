"""Without a TPU the command exits non-zero and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "linear-m8-fit",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "{" not in out.stdout
