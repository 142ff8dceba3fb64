import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(*extra: str) -> dict:
    """`run.py --rehearse` in a child (tiny size, CPU backend) → its
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--rehearse", "--seconds", "3", "--trace", "0", *extra],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture
def cell_runner():
    return run_cell
