"""No result without a TPU, and none without the program beside the
benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
ARGS = ["--workload", "olmo-1b.agent-loop", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(p):
    lines = p.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_refuses_without_a_tpu():
    p = run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert no_result(p)
