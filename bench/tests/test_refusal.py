"""The measuring path refuses a CPU, and a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

import harness

WORKLOAD = json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
ARGS = ["--workload", WORKLOAD, "--seed", "3000000001", "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def no_result(p):
    last = (p.stdout.strip().splitlines() or [""])[-1]
    return not last.startswith("{")


def test_cpu_is_refused():
    p = run(harness.ROOT)
    assert p.returncode != 0 and no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0 and no_result(p)
