"""The command refuses to measure where it cannot: with no TPU it exits
non-zero and prints no result, and in a directory that holds only
BENCHMARK.json and the benchmark's own files it cannot run at all."""
import json
import os
import shutil
import subprocess
import sys

from bench import common

ARGS = ["--workload", "smollm-360m.train-int8", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_no_result():
    p = run(common.ROOT)
    assert p.returncode != 0
    assert not has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(common.SPEC, tmp_path / "BENCHMARK.json")
    for d in json.loads(common.SPEC.read_text())["paths"]:
        shutil.copytree(common.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert not has_result(p.stdout)
