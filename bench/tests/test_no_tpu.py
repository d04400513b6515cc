"""Without a TPU the command fails and prints no result line; so it does
in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench import common


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-ovr-train",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_means_no_result():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _has_result(p.stdout)


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
