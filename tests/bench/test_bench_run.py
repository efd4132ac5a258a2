"""``bench/run.py`` refuses to run without the chip, and without the
program under test, printing no result line."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import registry

ARGS = ["--workload", "spfresh1b-spacev-shard.churn", "--seed",
        str(2**31 + 7), "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(registry.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr.lower()


def test_benchmark_files_alone_no_result(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's own
    files there is no program to measure."""
    bench = registry.load()
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(registry.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
