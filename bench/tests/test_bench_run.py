"""The benchmark's command refuses to run without a chip, and without the
program beside it, and prints no result either way."""

import json
import os
import shutil
import subprocess
import sys

from bench import layout

CMD = ["bench/run.py", "--workload", "lenet5.online", "--seed", "3",
       "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + CMD, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_a_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run(layout.ROOT)
    assert proc.returncode != 0
    assert not _printed_a_result(proc.stdout)
    assert "runs only on a TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(layout.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(layout.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _printed_a_result(proc.stdout)
