"""The command refuses to run, and prints no result, off the chip, in a
directory without the program, and for an unknown cell."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import registry

ROOT = registry.ROOT


def run(cwd, *args, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "g500-s21.keys64", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def assert_no_result(out):
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "metrics" not in out.stdout


def test_cpu_run_exits_nonzero_without_metrics():
    out = run(ROOT, *ARGS)
    assert_no_result(out)
    assert "no TPU" in out.stderr


def test_unknown_workload_is_refused():
    out = run(ROOT, "--workload", "nope", "--seed", "1", "--seconds", "1")
    assert_no_result(out)
    assert "no workload named 'nope'" in out.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run(tmp_path, *ARGS)
    assert_no_result(out)
    assert "no src/repro" in out.stderr


@pytest.mark.parametrize("trace", ["2", "x"])
def test_bad_arguments_are_refused(trace):
    out = run(ROOT, *ARGS[:-1], trace)
    assert_no_result(out)
