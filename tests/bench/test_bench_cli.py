"""The command refuses to run, and prints no result, without a TPU or
without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


CELL = harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"][0]


def _run(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_env(), capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_exits_nonzero_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directories has no program to measure."""
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    # past the chip check too, the missing program stops the run
    code = ("import sys; sys.path[:0] = ['.']; from bench import harness; "
            "harness.run_cell(harness.load_json('.', 'BENCHMARK.json'), "
            f"{CELL['name']!r}, seed=1, seconds=1, trace=False, t0=0.0, "
            "require_chip=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "repro" in p.stderr
    assert not _has_result(p.stdout)
