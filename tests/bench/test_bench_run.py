"""``bench/run.py`` refuses to measure without a TPU, and without the
program beside it: a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

import tiny


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "tconst41m.stream-long", "--seed", str(2 ** 31 + 5), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(p):
    return not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_no_tpu_no_result():
    p = _run(tiny.ROOT)
    assert p.returncode != 0 and _no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p)


def test_run_finds_the_program_beside_it():
    # the harness puts src/ on the path itself: run.py starts with only
    # bench/ importable
    code = ("import sys; sys.path.insert(0, 'bench'); import cell; "
            "cell.use_program(); import repro.serving.scheduler")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=tiny.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
