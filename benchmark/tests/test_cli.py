"""``run.py`` from the outside: refuses without a TPU and prints no result
line; refuses an unknown cell."""

import os
import subprocess
import sys

from harness import spec


def _run(*argv, cwd=spec.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), *argv],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=180)


def test_exits_nonzero_without_a_tpu_and_prints_no_result_line():
    proc = _run("--workload", "c4_train_b8", "--seed", "3", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_an_unknown_cell_is_refused():
    proc = _run("--workload", "no_such_cell", "--seed", "3", "--seconds", "1")
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "unknown workload" in proc.stderr


def test_takes_no_notice_of_bench_run(monkeypatch):
    import run as bench_run

    monkeypatch.setenv("BENCH_RUN", "parent-7")
    args = bench_run.parse_args(
        ["--workload", "x", "--seed", str(2**31 + 5), "--seconds", "2"])
    assert args.seed == 2**31 + 5 and args.trace == 0
