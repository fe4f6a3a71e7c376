"""Off the chip the command prints no result and exits non-zero: with no
TPU, in a rehearsal at a small size, and in a directory that holds only
the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["bench/run.py", "--workload", "tpch10m.q2-h1to7", "--seed",
        "4000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd, extra=(), timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *ARGS, *extra], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout, p.stderr


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj)


def test_no_chip_exits_nonzero():
    rc, out, err = _run(ROOT)
    assert rc != 0 and "no chip" in err
    _no_result(out)


def test_rehearsal_runs_every_step_and_exits_nonzero():
    rc, out, err = _run(ROOT, ["--rows", "20000"])
    assert rc != 0, err
    assert "every step ran" in err and "check not_ok: 0" in err
    _no_result(out)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, _ = _run(str(tmp_path), timeout=120)
    assert rc != 0
    _no_result(out)
