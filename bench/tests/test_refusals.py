"""bench/run.py refuses, with a non-zero exit and no result line, off the
chip and outside a full checkout."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rwkv6-7b-q3.decode",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(l.lstrip().startswith("{") for l in p.stdout.splitlines())


def test_refuses_on_cpu():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    _no_result(p)
    assert "src/repro" in p.stderr
