"""The command refuses to run where it cannot measure."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ARGS = ["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *BENCH["command"][1:], *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    last = (p.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{"), last


def test_refuses_a_cpu_device():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p)


def test_refuses_a_checkout_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p)
