"""Test set-up: the benchmark and the system under test on ``sys.path``,
JAX on the CPU."""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
