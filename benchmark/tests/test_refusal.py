"""The harness refuses what it cannot measure: no TPU unless the CPU was
asked for, fewer chips than the cell needs, a device kind with no peaks,
and a tree that holds only the benchmark."""

import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from benchmark import run
from conftest import REPO


def fake(platform, kind, n=1):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_unknown_device_kind_is_refused(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("tpu", "TPU v99"))
    with pytest.raises(run.Refused, match="not in benchmark/peaks.json"):
        run.check_device(REPO, {"chips": 1})


def test_known_device_kind_gives_its_peaks(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("tpu", "TPU v5 lite"))
    devs, peaks = run.check_device(REPO, {"chips": 1})
    assert peaks["hbm_bytes_per_s"] == 819e9 and len(devs) == 1


def test_cpu_not_asked_for_is_refused(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setattr(jax, "devices", lambda: fake("cpu", "cpu"))
    with pytest.raises(run.Refused, match="no TPU"):
        run.check_device(REPO, {"chips": 1})


def test_too_few_chips_is_refused(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("tpu", "TPU v5 lite"))
    with pytest.raises(run.Refused, match="needs 4 chips"):
        run.check_device(REPO, {"chips": 4})


def test_tree_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "phold-32k.load4", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
