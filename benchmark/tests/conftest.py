"""Shared fixtures: a shrunken copy of the benchmark (every config cut to
a few hundred hosts) that the harness runs end to end on the CPU."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the cut per config key; only counts change, never widths
TINY = {"n_hosts": 512}


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout-shaped tree: BENCHMARK.json and benchmark/, with every
    config shrunk to TINY. JAX_PLATFORMS=cpu asks for the rehearsal."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    for c in bench["configs"]:
        path = tmp_path / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update({k: v for k, v in TINY.items() if k in cfg})
        path.write_text(json.dumps(cfg))
    return str(tmp_path)


def cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def run_cell(root, capsys, cell, seed=2**31 + 7, seconds=0.5, trace=0):
    """Run the harness in-process; returns (rc, last stdout line)."""
    from benchmark import run

    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])
