"""The comparison that decides `correct` on the tgen flow-engine cell
has been shown to fail.

- The control: the plain reference with one stated guarantee broken
  (loss keyed by the flow's index in plan order, not its lane in its
  bucket's world) put in the program's place, compared with the
  reference.
- Faults planted in the timed path underneath a whole harness run
  (`benchmark/proof_flows.py`): one dispatch that hands back the world
  it was given with only its clock moved on, one flow's completion time
  1 us late, and half of the flows' bytes read cut. Each has to turn
  `correct` false."""

import importlib.util
import json
import os

import pytest

from conftest import REPO, run_cell

CELL = "tgen-atlas-1k.floweng"


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_inputs(n_hosts):
    cfg = json.load(open(os.path.join(
        REPO, "benchmark/configs/tgen-atlas-1k.json")))
    cfg.update(n_hosts=n_hosts)
    traffic = json.load(open(os.path.join(
        REPO, "benchmark/traffic/floweng.json")))
    return cfg, traffic


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 5])
def test_control_reads_not_correct(seed):
    drv = load(os.path.join(REPO, "benchmark/drivers/tgen_flows.py"), "drv")
    cfg, traffic = cell_inputs(512)
    cfg.update(graph_seed=seed)
    ref = drv._load_reference(cfg["reference"])
    dep = ref.deployment(cfg, traffic, seed)
    plain = drv.make_reference(ref, cfg, dep)
    want = plain.run()
    got = drv.make_reference(ref, cfg, dep, "plan_order_loss").run()
    found = ref.compare(got, want, [f["size"] for f in plain.flows])
    assert found["flows_differing"] > cfg["limits"]["flows_differing"]
    assert found["stats_gap"] > cfg["limits"]["stats_gap"]


@pytest.fixture
def small_root(tiny_root):
    """The tiny tree with the tgen config cut further, to 100 hosts."""
    path = os.path.join(tiny_root, "benchmark/configs/tgen-atlas-1k.json")
    cfg = json.load(open(path))
    cfg.update(n_hosts=100)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return tiny_root


@pytest.mark.parametrize("kind", ["unchanged", "late", "half"])
def test_planted_fault_reads_not_correct(small_root, capsys, monkeypatch,
                                         kind):
    from benchmark import proof_flows
    from shadow_tpu.tpu import floweng

    for name, fn in proof_flows.planted(kind, floweng).items():
        monkeypatch.setattr(floweng, name, fn)
    rc, line = run_cell(small_root, capsys, CELL, seconds=0.2)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_traced_window_makes_one_run(small_root, capsys):
    """A window long enough for two runs or more (about 2 s each on
    the CPU) makes one under a trace."""
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", str(2**31 + 3),
                     "--seconds", "6", "--trace", "1"],
                    root=small_root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    info, line = json.loads(lines[-2])["info"], json.loads(lines[-1])
    assert line["correct"] is True, line["checks"]
    assert info["runs"] == 1


def test_driver_tells_a_traced_window_or_refuses(monkeypatch, tmp_path):
    import jax
    from jax._src import profiler

    drv = load(os.path.join(REPO, "benchmark/drivers/tgen_flows.py"), "drv")
    assert drv.tracing_now() is False
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert drv.tracing_now() is True
    finally:
        jax.profiler.stop_trace()
    # a JAX that keeps its profiler session elsewhere: no guess
    monkeypatch.setattr(profiler, "_profile_state", None)
    with pytest.raises(RuntimeError, match="profile_session"):
        drv.tracing_now()
