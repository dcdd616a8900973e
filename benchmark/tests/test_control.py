"""The comparison that decides `correct` has been shown to fail.

- The control: the plain reference with one stated guarantee broken
  (deliveries due at the same time presented in (seq, src) order, not
  (src, seq)) put in the program's place, compared with the reference.
- Faults planted in the timed path underneath a whole harness run: a
  window step that returns its state unchanged, half of the hosts'
  deliveries left out of the respawn, and one delivered packet altered
  where the step produces it. Each has to turn `correct` false.
(One chip, no exchange between chips: that fault has no place here.)"""

import importlib.util
import json
import os

import pytest

from conftest import REPO, run_cell

CELLS = ["phold-32k.load4", "phold-32k.load1"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("load_name", ["load4", "load1"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 5])
def test_control_reads_not_correct(load_name, seed):
    drv = load(os.path.join(REPO, "benchmark/drivers/phold.py"), "drv")
    ref = load(os.path.join(REPO, "benchmark/reference/phold.py"), "ref")
    cfg = json.load(open(os.path.join(REPO,
                                      "benchmark/configs/phold-32k.json")))
    cfg.update(n_hosts=512)
    traffic = json.load(open(os.path.join(
        REPO, f"benchmark/traffic/{load_name}.json")))
    inp = drv.make_inputs(cfg, traffic, seed)
    want = ref.PholdReference(inp).run(48).dense()
    got = ref.PholdReference(inp, control="tiebreak").run(48).dense()
    got["rounds"] = 48
    found = ref.compare(got, want)
    assert found["hosts_differing"] > cfg["limits"]["hosts_differing"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_planted_fault_reads_not_correct(tiny_root, capsys, monkeypatch,
                                         cell, kind):
    from benchmark import faults
    from shadow_tpu import tpu

    monkeypatch.setattr(tpu, "window_step",
                        faults.planted(kind, tpu.window_step))
    rc, line = run_cell(tiny_root, capsys, cell, seconds=0.2)
    assert rc == 0
    assert line["correct"] is False, line["checks"]
