"""A JAX_PLATFORMS=cpu rehearsal of every cell at tiny size: the whole
run (set-up, window, read-back, reference, result line) end to end."""

import pytest

from conftest import cells, run_cell


def process_state():
    import os

    import jax

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)


@pytest.mark.parametrize("cell", cells())
def test_cell_rehearses_on_cpu(tiny_root, capsys, cell):
    before = process_state()
    rc, line = run_cell(tiny_root, capsys, cell)
    assert rc == 0
    # the run puts back what it set in the process
    assert process_state() == before
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["sim_s_per_wall_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    # no device memory reading on the CPU: the metric is left out
    assert "peak_hbm_mib" not in line["metrics"]


@pytest.mark.parametrize("cell", cells()[:1])
def test_traced_rehearsal_reports_counters_only(tiny_root, capsys, cell):
    rc, line = run_cell(tiny_root, capsys, cell, trace=1)
    assert rc == 0 and line["correct"] is True
    # no TPU plane in a CPU trace: device metrics stay silent, never 0
    assert set(line["metrics"]) == {"driver.compiles_in_window"}
    assert line["metrics"]["driver.compiles_in_window"]["value"] == 0
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}
