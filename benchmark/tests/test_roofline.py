"""The least-bytes count behind `window_step.hbm_roofline`, against a
hand count."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def metric():
    path = os.path.join(HERE, os.pardir, "metrics",
                        "window_step.hbm_roofline.py")
    spec = importlib.util.spec_from_file_location("roof", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_least_bytes_of_hand_shapes():
    shapes = [((2, 3), "int32"), ((2, 3), "bool"), ((2,), "int32")]
    # read once + written once: 2 * (24 + 6 + 8)
    assert metric().least_bytes(shapes) == 76


def test_least_bytes_of_a_tiny_plane_state():
    import jax

    from shadow_tpu.tpu import make_state

    N, CE, CI = 2, 2, 3
    st = make_state(N, egress_cap=CE, ingress_cap=CI)
    shapes = [(x.shape, str(x.dtype)) for x in jax.tree_util.tree_leaves(st)]
    # per host: egress 7 int32 + 2 bool per slot; ingress 5 int32 + 1 bool
    # per slot; 3 token/rng int32; 16 RR int32; router 13 int32 + 3 bool;
    # 5 counters int32
    per_host = CE * (7 * 4 + 2) + CI * (5 * 4 + 1) + 3 * 4 + 16 * 4 \
        + 13 * 4 + 3 + 5 * 4
    assert metric().least_bytes(shapes) == 2 * N * per_host


def test_roofline_share_from_device_time():
    # 8000 bytes a window at 8 GB/s is 1 us; 2 us of device time -> 50%
    assert metric().share([((1000,), "int32")], 8e9, 2e-3) == \
        pytest.approx(50.0)
