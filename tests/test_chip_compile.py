"""Compiles for a described TPU v5e — no chip attached, no chip time.

The TPU compiler installed here refuses what the chip would refuse: an
op the TPU backend cannot lower, a program that does not fit the
device. The CPU backend shows none of that, so the window step is
compiled here at the bench widths (N=32,768 hosts, egress cap 16,
ingress cap 32) in each of its qdisc/destination variants and held to
the chip's 16 GiB of HBM.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""

import os

import pytest

N, CE, CI, NODES = 32_768, 16, 32, 64
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype="int32"):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("rr_enabled,router_aqm",
                         [(False, False), (True, False), (False, True)],
                         ids=["lean", "rr", "router_aqm"])
def test_window_step_xla_fits_v5e_hbm(one_chip, rr_enabled, router_aqm):
    import jax
    import numpy as np

    from shadow_tpu.tpu import make_params, make_state, window_step

    lat = np.full((NODES, NODES), 5_000_000, np.int32)
    params = make_params(lat, np.full((NODES, NODES), 0.01, np.float32),
                         np.full((N,), 10_000_000_000, np.int64),
                         host_node=(np.arange(N) % NODES).astype(np.int32))
    state = make_state(N, egress_cap=CE, ingress_cap=CI)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=one_chip)
    key = jax.random.key(0)
    step = jax.jit(lambda st, pr, k, sh, w: window_step(
        st, pr, k, sh, w, rr_enabled=rr_enabled, router_aqm=router_aqm))
    compiled = step.lower(
        jax.tree.map(spec, state), jax.tree.map(spec, params), spec(key),
        _spec(one_chip, ()), _spec(one_chip, ())).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"
