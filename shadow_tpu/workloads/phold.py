"""The PHOLD respawn generator — the workload plane's oldest resident.

Relocated from `tpu/profiling.py` so the profiler module is
measurement-only again: PHOLD is a *workload* (the classic PDES
closed-loop benchmark Shadow ships configs for, `src/test/phold/`),
and every traffic source now lives under `shadow_tpu/workloads/`.
`tpu/profiling.respawn_batch` remains as a back-compat re-export;
bench.py / chaos_smoke / the profiler all import this home.
"""

from __future__ import annotations


def respawn_batch(delivered, spawn_seq, round_idx, n_hosts: int,
                  ingress_cap: int):
    """The PHOLD bench's deterministic respawn batch: each delivered
    packet triggers one new packet from the receiving host to a hashed
    destination (FIFO-ish priority = seq). ONE definition shared by
    `bench.py`'s scan body and the profiler's `ingest_rows` section,
    so the profiled batch is exactly the batch the bench feeds it —
    any workload change here changes both with it. Returns
    (valid_mask, dst, nbytes, seq, ctrl), all [N, CI]. Its ops carry
    the named scope `respawn` into the compiled program."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("respawn"):
        mask = delivered["mask"]
        dst = (delivered["src"] * 40503
               + delivered["seq"] * 1566083941 + round_idx * 97) % n_hosts
        # seq rank = position among the row's DUE lanes, not the raw
        # column index: due lanes sit at the row TAIL of the delivered
        # arrays, so a column-index rank would bake the ring capacity
        # into every respawned seq — making the PHOLD stream
        # capacity-dependent and breaking the elastic-growth parity
        # contract (docs/determinism.md "Growth is bitwise-invisible").
        # The cumsum rank is identical at any CI.
        rank = jnp.where(
            mask, jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0)
        seq = spawn_seq[:, None] + rank
        nbytes = jnp.full((n_hosts, ingress_cap), 1400, jnp.int32)
        ctrl = jnp.zeros((n_hosts, ingress_cap), bool)
    return mask, dst, nbytes, seq, ctrl
