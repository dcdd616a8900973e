"""Unit tests for analysis/batchdim — the SL701–703 world-axis proofs.

Three layers:

* synthetic SL701 vectors: tiny jaxprs with a known world-axis story
  (clean per-world math stays clean; cross-world reduces, slices, and
  shared-operand scatters fire);
* the SL702 fold-chain prover on known-good and known-bad derivations;
* SL703 refusal hygiene on injected entries/refusals.

One REAL registry entry (``window_step[lean]`` at W=2) is proved in
tier-1 as the smoke link between the synthetic vectors and the full
``check_all_batch`` sweep, which is @slow (CI runs it unfiltered in
the gating proof step).
"""

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.analysis import batchdim, jaxpr_audit


def _axis_findings(fn, *args, w=None):
    w = args[0].shape[0] if w is None else w
    closed = jax.make_jaxpr(fn)(*args)
    return batchdim.world_axis_findings(closed, "test:synthetic", w)


# -- SL701 synthetic vectors: clean cases ----------------------------------

def test_per_world_elementwise_and_row_reduce_clean():
    def f(x):
        return jnp.sum(x * 2.0 + 1.0, axis=1)

    findings, row = _axis_findings(f, jnp.ones((2, 4)))
    assert not findings and row["proved"]
    # the world axis survives to the output at dim 0
    assert row["out_world_dims"] == [0]


def test_vmapped_shared_table_gather_clean():
    """Per-world indices into a world-FREE (closed-over) table: reads
    from shared constants are fine; only shared WRITES cross worlds."""
    table = jnp.arange(16.0)

    def per_world(idx):
        return table[idx]

    findings, row = _axis_findings(
        jax.vmap(per_world), jnp.zeros((3, 5), jnp.int32))
    assert not findings and row["proved"]


def test_vmapped_per_world_gather_and_scatter_clean():
    """Batched gather/scatter with DECLARED operand batching dims is
    structurally per-world regardless of index values."""
    def per_world(state, idx, upd):
        read = state[idx]
        return state.at[idx].add(upd), read

    findings, row = _axis_findings(
        jax.vmap(per_world),
        jnp.zeros((2, 8)), jnp.zeros((2, 3), jnp.int32), jnp.ones((2, 3)))
    assert not findings and row["proved"]


def test_vmapped_static_slice_set_clean():
    """``x.at[:, 0].set(v)`` under vmap lowers to a window-dim scatter
    (world-free scalar indices, world axis in update_window_dims) —
    the shape floweng's run_windows hits, and it must stay clean."""
    def per_world(x, v):
        return x.at[:, 0].set(v)

    findings, row = _axis_findings(
        jax.vmap(per_world), jnp.zeros((2, 4, 3)), jnp.ones((2, 4)))
    assert not findings and row["proved"]


# -- SL701 synthetic vectors: firing cases ---------------------------------

def test_cross_world_reduce_fires():
    def f(x):
        return x / jnp.mean(x)  # ensemble-wide mean

    findings, row = _axis_findings(f, jnp.ones((2, 4)))
    assert findings and not row["proved"]
    assert any("reduces over the world axis" in f_.message
               for f_ in findings)


def test_world_indexing_fires():
    def f(x):
        return x[0]  # world 0 singled out

    findings, _row = _axis_findings(f, jnp.ones((2, 4)))
    assert findings
    assert all(f_.rule == "SL701" for f_ in findings)


def test_scan_over_world_axis_fires():
    def f(x):
        def body(c, row):
            return c + row, c

        return jax.lax.scan(body, jnp.zeros(4), x)

    findings, _row = _axis_findings(f, jnp.ones((2, 4)))
    assert any("iterates OVER the world axis" in f_.message
               for f_ in findings)


def test_scatter_into_shared_operand_fires():
    """Per-world indices scattered into a world-FREE accumulator: the
    classic shared-histogram bug. No declared batching dims here, so
    the walk must flag the shared write."""
    def f(idx, upd):
        shared = jnp.zeros(8)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0,))
        return jax.lax.scatter_add(
            shared, idx[:, :, None], upd, dnums)

    findings, _row = _axis_findings(
        f, jnp.zeros((2, 3), jnp.int32), jnp.ones((2, 3)))
    assert any("world-SHARED operand" in f_.message for f_ in findings)


def test_findings_carry_source_location():
    def f(x):
        return jnp.sum(x, axis=0)

    findings, _ = _axis_findings(f, jnp.ones((2, 4)))
    assert findings
    # op + provenance: SL701 findings name a file:line when jax records one
    assert findings[0].rule == "SL701"
    assert "`reduce_sum`" in findings[0].message


# -- SL702: the fold-chain prover ------------------------------------------

def _rng_ob(name, fn_of_seed, domain=(0, 2**31 - 1)):
    def build():
        return fn_of_seed, (jnp.int32(0),), 0, domain

    return batchdim.RngObligation(name, build)


def test_identity_fold_proves():
    root = jax.random.key(0)
    findings, row = batchdim.prove_fold_chain(_rng_ob(
        "t:identity", lambda s: jax.random.fold_in(root, s)))
    assert not findings and row["ok"]
    assert any(step["prim"] == "random_fold_in" and step["status"] == "inj"
               for step in row["chain"])


def test_offset_fold_proves():
    """seed + const is a bijection mod 2**32 — injectivity survives."""
    root = jax.random.key(7)
    findings, row = batchdim.prove_fold_chain(_rng_ob(
        "t:offset", lambda s: jax.random.fold_in(root, s + 17)))
    assert not findings and row["ok"]


def test_real_world_key_obligation_proves():
    (ob,) = [o for o in batchdim.rng_obligations()
             if o.name == "shadow_tpu.tpu.elastic:world_key"]
    findings, row = batchdim.prove_fold_chain(ob)
    assert not findings and row["ok"]
    assert row["seed_domain"] == [0, 2**31 - 1]


def test_even_mul_fold_fires():
    root = jax.random.key(0)
    findings, row = batchdim.prove_fold_chain(_rng_ob(
        "t:doubled", lambda s: jax.random.fold_in(root, s * 2)))
    assert findings and not row["ok"]
    assert "mul" in findings[0].message


def test_modulo_fold_fires_naming_rem():
    """seed % 4 collapses the domain; the prover must name the `rem`
    inside the pjit it lowers under, not give up at the call."""
    root = jax.random.key(0)
    findings, row = batchdim.prove_fold_chain(_rng_ob(
        "t:mod4", lambda s: jax.random.fold_in(root, s % 4)))
    assert findings and not row["ok"]
    assert "rem" in findings[0].message


# -- SL703: census stability + refusal hygiene -----------------------------

def _entry(key, fn_of_w):
    def build_w(w):
        def build():
            fn, args = fn_of_w(w)
            return fn, args

        return build

    return batchdim.BatchEntry(key, build_w)


def test_stable_entry_passes_census():
    e = _entry("t:stable", lambda w: (lambda x: x + 1.0,
                                      (jnp.zeros((w, 4)),)))
    findings, rows, _refs = batchdim.check_vmap_census([e], refusals={})
    assert not findings
    assert rows == [{"entry": "t:stable", "ok": True,
                     "world_counts": list(batchdim.BATCH_WORLD_COUNTS),
                     "ops": rows[0]["ops"]}]


def test_world_count_unroll_fires_census_drift():
    def fn_of_w(w):
        def f(x):
            y = x
            for _ in range(w):  # graph grows with W
                y = y + 1.0
            return y

        return f, (jnp.zeros((w, 4)),)

    findings, _rows, _refs = batchdim.check_vmap_census(
        [_entry("t:unroll", fn_of_w)], refusals={})
    assert any("not world-count-stable" in f.message for f in findings)


def test_stale_and_empty_refusals_fire():
    e = _entry("t:refused", lambda w: (lambda x: x, (jnp.zeros((w, 2)),)))
    findings, _rows, refs = batchdim.check_vmap_census(
        [e], refusals={"t:ghost": "why", "t:refused": "  "})
    msgs = " | ".join(f.message for f in findings)
    assert "stale vmap refusal" in msgs
    assert "without a written rationale" in msgs
    # the refused entry is excused from the census sweep either way
    assert {r["entry"] for r in refs} == {"t:refused"}


def test_checked_in_refusals_are_pallas_only():
    """The real refusal surface is empty: every registered entry vmaps,
    so none is excused from the SL701/SL703 sweeps (the refusal
    mechanism itself stays, pinned by the fixture tests above)."""
    assert batchdim.VMAP_REFUSALS == {}
    keys = {e.key for e in batchdim.batch_entries()}
    assert keys and not keys & set(batchdim.VMAP_REFUSALS)


# -- real entries ----------------------------------------------------------

def test_window_step_lean_proves_at_w2():
    """Tier-1 smoke proof on the flagship kernel: the lean window step,
    vmapped over two worlds, is world-isolated (shares the trace cache
    with the gating sweep, so this also pins the cache key shape)."""
    (entry,) = [e for e in batchdim.batch_entries()
                if e.key == "shadow_tpu.tpu.plane:window_step[lean]"]
    closed = jaxpr_audit.traced(
        f"{entry.key}@vmapW2", entry.build_w(2))[0]
    findings, row = batchdim.world_axis_findings(closed, entry.key, 2)
    assert not findings, [f.message for f in findings]
    assert row["proved"] and row["batched_ops"]


@pytest.mark.slow
def test_check_all_batch_clean_tree_wide():
    """The full gating sweep: every registered entry proves SL701 at
    W=2, the census is stable at W=2/W=3, both refusals are written,
    and the RNG obligation proves — zero active findings."""
    findings, report = batchdim.check_all_batch()
    active = [f for f in findings if not f.suppressed]
    assert not active, [str(f) for f in active]
    s = report["summary"]
    # summary.entries counts non-refused axis rows; all must prove
    assert s["entries"] >= 28 and s["refused"] == 2
    assert s["proved"] == s["entries"]
    assert all(r["ok"] for r in report["rng"])
