"""The SL5xx dataflow-proof machinery (analysis/dataflow.py + proofs.py):

- engine unit semantics: straight-line propagation, cond/while implicit
  flows WITH the branch-invariant passthrough refinement, scan carry
  fixpoints, pjit descent, conservative unknown-primitive handling;
- SL501: every invisibility theorem over the REAL kernels holds (the
  acceptance gate), the taint is not vacuous (plane outputs ARE
  tainted), and the deliberately-broken fixture kernel fails naming
  both ends of the illegal flow;
- SL502: the checked-in op-budget ledger matches the live tree, and a
  fixture kernel with one extra scatter fails with a per-primitive
  delta;
- SL504: the shardability report is non-empty for the routing exchange
  and EMPTY for row-local stages, and the mixed fixture kernel
  classifies each op correctly (including the replicated-table
  exemption).
"""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from shadow_tpu.analysis import proofs  # noqa: E402
from shadow_tpu.analysis.dataflow import (  # noqa: E402
    leaf_paths, op_census, propagate_taint, shard_census,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def _load_fixture(name: str):
    spec = importlib.util.spec_from_file_location(
        name.removesuffix(".py"), os.path.join(FIXTURES, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _labels(fn, args, tainted: dict[int, str]):
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    in_labels = []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_flatten(a)[0])
        pre = tainted.get(i)
        in_labels.extend(leaf_paths(a, prefix=pre) if pre else [None] * n)
    return (propagate_taint(closed, in_labels), leaf_paths(out_shape))


# -- engine semantics ------------------------------------------------------

def test_straight_line_taint_and_clean():
    def fn(a, b):
        return a + 1, b * 2, a + b

    out, _ = _labels(fn, (jnp.int32(1), jnp.int32(2)), {0: "t"})
    assert out[0] == "t" and out[1] is None and out[2] == "t"


def test_cond_implicit_flow_taints_all_outputs():
    def fn(p, x):
        return jax.lax.cond(p > 0, lambda v: v + 1, lambda v: v - 1, x)

    out, _ = _labels(fn, (jnp.int32(1), jnp.int32(2)), {0: "t"})
    assert out[0] == "t"  # tainted predicate, clean operand


def test_cond_passthrough_is_branch_invariant():
    """An operand returned verbatim by BOTH branches stays clean even
    under a tainted predicate — the ingest_rows gate_idle shape."""
    def fn(p, x, y):
        def yes(ops):
            return ops[0] + 1, ops[1]

        def no(ops):
            return ops[0] - 1, ops[1]

        return jax.lax.cond(p > 0, yes, no, (x, y))

    out, _ = _labels(fn, (jnp.int32(1), jnp.int32(2), jnp.int32(3)),
                     {0: "t"})
    assert out[0] == "t"  # computed differently per branch
    assert out[1] is None  # verbatim passthrough in both branches


def test_while_fixpoint_carries_taint_across_slots():
    """Taint flowing between carry slots needs the fixpoint: slot 1
    reads slot 0 only on later iterations."""
    def fn(a, b):
        def body(c):
            x, y, n = c
            return x, y + x, n + 1

        def cond(c):
            return c[2] < 3

        return jax.lax.while_loop(cond, body, (a, b, jnp.int32(0)))

    out, _ = _labels(fn, (jnp.int32(1), jnp.int32(2)), {0: "t"})
    assert out[0] == "t" and out[1] == "t"  # b absorbed a's taint
    assert out[2] is None  # the counter never sees it


def test_while_tainted_predicate_spares_passthrough_carry():
    def fn(t, x, y):
        def body(c):
            n, keep, acc = c
            return n + 1, keep, acc + 1

        def cond(c):
            return c[0] < t  # TAINTED trip count

        return jax.lax.while_loop(cond, body, (jnp.int32(0), x, y))

    out, _ = _labels(fn, (jnp.int32(3), jnp.int32(1), jnp.int32(2)),
                     {0: "t"})
    assert out[0] == "t" and out[2] == "t"  # iteration-count dependent
    assert out[1] is None  # verbatim carry: 0 or N iterations, same value


def test_scan_carry_and_ys():
    def fn(a, xs):
        def body(c, x):
            return c + x, c

        return jax.lax.scan(body, a, xs)

    out, _ = _labels(fn, (jnp.int32(0), jnp.zeros(3, jnp.int32)),
                     {1: "xs"})
    assert out[0] == "xs" and out[1] == "xs"


def test_vmap_broadcast_carries_taint():
    """Batched jaxprs (the SL701 ensemble surface) route shared
    operands through vmap-introduced `broadcast_in_dim`s: taint on the
    unbatched arg must survive the broadcast into every world's lane,
    and a clean batched arg must stay clean beside it."""
    def per_world(x, shared):
        return x * 2.0, x + shared

    fn = jax.vmap(per_world, in_axes=(0, None))
    out, _ = _labels(fn, (jnp.ones((2, 3)), jnp.ones(3)), {1: "t"})
    assert out[0] is None  # world-local product never touches `shared`
    assert out[1] == "t"   # broadcast_in_dim propagated the taint


def test_vmap_batched_scan_carry_taint():
    """vmap over a scanned body batches the carry: the per-world seed's
    taint must flow through the batched carry into both the final
    carry and the stacked ys, while the clean per-world xs stay
    clean in the untouched output slot."""
    def per_world(seed, xs):
        def body(c, x):
            return c + x, c

        return jax.lax.scan(body, seed, xs)

    fn = jax.vmap(per_world)
    out, _ = _labels(
        fn, (jnp.zeros(2, jnp.int32), jnp.zeros((2, 3), jnp.int32)),
        {0: "seed"})
    assert out[0] == "seed" and out[1] == "seed"

    # and the converse: clean seed, tainted xs — the batched carry
    # absorbs xs-taint across iterations exactly as in the solo scan
    out, _ = _labels(
        fn, (jnp.zeros(2, jnp.int32), jnp.zeros((2, 3), jnp.int32)),
        {1: "xs"})
    assert out[0] == "xs" and out[1] == "xs"


def test_pjit_descent_keeps_precision():
    inner = jax.jit(lambda x, y: (x + 1, y))

    def fn(a, b):
        return inner(a, b)

    out, _ = _labels(fn, (jnp.int32(1), jnp.int32(2)), {0: "t"})
    assert out[0] == "t" and out[1] is None


def test_custom_jvp_descent_keeps_precision():
    """`custom_jvp_call` carries its primal body as `call_jaxpr` and
    inlines 1:1 — the analysis must descend (a clean operand stays
    clean) instead of falling back to all-outputs-tainted."""
    @jax.custom_jvp
    def f(x, y):
        return x * 2.0, y

    @f.defjvp
    def f_jvp(primals, tangents):
        return f(*primals), (tangents[0] * 2.0, tangents[1])

    def fn(a, b):
        return f(a, b)

    out, _ = _labels(fn, (jnp.float32(1), jnp.float32(2)), {0: "t"})
    assert out[0] == "t"
    assert out[1] is None  # descent, not the conservative fallback


def test_custom_vjp_descent_keeps_precision():
    """`custom_vjp_call_jaxpr` spells its body `fun_jaxpr`; same
    descent contract. The second output passes the clean operand
    through a genuinely-mixing first output."""
    @jax.custom_vjp
    def g(x, y):
        return x + 0.0 * x, y

    def g_fwd(x, y):
        return g(x, y), None

    def g_bwd(res, ct):
        return ct

    g.defvjp(g_fwd, g_bwd)

    def fn(a, b):
        return g(a, b)

    out, _ = _labels(fn, (jnp.float32(1), jnp.float32(2)), {0: "t"})
    assert out[0] == "t" and out[1] is None


def test_scan_closed_over_const_feeds_carry():
    """A traced value closed over by the scan body enters as a
    num_consts operand, NOT a carry: its taint must still reach the
    carry through the body (and an untouched ys stays clean)."""
    def fn(k, a, xs):
        scale = k * 2  # closed over by the body -> scan const

        def body(c, x):
            return c + scale, x

        return jax.lax.scan(body, a, xs)

    out, _ = _labels(
        fn, (jnp.int32(3), jnp.int32(0), jnp.zeros(3, jnp.int32)),
        {0: "k"})
    assert out[0] == "k"  # carry absorbed the closed-over const
    assert out[1] is None  # ys = xs passthrough, untouched


def test_scan_const_taint_needs_no_carry_seed():
    """Fixpoint sanity for the const-into-carry flow: the carry starts
    CLEAN and only the closed-over const is tainted — one body pass
    must already propagate it (the carry fixpoint may not converge to
    the clean initial value)."""
    def fn(k, a, xs):
        def body(c, x):
            return jnp.where(x > 0, c + k, c), c

        return jax.lax.scan(body, a, xs)

    out, _ = _labels(
        fn, (jnp.int32(3), jnp.int32(0), jnp.zeros(3, jnp.int32)),
        {0: "k"})
    assert out[0] == "k" and out[1] == "k"


def test_leaf_paths_namedtuples_and_dicts():
    from shadow_tpu.tpu import plane

    state = plane.make_state(2, egress_cap=4, ingress_cap=4)
    paths = leaf_paths(state, prefix="state")
    assert "state.eg_dst" in paths and "state.rng_counter" in paths
    flat = len(jax.tree_util.tree_flatten(state)[0])
    assert len(paths) == flat
    d = {"mask": jnp.zeros(2), "src": jnp.zeros(2)}
    assert leaf_paths((d, jnp.int32(0)))[:2] == ["[0]['mask']",
                                                "[0]['src']"]


# -- SL501: the invisibility theorems --------------------------------------

def test_spec_surface_covers_the_three_kernels_and_planes():
    names = {s.name for s in proofs.invisibility_specs()}
    for required in ("window_step[metrics]", "window_step[guards]",
                     "window_step[hist]", "window_step[flightrec]",
                     "window_step[metrics+guards+hist+flightrec]",
                     "chain_windows[metrics]", "chain_windows[guards]",
                     "chain_windows[workload+metrics+guards]",
                     "ingest_rows[metrics+guards+hist+flightrec]",
                     "workload_step[append-only]",
                     "window_step[flows]", "flow_step[append-only]"):
        assert required in names, required


@pytest.mark.parametrize(
    "spec", proofs.invisibility_specs(), ids=lambda s: s.name)
def test_invisibility_theorem_holds(spec):
    findings = proofs.check_invisibility(spec)
    assert findings == [], "\n".join(f.message for f in findings)


def test_taint_is_not_vacuous():
    """The plane OUTPUTS must be tainted — a propagation bug that loses
    all taint would make every theorem pass vacuously."""
    spec = next(s for s in proofs.invisibility_specs()
                if s.name == "window_step[metrics]")
    fn, args = spec.build()
    out_labels, out_paths = _labels(fn, args, spec.tainted_args)
    tainted = [p for p, l in zip(out_paths, out_labels) if l is not None]
    assert tainted, "no output leaf tainted: the engine lost the taint"
    # ...and ONLY the metrics output (index 3) is
    assert all(p.startswith("[3]") for p in tainted), tainted


def test_broken_fixture_kernel_fails_named():
    """The deliberately-broken kernel (plane counter wired back into
    sim state) is reported with the offending output leaf AND the
    sourcing plane leaf named."""
    fixture = _load_fixture("fixture_taint_leak.py")
    findings = proofs.check_invisibility(fixture.spec())
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "SL501"
    assert "metrics.pkts" in f.message  # the source
    assert "[0].counter" in f.message  # the offending output leaf
    assert "[0].clock" not in f.message  # the untouched leaf stays clean


def test_workload_append_only_rejects_an_ingress_write():
    """The relaxed workload theorem still has teeth: a generator that
    writes the ingress ring fails."""
    from shadow_tpu.tpu import plane

    state = plane.make_state(4, egress_cap=8, ingress_cap=8)
    ws = jnp.zeros((4,), jnp.int32)

    def bad_generator(ws, state):
        return state._replace(
            in_seq=state.in_seq + ws[:, None]), ws + 1

    spec = proofs.InvisibilitySpec(
        "bad_generator", "tests", lambda: (bad_generator, (ws, state)),
        tainted_args={0: "ws"}, protected=proofs._workload_protected)
    findings = proofs.check_invisibility(spec)
    assert len(findings) == 1 and "in_seq" in findings[0].message


# -- SL502: the op-budget ledger -------------------------------------------

@pytest.mark.slow  # re-derives every budget from the tree (~13s);
# CI's prover-suites step runs this file unfiltered
def test_checked_in_budgets_match_the_tree():
    """The acceptance gate: analysis/op_budgets.json is current. On
    drift, regenerate with `python tools/shadowlint.py
    --write-op-budgets` and justify the delta in the PR."""
    findings, deltas = proofs.check_op_budgets()
    assert findings == [], (
        "\n".join(f"{f.path}: {f.message}" for f in findings)
        + "\n" + proofs.format_budget_delta(deltas))


def test_extra_scatter_fails_the_budget(tmp_path):
    fixture = _load_fixture("fixture_op_budget.py")
    entry = fixture.entry()
    ledger = tmp_path / "budgets.json"
    ledger.write_text(json.dumps({
        "version": 1,
        "budgets": {f"{entry.module}:{entry.name}": fixture.BUDGET},
    }))
    findings, deltas = proofs.check_op_budgets(str(ledger), [entry])
    assert len(findings) == 1 and findings[0].rule == "SL502"
    assert "scatter-add" in findings[0].message
    [delta] = deltas
    assert delta["delta"]["scatter-add"] == {"budget": 1, "actual": 2}
    table = proofs.format_budget_delta(deltas)
    assert "scatter-add" in table and "+1" in table


def test_budget_detects_unbudgeted_and_stale_entries(tmp_path):
    fixture = _load_fixture("fixture_op_budget.py")
    entry = fixture.entry()
    ledger = tmp_path / "budgets.json"
    ledger.write_text(json.dumps(
        {"version": 1, "budgets": {"gone:entry": {"sort": 1}}}))
    findings, _ = proofs.check_op_budgets(str(ledger), [entry])
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert "no op budget" in msgs[0] and "no longer audited" in msgs[1]


def test_census_counts_nested_bodies_once():
    def fn(x):
        def body(c, _):
            return jnp.sort(c), None

        return jax.lax.scan(body, x, None, length=5)

    census = op_census(jax.make_jaxpr(fn)(jnp.zeros(4, jnp.int32)))
    assert census["sort"] == 1 and census["scan"] == 1


# -- SL504: shardability ----------------------------------------------------

@pytest.fixture(scope="module")
def shard_report():
    """One report for all SL504 tests: building it traces every audit
    entry (~seconds), so share it across the module."""
    return proofs.build_shard_report()


def test_shard_report_routing_vs_rowlocal(shard_report):
    """Acceptance: cross-host primitives non-empty for the routing
    exchange, EMPTY for row-local stages."""
    report = shard_report
    sections = report["sections"]
    for routing in ("shadow_tpu.tpu.plane:routing_rank",
                    "shadow_tpu.tpu.plane:routing_place"):
        assert sections[routing]["cross_host"], routing
    for rowlocal in ("shadow_tpu.tpu.codel:codel_drain",
                     "shadow_tpu.tpu.codel:router_drain",
                     "shadow_tpu.tpu.tcp:tcp_event_step",
                     "shadow_tpu.tpu.tcp:tcp_pull_step"):
        assert sections[rowlocal]["cross_host"] == [], (
            rowlocal, sections[rowlocal]["cross_host"])
    assert report["summary"]["cross_host_ops"] > 0


def test_shard_classifier_on_mixed_fixture():
    fixture = _load_fixture("fixture_shard_classify.py")
    fn, args = fixture.build()
    census = shard_census(jax.make_jaxpr(fn)(*args))
    cross_prims = [oc["primitive"] for oc in census["cross_host"]]
    assert "scatter-add" in cross_prims  # the routing-style exchange
    assert "reduce_sum" in cross_prims  # the host-axis reduction
    # the constant-table gather must NOT be cross-host...
    assert "gather" not in cross_prims
    # ...it lands in host_local along with the row sort + row gather
    assert census["host_local"].get("sort", 0) >= 1
    assert census["host_local"].get("gather", 0) >= 2


def test_no_entry_reports_an_opaque_kernel(shard_report):
    """Every audited entry is plain jaxpr down to its primitives: no
    section hides an opaque kernel from the SL504 classifier."""
    sections = shard_report["sections"]
    assert "shadow_tpu.tpu.plane:window_step[lean]" in sections
    opaque = {k: v["opaque"] for k, v in sections.items() if v["opaque"]}
    assert not opaque, opaque
