"""shadowcost (SL601/SL602/SL603) coverage: the HLO boundary census on
synthetic kernels, the seeded fixtures firing each rule with the
entry + op pair + delta named, watermark extrapolation catching a
super-linear temp, the host-sync fence semantics (loops vs teardown,
device_get-derived host values, suppressions, the allow registry), the
canonical double-regen byte-identity of BOTH ledgers, and the
checked-in cost ledger's consistency with the registry. The full
compiled-surface acceptance sweep is @slow (the CI proof gate runs it
unfiltered on every build via `shadowlint --only ...,SL601,SL602`)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from shadow_tpu.analysis import costmodel  # noqa: E402
from shadow_tpu.analysis.costmodel import (  # noqa: E402
    CostEntry, check_cost_budgets, check_host_sync,
    check_host_sync_source, check_watermarks, cost_budget_path,
    default_cost_entries, fusion_boundaries, write_cost_budgets,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def _load_fixture(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name.removesuffix(".py"), os.path.join(FIXTURES, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(tmp_path, doc):
    path = tmp_path / "cost_budgets.json"
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    return str(path)


# -- the HLO census substrate ---------------------------------------------


def test_fusion_boundaries_on_synthetic_kernel():
    """A sort between two fusions materializes its operand and its
    output; both show up with correct shapes/bytes, ranked
    largest-first, and tiny values stay below the threshold."""
    def f(x):
        y = jnp.exp(x) + 1.0          # fusion 1
        s = jax.lax.sort(y, dimension=1)
        return (s * 2.0).sum()        # fusion 2

    comp = jax.jit(f).lower(jnp.ones((16, 32), jnp.float32)).compile()
    bounds = fusion_boundaries(comp.as_text(), 16 * 32)
    assert bounds, "no boundaries found around an unfusable sort"
    assert all(b["bytes"] >= 16 * 32 * 4 for b in bounds)
    assert any("f32[16,32]" in s for b in bounds for s in b["shapes"])
    assert [b["bytes"] for b in bounds] == sorted(
        (b["bytes"] for b in bounds), reverse=True)
    # a sky-high threshold filters everything
    assert fusion_boundaries(comp.as_text(), 10**9) == []


def test_output_only_values_are_not_boundaries():
    """A value that only reaches the root tuple is an OUTPUT — no
    fusion can elide it, so it must not appear in the worklist."""
    def f(x):
        return jnp.exp(x), jnp.tanh(x)  # both results are outputs

    comp = jax.jit(f).lower(jnp.ones((16, 32), jnp.float32)).compile()
    assert fusion_boundaries(comp.as_text(), 16 * 32) == []


# -- the seeded fixtures --------------------------------------------------


def test_fixture_fires_sl602_naming_pair_and_delta(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    path = _write(tmp_path, mod.budget(big_boundaries=0))
    findings, deltas = check_cost_budgets(path, entries=[mod.entry()])
    f602 = [f for f in findings if f.rule == "SL602"]
    assert f602, [str(f) for f in findings]
    msg = str(f602[0])
    assert "tests.lint_fixtures:fusion_break" in msg  # the entry
    assert "->" in msg and ("sort" in msg or "fusion" in msg)  # op pair
    assert "0 budgeted" in msg  # budget-vs-actual
    assert deltas and "big_boundaries" in deltas[0]["delta"]
    table = costmodel.format_cost_delta(deltas)
    assert "big_boundaries" in table and "fusion_break" in table


def test_fixture_fires_sl601_on_cost_drift(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    live = mod.budget()["platforms"][costmodel._platform()][
        "tests.lint_fixtures:fusion_break"]
    path = _write(tmp_path, mod.budget(flops=live["flops"] * 10 + 999))
    findings, deltas = check_cost_budgets(path, entries=[mod.entry()])
    f601 = [f for f in findings if f.rule == "SL601"]
    assert f601 and "flops" in str(f601[0])
    assert "fusion_break" in str(f601[0])
    assert deltas[0]["delta"]["flops"]["actual"] == live["flops"]


def test_fixture_passes_against_its_own_live_budget(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    path = _write(tmp_path, mod.budget())
    findings, deltas = check_cost_budgets(path, entries=[mod.entry()])
    assert findings == [] and deltas == []


def test_missing_platform_and_missing_entry_fail(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    doc = mod.budget()
    doc["platforms"] = {"nonexistent-platform": {}}
    findings, _ = check_cost_budgets(_write(tmp_path, doc),
                                     entries=[mod.entry()])
    assert any("no cost budgets for platform" in f.message
               for f in findings)
    doc2 = mod.budget()
    doc2["platforms"][costmodel._platform()] = {}
    findings, _ = check_cost_budgets(_write(tmp_path, doc2),
                                     entries=[mod.entry()])
    assert any("has no budget" in f.message for f in findings)


def test_infra_failures_tag_both_budget_rules(tmp_path):
    """A ledger the fence could not check must fail a `--only SL602`
    run too: missing file / platform / entry findings carry BOTH
    rules, so rule filtering can never turn a dead gate green."""
    mod = _load_fixture("fixture_fusion_break.py")
    findings, _ = check_cost_budgets(str(tmp_path / "nope.json"),
                                     entries=[mod.entry()])
    assert {f.rule for f in findings} == {"SL601", "SL602"}
    doc = mod.budget()
    doc["platforms"] = {"nonexistent-platform": {}}
    findings, _ = check_cost_budgets(_write(tmp_path, doc),
                                     entries=[mod.entry()])
    assert {f.rule for f in findings} == {"SL601", "SL602"}


def test_within_zero_budget_zero_actual_passes():
    """An exact match passes under ANY band shape — a rel-only band
    on a zero budget (transcendentals on cpu) must not fail 0 vs 0."""
    assert costmodel._within(0, 0, {"rel": 0.25})
    assert costmodel._within(0, 0, {})
    assert not costmodel._within(0, 5, {"rel": 0.25})


def test_report_worklist_is_complete():
    """The artifact's cross-entry worklist carries EVERY boundary
    (the no-silent-caps rule); only the per-entry head is bounded."""
    mod = _load_fixture("fixture_fusion_break.py")
    report = costmodel.build_cost_report(entries=[mod.entry()])
    section = report["entries"][0]
    assert len(report["fusion_worklist"]) == section["boundaries_total"]
    assert len(section["boundaries"]) <= costmodel._WORKLIST_PER_ENTRY


def test_tolerance_bands_absorb_small_drift(tmp_path):
    """A metric within the rel OR abs band passes; the band is read
    from the ledger document, not hardcoded."""
    mod = _load_fixture("fixture_fusion_break.py")
    live = mod.budget()["platforms"][costmodel._platform()][
        "tests.lint_fixtures:fusion_break"]
    doc = mod.budget(flops=int(live["flops"] * 1.1))  # within 25% rel
    findings, _ = check_cost_budgets(_write(tmp_path, doc),
                                     entries=[mod.entry()])
    assert findings == []
    doc = mod.budget(fusions=live["fusions"] + 2)  # at the abs band
    findings, _ = check_cost_budgets(_write(tmp_path, doc),
                                     entries=[mod.entry()])
    assert findings == []


# -- watermark extrapolation ----------------------------------------------


def _quad_entry(n):
    def build():
        def kernel(x):
            m = x[:, None] * x[None, :]          # [n, n]: quadratic
            return jax.lax.sort(m, dimension=1).sum()

        return kernel, (jnp.ones((n,), jnp.float32),)

    return build


def test_watermark_catches_superlinear_temp():
    entry = CostEntry("tests.lint_fixtures:quad_temp", 128, 1,
                      _quad_entry(128),
                      scale_n=256, scale_build=_quad_entry(256))
    findings, rows = check_watermarks([entry])
    assert findings and findings[0].rule == "SL601"
    assert "super-linearly" in findings[0].message
    assert rows[0]["temp2_bytes"] > rows[0]["linear_bound_bytes"]


def test_watermark_passes_linear_temp():
    def lin(n):
        def build():
            def kernel(x):
                return jax.lax.sort(jnp.exp(x), dimension=0).sum()

            return kernel, (jnp.ones((n * 64,), jnp.float32),)

        return build

    entry = CostEntry("tests.lint_fixtures:lin_temp", 4, 1, lin(4),
                      scale_n=8, scale_build=lin(8))
    findings, rows = check_watermarks([entry])
    assert findings == [] and rows[0]["ok"]


# -- SL603: the host-sync fence -------------------------------------------


def _line_of(source, needle):
    for i, text in enumerate(source.splitlines(), start=1):
        if needle in text:
            return i
    raise AssertionError(f"{needle!r} not in fixture")


def test_sl603_fixture_fires_and_clean_shapes_pass():
    with open(os.path.join(FIXTURES, "fixture_host_sync.py"),
              encoding="utf-8") as fh:
        src = fh.read()
    findings = check_host_sync_source(src, "bench.py")
    active = {f.line for f in findings if not f.suppressed}
    assert active == {
        _line_of(src, "float(delivered.sum())"),
        _line_of(src, "metrics.events.item()"),
        _line_of(src, "jax.device_get(state.n_sent)"),
        _line_of(src, "jax.block_until_ready(state)"),
    }
    # the comment-suppressed np.asarray carries its justification
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1
    assert sup[0].justification == "fixture: sanctioned debug read"
    # drain_after/digest (teardown + device_get-derived) stay clean
    for needle in ("jax.block_until_ready(state)  # teardown",
                   "float(jax.device_get(state.n_sent).sum())",
                   "arr = np.asarray(leaf)"):
        assert _line_of(src, needle) not in active, needle


def test_sl603_registry_allow_suppresses_with_justification():
    src = ("import numpy as np\n"
           "def run_elastic_window(state, attempt):\n"
           "    while True:\n"
           "        out, ovf = attempt(state)\n"
           "        if int(np.asarray(ovf).sum()) == 0:\n"
           "            return out\n")
    findings = check_host_sync_source(src, "shadow_tpu/tpu/elastic.py")
    assert findings and all(f.suppressed for f in findings)
    assert all("elastic capacity policy" in f.justification
               for f in findings)
    # the same code under a DIFFERENT function name is NOT sanctioned
    findings = check_host_sync_source(
        src.replace("run_elastic_window", "sneaky_loop"),
        "shadow_tpu/tpu/elastic.py")
    assert findings and not any(f.suppressed for f in findings)


def test_sl603_while_test_counts_as_loop():
    src = ("import jax\n"
           "def spin(x):\n"
           "    while jax.device_get(x) > 0:\n"
           "        x = x - 1\n")
    findings = check_host_sync_source(src, "bench.py")
    assert [f.rule for f in findings] == ["SL603"]


def test_sl603_def_inside_loop_is_not_per_iteration():
    """A function DEFINED in a loop runs later — its body is a fresh
    sync context (the chain_fn/on_chain closure pattern)."""
    src = ("import jax\n"
           "def drive(chunks):\n"
           "    fns = []\n"
           "    for c in chunks:\n"
           "        def on_chain(r1, state, extras):\n"
           "            return jax.device_get(state)\n"
           "        fns.append(on_chain)\n"
           "    return fns\n")
    assert check_host_sync_source(src, "bench.py") == []


def test_sl603_comprehension_is_a_loop():
    """A flagged `for` rewritten as a comprehension must not dodge the
    fence; host-derived comp targets stay exempt like For targets."""
    src = ("import jax\n"
           "def drive(deliveries):\n"
           "    return [float(d.sum()) for d in deliveries]\n")
    findings = check_host_sync_source(src, "bench.py")
    assert [f.rule for f in findings] == ["SL603"]
    src_host = ("import jax\n"
                "import numpy as np\n"
                "def digest(trees):\n"
                "    return [np.asarray(leaf)\n"
                "            for leaf in jax.device_get(trees)]\n")
    assert check_host_sync_source(src_host, "bench.py") == []


def test_sl603_block_until_ready_result_is_still_device():
    """block_until_ready returns the DEVICE array (only flushed): a
    later per-iteration read of it must still fire."""
    src = ("import jax\n"
           "def drive(arr, windows):\n"
           "    arr = jax.block_until_ready(arr)\n"
           "    total = 0.0\n"
           "    for w in range(windows):\n"
           "        total += float(arr.sum())\n"
           "    return total\n")
    findings = check_host_sync_source(src, "bench.py")
    assert [f.line for f in findings] == [_line_of(src.rstrip("\n"),
                                                   "float(arr.sum())")]


def test_sl603_int_is_deliberately_not_netted():
    """The documented hole: bare int() on a device scalar slips the
    lexical net (in-tree device reads all spell the pull as
    device_get/np.asarray/.item()/float(), which are caught; netting
    int() costs ~6 false positives on host coercions per sweep). This
    test pins the DECISION — if the tree ever grows an int()-on-device
    idiom, revisit costmodel._MATERIALIZERS."""
    src = ("def drive(delivered, windows):\n"
           "    total = 0\n"
           "    for w in range(windows):\n"
           "        total += int(delivered.sum())\n"
           "    return total\n")
    assert check_host_sync_source(src, "bench.py") == []


def test_sl603_tree_clean_or_justified():
    """The four driver-loop modules report zero active findings; every
    allow carries a written rationale (the fix-or-allow contract)."""
    findings = check_host_sync()
    active = [str(f) for f in findings if not f.suppressed]
    assert active == [], "\n".join(active)
    assert all(f.justification for f in findings if f.suppressed)
    # the elastic overflow readback IS allowed (not silently absent):
    # the registry entry is load-bearing, not decorative
    assert any("elastic.py" in f.path for f in findings if f.suppressed)


def test_sl603_driver_module_list_matches_tree():
    """Every fenced module exists; a rename breaks the fence loudly
    (check_host_sync reports the missing file as a finding)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in costmodel.DRIVER_MODULES:
        assert os.path.exists(os.path.join(repo, rel)), rel
    missing = costmodel.check_host_sync(repo_root="/nonexistent")
    assert len(missing) == len(costmodel.DRIVER_MODULES)
    assert all("cannot check" in f.message for f in missing)


# -- canonical ledgers (satellite: byte-stable regen) ---------------------


def test_cost_budgets_double_regen_byte_identical(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    write_cost_budgets(p1, entries=[mod.entry()])
    write_cost_budgets(p2, entries=[mod.entry()])
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    assert b1.endswith(b"\n") and not b1.endswith(b"\n\n")
    # regen ON TOP of an existing file is also byte-stable
    write_cost_budgets(p1, entries=[mod.entry()])
    assert open(p1, "rb").read() == b1
    # keys are canonically sorted at every level
    doc = json.loads(b1)
    for section in doc["platforms"].values():
        assert list(section) == sorted(section)
        for metrics in section.values():
            assert list(metrics) == sorted(metrics)


def test_cost_budgets_regen_preserves_other_platforms(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    path = str(tmp_path / "c.json")
    write_cost_budgets(path, entries=[mod.entry()])
    doc = json.load(open(path))
    doc["platforms"]["tpu-imaginary"] = {"some:entry": {"flops": 1}}
    with open(path, "w") as fh:
        json.dump(doc, fh)
    write_cost_budgets(path, entries=[mod.entry()])
    doc2 = json.load(open(path))
    assert doc2["platforms"]["tpu-imaginary"] == {
        "some:entry": {"flops": 1}}


def test_op_budgets_double_regen_byte_identical(tmp_path):
    from shadow_tpu.analysis import proofs

    mod = _load_fixture("fixture_op_budget.py")
    entry = mod.entry()
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    proofs.write_op_budgets(p1, entries=[entry])
    proofs.write_op_budgets(p2, entries=[entry])
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    assert b1.endswith(b"\n") and not b1.endswith(b"\n\n")
    doc = json.loads(b1)
    assert list(doc["budgets"]) == sorted(doc["budgets"])


# -- the checked-in ledger ------------------------------------------------


def test_checked_in_cost_ledger_is_consistent():
    """Registry keys == ledger keys for this platform (no compile:
    pure file/registry consistency), tolerance bands present, the
    file byte-matches a canonical re-dump of itself."""
    path = cost_budget_path()
    assert os.path.exists(path), "cost_budgets.json not checked in"
    raw = open(path, "rb").read()
    doc = json.loads(raw)
    assert set(doc["platforms"]["cpu"]) == {
        e.key for e in default_cost_entries()}
    for metrics in doc["platforms"]["cpu"].values():
        assert set(metrics) == {"flops", "bytes_accessed",
                                "transcendentals", "fusions",
                                "big_boundaries"}
    assert set(doc["tolerance"]) >= {"flops", "bytes_accessed",
                                     "fusions", "big_boundaries"}
    redump = json.dumps(doc, indent=2, sort_keys=True) + "\n"
    assert raw.decode() == redump, \
        "ledger not canonical: regen with --write-cost-budgets"


def test_watermark_pairs_cover_window_step_and_chain():
    keyed = {e.key: e for e in default_cost_entries()}
    assert keyed["shadow_tpu.tpu.plane:window_step[lean]"].scale_build
    assert keyed["shadow_tpu.tpu.plane:chain_windows"].scale_build


def test_real_entry_passes_checked_in_budget():
    """Fast canary against the REAL ledger: the cheapest registered
    entry compiles and lands inside its checked-in band (the full
    surface runs in the CI proof gate and the @slow sweep)."""
    entry = [e for e in default_cost_entries()
             if e.key.endswith("ingest_rows[planes]")][0]
    findings, deltas = check_cost_budgets(entries=[entry])
    findings = [f for f in findings
                if f.path == entry.key or entry.key in f.message]
    assert findings == [], [str(f) for f in findings]


def test_worklist_names_the_rank_place_materialization():
    """The acceptance handoff: window_step's ranked worklist leads
    with the routing-stage materializations ROADMAP-4 targets — the
    place gather of the stacked [B, 5] stream rows and the routing
    flat sort."""
    entry = [e for e in default_cost_entries()
             if e.key.endswith("window_step[lean]")][0]
    bounds = costmodel.entry_costs(entry)["boundaries"]
    head = bounds[:3]
    # the place gather: one 5-wide stream row per ingress lane, laid
    # out as [N*CI, 1, 5]
    assert any(s == "s32[32,1,5]"
               for b in head for s in b["shapes"]), \
        [b["shapes"] for b in head]
    assert any("sort" in b["producer"] for b in head), \
        [b["producer"] for b in head]


@pytest.mark.slow
def test_full_surface_clean_and_watermarks_linear():
    """The acceptance sweep: every registered entry within its
    checked-in band on this platform, both watermark pairs linear.
    @slow (compiles the full surface); the CI proof gate runs the
    same check unfiltered on every build."""
    findings, _ = check_cost_budgets()
    assert [str(f) for f in findings] == []
    wm_findings, rows = check_watermarks()
    assert wm_findings == [] and all(r["ok"] for r in rows)
    assert len(rows) == 2


# -- report + compare_runs ------------------------------------------------


def test_cost_report_shape(tmp_path):
    mod = _load_fixture("fixture_fusion_break.py")
    report = costmodel.build_cost_report(entries=[mod.entry()])
    assert report["platform"] == costmodel._platform()
    assert report["entries"][0]["entry"] == \
        "tests.lint_fixtures:fusion_break"
    assert report["fusion_worklist"], "fixture cube not in worklist"
    assert report["fusion_worklist"][0]["bytes"] >= 8 * 8 * 8 * 4
    assert {"modules", "active", "allowed"} <= set(report["host_sync"])
    assert report["summary"]["host_sync_active"] == 0


def test_compare_runs_cost_delta(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import compare_runs

    def rec(platform, flops):
        return {
            "platform": platform,
            "entries": [{"entry": "plane:window_step[lean]",
                         "metrics": {"flops": flops,
                                     "bytes_accessed": 1000,
                                     "fusions": 10,
                                     "big_boundaries": 4}}],
        }

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(rec("cpu", 100), open(a, "w"))
    json.dump(rec("cpu", 80), open(b, "w"))
    assert compare_runs.main(["--cost", a, b]) == 0
    out = capsys.readouterr().out
    assert "flops" in out and "window_step[lean]" in out
    assert "MEANINGLESS" not in out and "WARNING" not in out
    # mismatched platform keys: the loud banner (the bench lesson)
    json.dump(rec("tpu", 80), open(b, "w"))
    assert compare_runs.main(["--cost", a, b]) == 0
    out = capsys.readouterr().out
    assert "WARNING" in out and "platform" in out
