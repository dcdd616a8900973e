"""The shadowlint rule registry, findings, and suppression syntax.

Every rule protects one determinism or jit-cache invariant of the
simulation (PAPER.md: "same seed -> same results, on any machine, at any
parallelism"). Pass 1 (``astlint``) rules are SL1xx and run over source
text; pass 2 (``jaxpr_audit``) rules are SL2xx and run over the jaxprs of
the jitted ``tpu/`` entry points.

Suppression syntax (pass 1)::

    time.monotonic()  # shadowlint: disable=SL101 -- wall-clock stats only

A ``# shadowlint: disable=SLxxx[,SLyyy] -- <justification>`` comment
suppresses those rules on its own line and on the line directly below it
(so it can trail the offending statement or sit on the preceding line).
The justification after ``--`` is REQUIRED: a disable comment without one
still fails the lint, so every suppression documents why the hazard is
not real. Pass-2 entries carry per-rule allow-lists in the audit registry
(`jaxpr_audit.default_entries`) instead, since jaxpr findings have no
source line to anchor a comment to.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "Finding",
    "RULES",
    "RuleInfo",
    "Suppressions",
    "parse_suppressions",
]


@dataclass(frozen=True)
class RuleInfo:
    id: str
    name: str
    summary: str
    invariant: str  # the determinism invariant the rule protects
    #: where the rule runs — a human-readable scope line for
    #: ``shadowlint --list-rules`` (path prefixes for AST rules, the
    #: audited registry for jaxpr/proof rules)
    scope: str = ""
    #: the seeded violation under tests/lint_fixtures/ proving the rule
    #: can fail (every rule MUST have one; pinned by
    #: tests/test_shadowlint.py::test_every_rule_has_a_fixture)
    fixture: str = ""


RULES: dict[str, RuleInfo] = {
    r.id: r
    for r in [
        RuleInfo(
            "SL101", "wall-clock-read",
            "wall-clock read (time.time/monotonic/perf_counter, "
            "datetime.now) in simulation code",
            "simulated time comes only from the event clock; real time "
            "feeding any simulation decision breaks replay",
            scope="shadow_tpu/ (tools/ benchmarks measure wall time on purpose)",
            fixture="fixture_wallclock.py",
        ),
        RuleInfo(
            "SL102", "global-randomness",
            "unseeded/global randomness (random.*, legacy np.random.*) "
            "outside core/rng.py",
            "all draws come from the seeded Xoshiro256++ streams in "
            "core/rng.py (or counter-based threefry on device), so "
            "results are a pure function of the config seed",
            scope="everywhere except core/rng.py",
            fixture="fixture_randomness.py",
        ),
        RuleInfo(
            "SL103", "unordered-iteration",
            "iteration over a set/frozenset where order can feed event "
            "scheduling",
            "event order must be scheduling-independent; set iteration "
            "order depends on insertion history and hash seeding",
            scope="core/, net/, host/, kernel/, process/, tcp/, apps/",
            fixture="fixture_unordered.py",
        ),
        RuleInfo(
            "SL104", "mutable-default-arg",
            "mutable default argument (list/dict/set) on a function",
            "a shared mutable default carries state across calls and "
            "hosts, making results depend on call history",
            scope="everywhere",
            fixture="fixture_mutable_default.py",
        ),
        RuleInfo(
            "SL105", "traced-branch",
            "Python-level branching on a traced value inside a tpu/ "
            "kernel module",
            "host branches on device values force a blocking sync and "
            "bake one branch into the compiled graph (silent recompiles "
            "or wrong results under jit)",
            scope="shadow_tpu/tpu/",
            fixture="fixture_traced_branch.py",
        ),
        RuleInfo(
            "SL301", "sync-in-kernel",
            "jax.device_get / block_until_ready inside a tpu/ kernel "
            "body (a function that is jitted or a lax control-flow body)",
            "telemetry harvest and every other host readback stay "
            "OUTSIDE jitted code (docs/observability.md): a sync inside "
            "a kernel body blocks the device pipeline on every window "
            "and turns into a host callback under jit",
            scope="shadow_tpu/tpu/",
            fixture="fixture_kernel_sync.py",
        ),
        RuleInfo(
            "SL402", "assert-in-kernel",
            "Python `assert` inside a tpu/ kernel body (a jit-decorated"
            "/jit-wrapped function or a lax control-flow body)",
            "an assert in traced code runs ONCE at trace time against "
            "abstract values (and vanishes entirely under -O): it can "
            "never check runtime data, so it reads as an invariant "
            "check that silently is not one. Runtime invariants go "
            "through the guard plane (shadow_tpu/guards/, "
            "docs/robustness.md); trace-time shape/static checks use "
            "an explicit raise",
            scope="shadow_tpu/tpu/",
            fixture="fixture_kernel_assert.py",
        ),
        RuleInfo(
            "SL401", "swallowed-error",
            "broad exception swallow (`except Exception: pass` or a "
            "bare `except:` without re-raise)",
            "the fault plane's whole premise is that failures surface "
            "as structured, attributable events (docs/robustness.md); "
            "a silently swallowed broad exception turns a real fault "
            "into an unexplained hang or wrong result",
            scope="shadow_tpu/",
            fixture="fixture_swallowed.py",
        ),
        RuleInfo(
            "SL403", "variadic-sort",
            "lax.sort (or the `_row_sort` wrapper) carrying more than 3 "
            "payload operands through the comparator network in tpu/",
            "the sort diet (docs/performance.md): payload columns ride a "
            "packed-key permutation or a bucketed counting placement, "
            "never the O(n log n) comparator network — the variadic "
            "anti-pattern was the window step's dominant cost until PR 2 "
            "removed it; the compiled-in packed_sort=False parity "
            "reference paths carry justified suppressions",
            scope="shadow_tpu/tpu/",
            fixture="fixture_variadic_sort.py",
        ),
        RuleInfo(
            "SL405", "sync-telemetry-read",
            "host-side float(...)/.item() read of a device telemetry "
            "array (metrics/histogram/flight-recorder leaves) outside "
            "harvest-boundary code",
            "every observability read goes through the asynchronous "
            "TelemetryHarvester/FlightRecorder drain "
            "(docs/observability.md no-host-sync rule): a float()/"
            ".item() on a device counter is a blocking D2H sync that "
            "stalls the dispatch pipeline wherever it runs — "
            "shadow_tpu/telemetry/ (the harvest boundary itself) is "
            "the one sanctioned reader",
            scope="shadow_tpu/ except shadow_tpu/telemetry/ (the harvest boundary)",
            fixture="fixture_telemetry_read.py",
        ),
        RuleInfo(
            "SL201", "x64-leak",
            "64-bit dtype (float64/int64) appearing in a device jaxpr",
            "the device plane is int32/float32 by contract "
            "(tpu/plane.py dtype discipline); x64 leaks change numerics "
            "between hosts and recompile per weak-type",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_x64_leak.py",
        ),
        RuleInfo(
            "SL202", "convert-churn",
            "redundant convert_element_type chain in a device jaxpr",
            "dtype round-trips signal weak-type churn at jit boundaries "
            "— the classic silent-recompile trigger",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_convert_churn.py",
        ),
        RuleInfo(
            "SL203", "host-callback",
            "host callback primitive inside a jitted kernel",
            "callbacks leave the device mid-kernel: nondeterministic "
            "interleaving and a host sync on the hot path",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_host_callback.py",
        ),
        RuleInfo(
            "SL204", "transfer-in-loop",
            "host transfer/callback inside a while_loop/scan body",
            "a per-iteration device<->host hop turns an O(1)-dispatch "
            "window chain into O(iterations) syncs",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_loop_transfer.py",
        ),
        RuleInfo(
            "SL205", "baked-constant",
            "large constant baked into a jitted graph",
            "big captured constants bloat every compiled executable and "
            "re-upload on each compile; pass them as arguments instead",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_baked_constant.py",
        ),
        RuleInfo(
            "SL501", "presence-invisibility",
            "an observability-plane input leaf (metrics/guards/hist/"
            "flightrec; workload under the append-only relaxation) "
            "reaches a sim-state output leaf in the traced jaxpr",
            "the presence switches are bitwise-invisible BY THEOREM: "
            "the taint analysis (analysis/dataflow.py) proves, for "
            "every plane variant of window_step/chain_windows/"
            "ingest_rows and for all inputs, that no plane value can "
            "flow into NetPlaneState columns, the RNG counter, the "
            "clock offsets, or the delivered stream — where the "
            "runtime parity matrices only sample rr×aqm×no_loss "
            "corners (docs/determinism.md 'Proofs vs parity tests')",
            scope="proof registry (analysis/proofs.invisibility_specs)",
            fixture="fixture_taint_leak.py",
        ),
        RuleInfo(
            "SL502", "op-budget",
            "the static census of expensive primitives (sorts, "
            "gathers, scatter variants, control flow, pallas calls, "
            "host transfers) deviates from the checked-in "
            "analysis/op_budgets.json ledger",
            "the sort/scatter diet stays dieted without re-benching "
            "every PR: a reintroduced variadic sort or per-column "
            "scatter changes the census and fails CI in seconds; "
            "legitimate changes regenerate the ledger "
            "(tools/shadowlint.py --write-op-budgets) so every op-cost "
            "delta is explicit in the diff (docs/performance.md)",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_op_budget.py",
        ),
        RuleInfo(
            "SL503", "donation-safety",
            "a buffer-donation hazard: a donated array referenced "
            "after dispatch, or a raw jax.jit(donate_argnums=...) "
            "bypassing the donating_jit wrapper",
            "the donation contract (docs/performance.md): a donated "
            "pytree is CONSUMED by the call — XLA may alias its "
            "buffers in place, so a later host read sees garbage (or "
            "deleted-buffer errors). All donation goes through "
            "tpu.donating_jit (which donates on every backend, so the "
            "CPU tests enforce the contract) with consistent "
            "donate_argnums across the "
            "unified drivers; host code rebinds the returned state and "
            "never touches the donated argument again",
            scope="shadow_tpu/, tools/, bench.py",
            fixture="fixture_donation.py",
        ),
        RuleInfo(
            "SL504", "shardability-fence",
            "expensive primitives classified host-axis-local vs "
            "cross-host per audited section; GATES when a cross-host "
            "op appears in a row-local-pinned stage (tcp/codel)",
            "the ROADMAP-2 shard_map cut needs a scoped work-list "
            "before any million-host work starts: cross-host ops "
            "(gathers/scatters keyed by computed host ids, full-axis "
            "sorts, host-axis reductions) need a collective or a "
            "ragged exchange; host-local ops shard for free. The "
            "report (tools/shadowlint.py --shard-report) stays "
            "informational for most entries, but the tcp/codel "
            "row-local stages are pinned EMPTY "
            "(proofs.ROW_LOCAL_PINNED): a cross-host primitive "
            "sneaking into one fails the build — the regression fence "
            "for the shard_map refactor",
            scope="jaxpr audit registry (analysis/jaxpr_audit.py)",
            fixture="fixture_shard_classify.py",
        ),
        RuleInfo(
            "SL505", "branch-equivalence",
            "a registered lax.cond gate (gate_idle / ident-vs-sort / "
            "flow idle gates) whose branches are NOT provably "
            "bitwise-equal on the gated domain",
            "the device plane's cond gates may only ever change "
            "SPEED, never a bit: the idle gates must be the identity "
            "on entry-free windows and the ident-vs-sort gates must "
            "equal the sort on ordered input — the contract memoized "
            "replay and deeper sort-diet gating stand on. The prover "
            "(analysis/condeq.py) shows branch equality structurally "
            "(canonicalization + the sort-of-sorted rewrite + a "
            "selection witness) or by exhaustive evaluation over a "
            "registered boundary-value lattice, with the mode "
            "recorded per gate (docs/determinism.md 'Branch gates "
            "are theorems')",
            scope="gate registry (analysis/condeq.gate_obligations)",
            fixture="fixture_condeq_gate.py",
        ),
        RuleInfo(
            "SL601", "compiled-cost-budget",
            "a registered entry's compiled-HLO cost (XLA "
            "cost_analysis flops / bytes accessed / transcendentals) "
            "deviates from the platform-keyed "
            "analysis/cost_budgets.json beyond its tolerance band, or "
            "its peak temp watermark grows super-linearly across the "
            "two traced shapes",
            "the perf fences hold at BUILD time on the compiled "
            "artifact, which is container-independent for a given "
            "platform key — where every runtime gate only holds on a "
            "matched container (the PR-7/PR-11 cross-container "
            "false-regression lesson). analysis/costmodel.py lowers "
            "each cached jaxpr through jit().lower().compile(), diffs "
            "the cost scalars against the checked-in ledger, and "
            "extrapolates the temp watermark across two host-axis "
            "shapes (the ROADMAP-2 million-host memory fence); "
            "legitimate changes regenerate the ledger "
            "(--write-cost-budgets) so every cost delta is explicit "
            "in the diff (docs/performance.md 'Static cost fences')",
            scope="cost registry (analysis/costmodel.default_cost_entries)",
            fixture="fixture_fusion_break.py",
        ),
        RuleInfo(
            "SL602", "fusion-boundary",
            "a registered entry's optimized HLO materializes more "
            "[N,CE]-or-larger intermediates between fusions than the "
            "checked-in budget (or its fusion count drifts): a "
            "producer->consumer pair writing + re-reading a "
            "ring-sized buffer the fusion work should elide",
            "the compiled-floor attack (ROADMAP-4) is fusion work, "
            "and its progress must be monotone: every materialized "
            ">=[N,CE] boundary is a write+read of HBM/cache the "
            "rank->place->egress pipeline exists to remove, so the "
            "census is budgeted per entry and the full ranked "
            "worklist (shape, bytes, both ends, source op_name) is "
            "the artifact that fusion work consumes "
            "(--cost-report; docs/performance.md 'Static cost "
            "fences')",
            scope="cost registry (analysis/costmodel.default_cost_entries)",
            fixture="fixture_fusion_break.py",
        ),
        RuleInfo(
            "SL603", "host-sync-fence",
            "a per-iteration host sync (jax.device_get / .item() / "
            "float() / np.asarray / block_until_ready on a device "
            "value) inside a for/while body of a driver-loop module "
            "(bench.py, tools/chaos_smoke.py, workloads/runner.py, "
            "tpu/elastic.py)",
            "the chained driver's whole value is host syncs ONLY at "
            "chain ends (docs/performance.md 'The driver loop'): a "
            "blocking D2H read inside a driver loop re-serializes "
            "the dispatch pipeline per iteration — the SL405 "
            "telemetry rule generalized to every device value in the "
            "four modules that own a window loop. Chain-end/teardown "
            "reads outside loops and values already pulled through "
            "jax.device_get are the sanctioned pattern; deliberate "
            "in-loop syncs (the elastic overflow readback) carry "
            "justified allows in costmodel.HOST_SYNC_ALLOWED",
            scope="driver-loop modules (costmodel.DRIVER_MODULES)",
            fixture="fixture_host_sync.py",
        ),
        RuleInfo(
            "SL506", "integer-range",
            "a non-exempt signed-int32 op whose interval (seeded from "
            "the checked-in input-domain registry) admits wraparound",
            "the plane's int32-ns dtype discipline holds by interval "
            "arithmetic, not by luck: analysis/ranges.py propagates "
            "[lo, hi] through every audited plane/flows jaxpr — "
            "while-loop carries refined by the loop predicate, "
            "declared-modular counters wrap-exempt — and fails the "
            "build on any op that can overflow, naming the op, its "
            "source line, and the computed interval. Every 'no "
            "overflow because ...' comment is now either this "
            "theorem or a caught bug (docs/determinism.md)",
            scope="range registry (analysis/ranges.range_specs)",
            fixture="fixture_int_overflow.py",
        ),
        RuleInfo(
            "SL701", "world-isolation",
            "a primitive in a vmapped entry's batched jaxpr that "
            "reduces, gathers, scatters, sorts, concatenates, or "
            "otherwise combines values ACROSS the leading world axis",
            "the ensemble contract — world b of a W-world vmapped "
            "run equals world b's solo run — holds because NO "
            "dataflow path mixes two worlds: analysis/batchdim.py "
            "re-traces every audited entry under jax.vmap and walks "
            "axis provenance through every primitive (broadcast "
            "moves the world dim by broadcast_dimensions, gather/"
            "scatter must carry it in their declared batching dims, "
            "reductions must not name it). A finding names the op, "
            "its source line, and the offending axis; zero findings "
            "is the world-isolation theorem the worlds-parity test "
            "witnesses at runtime (docs/determinism.md 'Worlds are "
            "theorems')",
            scope="batch registry (analysis/batchdim.batch_entries)",
            fixture="fixture_cross_world.py",
        ),
        RuleInfo(
            "SL702", "rng-stream-disjointness",
            "a per-world RNG key derivation chain that is not "
            "provably injective in the world seed",
            "per-world randomness never collides because the "
            "derivation seed -> key is injective: "
            "analysis/batchdim.py walks the fold chain's jaxpr "
            "symbolically (mod-2^n bijections pass outright, "
            "non-bijective affine steps need a wrap-free interval "
            "argument over the declared seed domain — the SL506 "
            "machinery on fold-in arithmetic) and a threefry "
            "invocation under a fixed root key is a counter-block "
            "bijection. Distinct seeds therefore yield distinct "
            "derived keys, so no two worlds ever issue the same "
            "(key, counter) cipher call — counter streams are "
            "pairwise disjoint for all b != c",
            scope="RNG obligation registry "
                  "(analysis/batchdim.rng_obligations)",
            fixture="fixture_rng_overlap.py",
        ),
        RuleInfo(
            "SL703", "vmap-traceability-census",
            "an audited entry that fails to vmap at the two audit "
            "world counts, whose batched primitive census drifts "
            "with the world count, or a vmap refusal that is stale "
            "or rationale-free",
            "every entry on the audit surface is ensemble-ready BY "
            "CONSTRUCTION or refuses in writing: "
            "analysis/batchdim.py traces each entry under vmap at "
            "W=2 and W=3 and requires an identical primitive census "
            "(same graph, wider arrays — the world-count "
            "shape-polymorphism witness). An entry that cannot "
            "(an opaque kernel) refuses via batchdim.VMAP_REFUSALS "
            "with a written rationale — registered, not silent; a "
            "refusal naming a de-registered entry is itself a "
            "finding",
            scope="batch registry (analysis/batchdim.batch_entries "
                  "+ batchdim.VMAP_REFUSALS)",
            fixture="fixture_vmap_refusal.py",
        ),
    ]
}


@dataclass
class Finding:
    """One rule violation (or suppressed violation) with its location.

    ``line`` is 1-based for pass-1 findings and 0 for jaxpr findings,
    whose location is the audit entry name in ``path``.
    """

    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False
    justification: str = ""

    def to_json(self) -> dict:
        info = RULES[self.rule]
        return {
            "rule": self.rule,
            "name": info.name,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "suppressed": self.suppressed,
            "justification": self.justification,
        }

    def __str__(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}" if self.line else self.path
        tag = " [suppressed]" if self.suppressed else ""
        return f"{loc}: {self.rule} {self.message}{tag}"


_SUPPRESS_RE = re.compile(
    r"#\s*shadowlint:\s*disable=([A-Z0-9,\s]+?)"
    r"(?:\s*--\s*(.*?))?\s*$"
)


@dataclass
class Suppressions:
    """Per-file map of line -> {rule -> justification}.

    A disable comment on line L covers findings on L and L+1; an empty
    justification means the comment is malformed (missing ``-- reason``)
    and does NOT suppress.
    """

    by_line: dict[int, dict[str, str]] = field(default_factory=dict)
    malformed: list[tuple[int, str]] = field(default_factory=list)

    def lookup(self, rule: str, line: int) -> str | None:
        """Justification text if (rule, line) is suppressed, else None."""
        for cand in (line, line - 1):
            just = self.by_line.get(cand, {}).get(rule)
            if just:
                return just
        return None


def parse_suppressions(source: str) -> Suppressions:
    sup = Suppressions()
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = [r.strip() for r in m.group(1).split(",") if r.strip()]
        just = (m.group(2) or "").strip()
        if not just:
            sup.malformed.append((lineno, text.strip()))
            continue
        slot = sup.by_line.setdefault(lineno, {})
        for rule in rules:
            slot[rule] = just
    return sup
