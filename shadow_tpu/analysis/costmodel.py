"""shadowcost: compiled-HLO cost fences for the window plane (SL6xx).

Where shadowprove (SL501-SL506) proves the device plane *correct* at
build time, this pass proves it *cheap* at build time: every perf gate
before it was a runtime measurement that only holds on a matched
container (the PR-7/PR-11 cross-container false-regression saga). The
compiled artifact is the container-independent substrate — same jax/XLA
version, same platform key, same HLO — so its costs can be checked in
and diffed like any other ledger. Four legs over the registered cost
entries (``default_cost_entries``), all sharing the per-process
lower+compile memo (``jaxpr_audit.compiled``, keyed (trace_key,
platform)) on top of the PR-14 jaxpr trace cache:

- **SL601 compiled-cost budgets** — ``jit(...).lower().compile()``
  each entry, pull XLA ``cost_analysis()`` (flops, bytes accessed,
  transcendentals), and diff against the checked-in, platform-keyed
  ``analysis/cost_budgets.json`` under per-metric tolerance bands. A
  CI perf fence that needs no warm benchmark and never lies across
  containers: budgets for a platform only gate ON that platform.
  Regen is explicit (``tools/shadowlint.py --write-cost-budgets``), so
  every cost delta is visible in the diff.

- **SL601 watermark extrapolation** — compile ``window_step`` and
  ``chain_windows`` at TWO host-axis shapes and compare XLA
  ``memory_analysis()`` peak temp bytes: an entry whose temp watermark
  grows super-linearly in N fails the build. This is the regression
  fence for the ROADMAP-2 million-host ``shard_map`` work — a hidden
  [N, N] (or worse) temp at N=4 is a terabyte at N=1M.

- **SL602 fusion-boundary census** — parse the optimized HLO and
  census every producer->consumer pair that MATERIALIZES an
  [N, CE]-or-larger intermediate between fusions (post-fusion, every
  non-fused value is a real buffer: a write + a read the fusion work
  would elide). The per-entry count is budgeted next to the SL601
  metrics; the full ranked worklist — shape, bytes, both ends, the
  source ``op_name`` — is the artifact ROADMAP-4's rank->place->egress
  fusion work consumes (``--cost-report``).

- **SL603 host-sync fence** — the SL405 telemetry-read rule
  generalized tree-wide: in the driver-loop modules (``bench.py``,
  ``tools/chaos_smoke.py``, ``workloads/runner.py``, ``tpu/elastic.py``)
  any ``jax.device_get`` / ``.item()`` / ``float()`` / ``np.asarray``
  / ``block_until_ready`` on a device value INSIDE a ``for``/``while``
  body is a per-iteration blocking sync — the exact pipeline stall the
  chained driver exists to amortize — and fails the build. Chain-end /
  teardown reads outside loops are the sanctioned drain pattern
  (harvester ticks and flight-recorder drains run from ``on_chain``
  callbacks, which are not lexically inside loops); values already
  pulled through one ``jax.device_get`` are host-side and exempt.
  Justified exceptions live in the ``HOST_SYNC_ALLOWED`` registry
  (or a standard suppression comment).

Docs: docs/performance.md "Static cost fences";
docs/determinism.md rules table (SL601/SL602/SL603).
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass
from typing import Callable

from .rules import Finding, parse_suppressions

__all__ = [
    "CostEntry",
    "DRIVER_MODULES",
    "HOST_SYNC_ALLOWED",
    "build_cost_report",
    "check_cost_budgets",
    "check_host_sync",
    "check_host_sync_source",
    "check_watermarks",
    "cost_budget_path",
    "default_cost_entries",
    "entry_costs",
    "format_cost_delta",
    "fusion_boundaries",
    "proof_gate_budget_s",
    "run_cost_pass",
    "write_cost_budgets",
]


# --------------------------------------------------------------------------
# the cost-entry registry
# --------------------------------------------------------------------------


@dataclass
class CostEntry:
    """One budgeted compiled entry.

    ``key`` doubles as the shared trace/compile cache key AND the
    budget-ledger key; ``build`` is the zero-arg (fn, args) thunk
    (reused from the jaxpr-audit registry wherever possible — one
    builder per cache key, the PR-14 collision rule). ``n``/``ce``
    give the traced host-axis size and egress ring width, so the
    SL602 "[N, CE]-or-larger" materialization threshold scales with
    the entry's own shape. ``scale`` names the watermark twin: a
    second build at ``scale_n`` hosts whose peak temp bytes must stay
    within a linear extrapolation of the base shape's.
    """

    key: str
    n: int
    ce: int
    build: Callable[[], tuple]
    scale_n: int | None = None
    scale_build: Callable[[], tuple] | None = None

    @property
    def scale_key(self) -> str | None:
        return f"{self.key}@n{self.scale_n}" if self.scale_n else None


def default_cost_entries() -> list[CostEntry]:
    """The budgeted surface: the window-step compile modes the drivers
    actually dispatch (hot path, lean, flows), the ingest kernel, the
    device-resident chain, and the standalone flow kernel — every
    builder REUSED from the jaxpr-audit registry so the cost ledger and
    the op ledger can never diverge on what an entry is. One deliberate
    exclusion, priced by the seven-family proof-gate time budget (one
    CI step, one shared cache): ``window_step[flows]`` (its compiled
    cost is structurally ``window_step[lean]`` + the standalone
    ``flow_step`` kernel, both budgeted here; the flow sections' fusion
    structure is censused on ``flow_step`` where it is not diluted by
    the window body).
    window_step and chain_windows carry the two-shape watermark pairs
    the ROADMAP-2 shard_map fence extrapolates from."""
    from .jaxpr_audit import (_chain_entry, _compute_entry,
                              _flows_entry, _ingest_rows_entry,
                              _plane_entry, ensemble_step_build)

    mod = "shadow_tpu.tpu.plane"
    return [
        CostEntry(f"{mod}:window_step[rr,aqm,loss]", 4, 8,
                  _plane_entry(True, True, False)),
        CostEntry(f"{mod}:window_step[lean]", 4, 8,
                  _plane_entry(False, False, True),
                  scale_n=8,
                  scale_build=_plane_entry(False, False, True, n=8)),
        CostEntry(f"{mod}:ingest_rows[planes]", 4, 8,
                  _ingest_rows_entry()),
        CostEntry(f"{mod}:chain_windows", 4, 8,
                  _chain_entry(),
                  scale_n=8, scale_build=_chain_entry(n=8)),
        CostEntry("shadow_tpu.tpu.flows:flow_step", 4, 8,
                  _flows_entry("step")),
        # the compute plane (ISSUE-20): the compute-threaded window
        # step IS a dispatched driver mode (family `serve`), so it is
        # budgeted whole — unlike window_step[flows] it adds only the
        # O(N*CI) FIFO section, and a regression there would hide
        # inside the lean budget's slack if priced by decomposition
        CostEntry(f"{mod}:window_step[compute]", 4, 8,
                  _compute_entry("window")),
        # the SL601 ensemble fence (ISSUE-16): the vmapped ensemble
        # step at two WORLD counts — `n` here is the scaled dimension
        # (worlds, not hosts), so the W=2 -> W=4 watermark pair fences
        # super-linear ensemble memory exactly like the host-axis
        # n=4 -> n=8 pairs above. The key matches batchdim's @vmapW2
        # trace-cache variant, so the proof pass and the cost pass
        # share one trace of the batched step.
        CostEntry("shadow_tpu.tpu.elastic:ensemble_step[lean]@vmapW2",
                  2, 8, ensemble_step_build(2),
                  scale_n=4, scale_build=ensemble_step_build(4)),
    ]


def _compiled(key: str, build):
    from .jaxpr_audit import compiled

    return compiled(key, build)


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


# --------------------------------------------------------------------------
# optimized-HLO parsing (the SL602 substrate)
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: one array-shape atom: ``f32[64,64]{1,0}`` / ``s32[]`` / ``pred[4,8]``
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")

#: ``  [ROOT ]%name = <shape(s)> opcode(...`` — shapes may be a
#: parenthesized tuple, so the opcode is matched as the last word
#: before the first call paren
_INSTR_RE = re.compile(
    r"^\s+(ROOT\s+)?%([\w.\-]+)\s+=\s+(.*?)\s+([\w\-]+)\(")

_COMP_HEAD_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")


def _match_comp_header(line: str):
    """(is_entry, name) when `line` opens a computation, else None.
    Parameter lists NEST parens (while/cond region params are tuples:
    ``%region_1.655 (arg_tuple.656: (u32[4,8], ...)) -> ... {``), so
    the list is balanced procedurally before requiring the ``->``
    return arrow — a plain regex here silently dropped every loop
    body from the census."""
    if not line.rstrip().endswith("{"):
        return None
    m = _COMP_HEAD_RE.match(line)
    if m is None:
        return None
    i, depth = m.end() - 1, 0
    while i < len(line):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    if "->" not in line[i:]:
        return None
    return bool(m.group(1)), m.group(2)


@dataclass
class _Instr:
    name: str
    opcode: str
    #: (dtype, element_count, shape_text) per array in the result
    results: list[tuple[str, int, str]]
    operands: list[str]
    op_name: str
    is_root: bool


def _parse_shapes(text: str) -> list[tuple[str, int, str]]:
    out = []
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue  # token/opaque types carry no buffer of interest
        count = 1
        for d in dims.split(","):
            if d:
                count *= int(d)
        out.append((dtype, count, f"{dtype}[{dims}]"))
    return out


def _parse_instr(line: str) -> _Instr | None:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    is_root, name, shapes_text, opcode = (
        bool(m.group(1)), m.group(2), m.group(3), m.group(4))
    # operands live between the opcode's '(' and its matching ')'
    start = m.end()
    depth, i = 1, start
    while i < len(line) and depth:
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
        i += 1
    operands = re.findall(r"%([\w.\-]+)", line[start:i - 1])
    op_name = ""
    nm = re.search(r'op_name="([^"]*)"', line)
    if nm:
        op_name = nm.group(1)
    return _Instr(name, opcode, _parse_shapes(shapes_text), operands,
                  op_name, is_root)


def _parse_hlo(text: str) -> dict[str, tuple[bool, list[_Instr]]]:
    """computation name -> (is_entry, instructions), across the whole
    optimized module."""
    comps: dict[str, tuple[bool, list[_Instr]]] = {}
    current: list[_Instr] | None = None
    for line in text.splitlines():
        if current is None:
            head = _match_comp_header(line)
            if head is not None:
                current = []
                comps[head[1]] = (head[0], current)
            continue
        if line.startswith("}"):
            current = None
            continue
        instr = _parse_instr(line)
        if instr is not None:
            current.append(instr)
    return comps


def count_fusions(text: str) -> int:
    """Fusion instructions across every computation of the module."""
    return _count_fusions(_parse_hlo(text))


def _count_fusions(comps: dict) -> int:
    return sum(1 for _name, (_e, instrs) in comps.items()
               for ins in instrs if ins.opcode == "fusion")


#: opcodes whose results are not *materialized intermediates* the
#: fusion work could elide: inputs, pure aliasing/bookkeeping, and the
#: control-flow wrappers (their bodies are censused separately — a
#: while's carry is the loop contract, not a fusion boundary)
_NOT_A_BOUNDARY = frozenset({
    "parameter", "constant", "iota", "get-tuple-element", "tuple",
    "bitcast", "copy", "after-all", "while", "conditional", "call",
})


#: consumers that merely repackage a value (no read of the bytes):
#: looked THROUGH when resolving who actually consumes a buffer — a
#: value whose resolved consumer set is empty only feeds the
#: computation's outputs, which no fusion can elide
_TRANSPARENT_CONSUMERS = frozenset({
    "tuple", "get-tuple-element", "bitcast", "copy",
})


def fusion_boundaries(text: str, min_elems: int) -> list[dict]:
    """Every producer->consumer pair in the optimized module that
    materializes an array of >= `min_elems` elements between fusions,
    ranked largest-first. Fused-computation bodies are skipped
    (nothing inside a fusion materializes); every other computation —
    entry, while/cond bodies — is censused, since chain_windows' hot
    path lives in its while body. Consumers are resolved through
    tuple/GTE repackaging, and a value that only reaches the ROOT
    (an output, not an intermediate) is not a boundary."""
    return _boundaries_from(_parse_hlo(text), min_elems)


def _boundaries_from(comps: dict, min_elems: int) -> list[dict]:
    out = []
    for comp_name, (is_entry, instrs) in comps.items():
        if "fused_computation" in comp_name:
            continue
        direct: dict[str, list[_Instr]] = {}
        for ins in instrs:
            for op in ins.operands:
                direct.setdefault(op, []).append(ins)

        def real_consumers(name: str, seen: set[str]) -> set[str]:
            found: set[str] = set()
            for ins in direct.get(name, ()):
                if ins.opcode in _TRANSPARENT_CONSUMERS:
                    # root repackaging = the value exits the
                    # computation; a non-root repack forwards to its
                    # own consumers
                    if not ins.is_root and ins.name not in seen:
                        seen.add(ins.name)
                        found |= real_consumers(ins.name, seen)
                else:
                    # a computing root still READS the buffer
                    found.add(f"{ins.opcode}:{ins.name}")
            return found

        for ins in instrs:
            if ins.is_root or ins.opcode in _NOT_A_BOUNDARY:
                continue
            big = [(d, c, s) for d, c, s in ins.results
                   if c >= min_elems]
            if not big:
                continue
            used_by = real_consumers(ins.name, set())
            if not used_by:
                continue
            nbytes = sum(_DTYPE_BYTES[d] * c for d, c, _s in big)
            out.append({
                "computation": "entry" if is_entry else comp_name,
                "producer": f"{ins.opcode}:{ins.name}",
                "consumers": sorted(used_by),
                "shapes": [s for _d, _c, s in big],
                "bytes": nbytes,
                "op_name": ins.op_name,
            })
    out.sort(key=lambda b: (-b["bytes"], b["computation"],
                            b["producer"]))
    return out


# --------------------------------------------------------------------------
# per-entry compiled costs
# --------------------------------------------------------------------------

#: per-process memo of the parsed costs, keyed (entry key, platform)
_COSTS_CACHE: dict[tuple[str, str], dict] = {}


def entry_costs(entry: CostEntry) -> dict:
    """The budgetable metrics + the boundary worklist for one entry,
    off the shared compile memo: XLA cost_analysis scalars, the module
    fusion count, the >=[N, CE] boundary census, and the peak temp
    bytes (memory_analysis)."""
    cache_key = (entry.key, _platform())
    hit = _COSTS_CACHE.get(cache_key)
    if hit is not None:
        return hit
    comp = _compiled(entry.key, entry.build)
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    comps = _parse_hlo(comp.as_text())  # ONE parse feeds both censuses
    boundaries = _boundaries_from(comps, entry.n * entry.ce)
    mem = comp.memory_analysis()
    hit = {
        "metrics": {
            "flops": int(ca.get("flops", 0)),
            "bytes_accessed": int(ca.get("bytes accessed", 0)),
            "transcendentals": int(ca.get("transcendentals", 0)),
            "fusions": _count_fusions(comps),
            "big_boundaries": len(boundaries),
        },
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
        "boundaries": boundaries,
        "threshold_elems": entry.n * entry.ce,
    }
    _COSTS_CACHE[cache_key] = hit
    return hit


# --------------------------------------------------------------------------
# SL601/SL602: the platform-keyed cost ledger
# --------------------------------------------------------------------------

_COST_BUDGET_FILE = "cost_budgets.json"

#: which rule owns each budgeted metric: arithmetic/traffic costs are
#: SL601, fusion-structure counts are SL602
_METRIC_RULE = {
    "flops": "SL601",
    "bytes_accessed": "SL601",
    "transcendentals": "SL601",
    "fusions": "SL602",
    "big_boundaries": "SL602",
}

#: default tolerance bands, mirrored into the checked-in ledger so
#: they are reviewable next to the numbers they guard. A metric passes
#: when it is within the relative band OR the absolute one (small
#: counts need the abs floor; big counts need the rel band).
_DEFAULT_TOLERANCE = {
    "flops": {"rel": 0.25, "abs": 64},
    "bytes_accessed": {"rel": 0.25, "abs": 4096},
    "transcendentals": {"rel": 0.25, "abs": 8},
    "fusions": {"abs": 2},
    "big_boundaries": {"abs": 0},
}


def cost_budget_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        _COST_BUDGET_FILE)


def _canonical_dump(doc: dict, path: str) -> None:
    """ONE spelling for ledger bytes (op + cost budgets): sorted keys,
    indent 2, trailing newline — so a double regen is byte-identical
    and a regen diff is minimal (pinned by tests/test_costmodel.py)."""
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def write_cost_budgets(path: str | None = None, entries=None) -> dict:
    """Regenerate THIS platform's section of the cost ledger,
    preserving every other platform's budgets (an accelerator
    container's numbers survive a CPU-container regen and vice versa).
    With an explicit `entries` subset only those keys update."""
    path = path or cost_budget_path()
    doc = {
        "_comment": (
            "SL601/SL602 compiled-cost ledger: XLA cost_analysis "
            "scalars + fusion/boundary census per registered cost "
            "entry (analysis/costmodel.default_cost_entries), keyed "
            "by platform — budgets only gate on the platform they "
            "were measured on, so this fence never lies across "
            "containers. CI diffs the live compile against this file "
            "under the tolerance bands below; regenerate via `python "
            "tools/shadowlint.py --write-cost-budgets` and justify "
            "the delta in the PR."),
        "version": 1,
        "tolerance": _DEFAULT_TOLERANCE,
        "platforms": {},
    }
    if os.path.exists(path):
        with open(path, encoding="utf-8") as fh:
            prior = json.load(fh)
        doc["platforms"] = prior.get("platforms", {})
        doc["tolerance"] = prior.get("tolerance", _DEFAULT_TOLERANCE)
    all_entries = entries if entries is not None \
        else default_cost_entries()
    platform = _platform()
    section = {} if entries is None \
        else dict(doc["platforms"].get(platform, {}))
    for entry in all_entries:
        section[entry.key] = dict(
            sorted(entry_costs(entry)["metrics"].items()))
    doc["platforms"][platform] = section
    _canonical_dump(doc, path)
    return doc


def _within(want: int, have: int, tol: dict) -> bool:
    if have == want:  # exact match passes under ANY band shape
        return True
    delta = abs(have - want)
    if "rel" in tol and want and delta <= tol["rel"] * abs(want):
        return True
    return "abs" in tol and delta <= tol["abs"]


def check_cost_budgets(path: str | None = None, entries=None
                       ) -> tuple[list[Finding], list[dict]]:
    """Diff the live compiled costs against the checked-in ledger for
    THIS platform. Returns (findings, deltas); deltas carry the
    budget-vs-actual table the CLI renders on failure."""
    path = path or cost_budget_path()
    entries = entries if entries is not None else default_cost_entries()

    def infra(where: str, message: str) -> list[Finding]:
        # ledger-infrastructure failures (missing file / platform /
        # entry) break BOTH budget families: emit one finding per
        # rule, so a `--only SL602` run can never go green on a
        # ledger it could not check (main() filters by selected rule)
        return [Finding(rule, where, 0, 0, message)
                for rule in ("SL601", "SL602")]

    if not os.path.exists(path):
        return infra(
            path,
            "cost ledger missing: run `python tools/shadowlint.py "
            "--write-cost-budgets` and check the file in"), []
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    platform = _platform()
    budgets = doc.get("platforms", {}).get(platform)
    if budgets is None:
        return infra(
            path,
            f"no cost budgets for platform `{platform}`: regenerate "
            "the ledger on this container (--write-cost-budgets) so "
            "the fence gates here too"), []
    tolerance = doc.get("tolerance", _DEFAULT_TOLERANCE)

    findings: list[Finding] = []
    deltas: list[dict] = []
    live = {e.key: e for e in entries}
    for key in sorted(set(budgets) | set(live)):
        want = budgets.get(key)
        entry = live.get(key)
        if want is None:
            findings.extend(infra(
                key,
                "cost entry has no budget on this platform: "
                "regenerate the ledger (--write-cost-budgets) so the "
                "new entry's compiled cost is pinned"))
            continue
        if entry is None:
            findings.extend(infra(
                key,
                "budgeted cost entry no longer registered: regenerate "
                "the ledger (--write-cost-budgets) to drop it "
                "explicitly"))
            continue
        costs = entry_costs(entry)
        have = costs["metrics"]
        diff = {}
        for metric in sorted(set(want) | set(have)):
            w, h = int(want.get(metric, 0)), int(have.get(metric, 0))
            tol = tolerance.get(metric, {})
            if not _within(w, h, tol):
                diff[metric] = {"budget": w, "actual": h}
        if not diff:
            continue
        deltas.append({"entry": key, "platform": platform,
                       "delta": diff})
        for rule in ("SL601", "SL602"):
            ruled = [m for m in diff if _METRIC_RULE.get(m, "SL601")
                     == rule]
            if not ruled:
                continue
            worst = max(ruled, key=lambda m: abs(diff[m]["actual"]
                                                 - diff[m]["budget"]))
            extra = ""
            if rule == "SL602" and costs["boundaries"]:
                top = costs["boundaries"][0]
                extra = (f"; largest boundary `{top['producer']} -> "
                         f"{', '.join(top['consumers'])}` materializes "
                         f"{'+'.join(top['shapes'])} "
                         f"({top['bytes']} B) at "
                         f"`{top['op_name'] or top['computation']}`")
            findings.append(Finding(
                rule, key, 0, 0,
                f"compiled {worst} deviates from the checked-in "
                f"budget ({diff[worst]['budget']} budgeted, "
                f"{diff[worst]['actual']} actual, platform "
                f"`{platform}`"
                + (f"; +{len(ruled) - 1} more metric(s)"
                   if len(ruled) > 1 else "")
                + ")" + extra
                + " — a compiled-cost regression, or a ledger update "
                "missing from this diff (--write-cost-budgets)"))
    return findings, deltas


def format_cost_delta(deltas: list[dict]) -> str:
    """Readable budget-vs-actual table for the CI log (same shape as
    the SL502 table)."""
    lines = ["entry                                    metric"
             "               budget  actual   delta"]
    for d in deltas:
        for metric, v in sorted(d["delta"].items()):
            lines.append(
                f"{d['entry'][:40]:<40} {metric:<18} "
                f"{v['budget']:>8}  {v['actual']:>6}  "
                f"{v['actual'] - v['budget']:>+6}")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# SL601: two-shape watermark extrapolation
# --------------------------------------------------------------------------

#: a temp watermark may grow up to `slack` times faster than the
#: host-axis shape before it reads as super-linear; the absolute floor
#: absorbs shape-independent scratch (compiler bookkeeping, small
#: per-column pads) that would otherwise dominate tiny trace shapes
WATERMARK_SLACK = 1.5
WATERMARK_FLOOR_BYTES = 4096


def proof_gate_budget_s(n_cpus: int | None = None) -> int:
    """THE wall-time budget for the gating shadowlint proof step,
    scaled to the runner: CI wraps the gate in
    ``timeout $(python -c 'from shadow_tpu.analysis.costmodel import
    proof_gate_budget_s; print(proof_gate_budget_s())')``.

    The fixed 30s budget PR 15 inherited failed by ~1.3s on 1-CPU
    containers, so the pin is now a measured cost model instead of a
    constant: on a 1-CPU runner the seven SL5xx/SL6xx families cost
    ~31s and the SL7xx batch pass adds ~45-55s of vmap re-tracing
    (two world counts over the 28 non-refused entries; measured
    2026-08 on the CI container class). Tracing parallelizes poorly
    but XLA compilation and the interpreter walks do gain from extra
    cores, hence the 1/n term; the constant floor absorbs the
    serial trace path. Budget = 60 + 120/n seconds, i.e. 180s on the
    1-CPU runner (~2x the measured 86s total) and 90s at 4 cores —
    tight enough that a hung trace or an accidental second compile
    sweep still fails fast, loose enough that scheduler jitter on
    small runners cannot flake the gate."""
    if n_cpus is None:
        n_cpus = os.cpu_count() or 1
    return 60 + 120 // max(1, n_cpus)


def check_watermarks(entries=None) -> tuple[list[Finding], list[dict]]:
    """Compile each watermark pair at both shapes and fail any entry
    whose peak temp bytes grow faster than linearly in N (with slack):
    ``temp(n2) <= temp(n1) * (n2/n1) * slack + floor``. The ROADMAP-2
    shard_map fence: at a million hosts, a super-linear temp is the
    difference between a shard that fits and one that cannot exist."""
    findings: list[Finding] = []
    rows: list[dict] = []
    for entry in (entries if entries is not None
                  else default_cost_entries()):
        if entry.scale_build is None:
            continue
        temp1 = entry_costs(entry)["temp_bytes"]
        comp2 = _compiled(entry.scale_key, entry.scale_build)
        mem2 = comp2.memory_analysis()
        temp2 = int(getattr(mem2, "temp_size_in_bytes", 0) or 0)
        factor = entry.scale_n / entry.n
        bound = int(temp1 * factor * WATERMARK_SLACK
                    + WATERMARK_FLOOR_BYTES)
        ok = temp2 <= bound
        rows.append({
            "entry": entry.key, "n1": entry.n, "n2": entry.scale_n,
            "temp1_bytes": temp1, "temp2_bytes": temp2,
            "linear_bound_bytes": bound, "ok": ok,
        })
        if not ok:
            growth = temp2 / max(temp1, 1)
            findings.append(Finding(
                "SL601", entry.key, 0, 0,
                f"peak temp watermark grows super-linearly in N: "
                f"{temp1} B at N={entry.n} -> {temp2} B at "
                f"N={entry.scale_n} ({growth:.1f}x for a {factor:.0f}x "
                f"shape; linear bound {bound} B) — a hidden "
                "quadratic-in-hosts buffer, the exact thing the "
                "ROADMAP-2 million-host shard_map cut cannot absorb"))
    return findings, rows


# --------------------------------------------------------------------------
# SL603: the tree-wide host-sync fence
# --------------------------------------------------------------------------

#: the driver-loop modules the fence covers — the files that own a
#: window-driving loop (everything else either is the sanctioned
#: harvest boundary, shadow_tpu/telemetry/, or never holds device
#: values in a loop). The shadowscope tracer and its report CLI are
#: swept too: the run ledger is emitted AT the chain-boundary sync and
#: must stay incapable of smuggling a per-span device read in later
#: (docs/observability.md "Run ledger").
DRIVER_MODULES = (
    "bench.py",
    "tools/chaos_smoke.py",
    "tools/trace_report.py",
    "shadow_tpu/workloads/runner.py",
    "shadow_tpu/tpu/elastic.py",
    "shadow_tpu/telemetry/tracer.py",
)

#: (repo-relative path, enclosing function) -> justification. The
#: registry analogue of the jaxpr-audit allow-lists: every sanctioned
#: in-loop sync documents WHY it must block there.
HOST_SYNC_ALLOWED: dict[tuple[str, str], str] = {
    ("shadow_tpu/tpu/elastic.py", "run_elastic_window"): (
        "the elastic capacity policy's decision point: one per-ring "
        "overflow readback per CHAIN attempt is the driver contract "
        "(docs/robustness.md 'Elastic capacity') — chain_len amortizes "
        "the sync, and the growth decision cannot be made without "
        "materializing the overflow counters"),
}

#: call leaves that ARE a blocking device sync wherever they run
_SYNC_CALL_PATHS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_ATTR_LEAVES = {"item", "block_until_ready"}
#: host-materialization callables that sync when fed a device value.
#: DELIBERATELY not ``int``/``bool``: in this tree those coerce host
#: values (regex groups, numpy post-processing scalars, python ints)
#: almost exclusively — adding them costs ~6 false positives per
#: driver module sweep for a spelling (bare ``int(device_scalar)``)
#: no in-tree code uses; every real device read routes through
#: device_get / np.asarray / .item() / float(), which ARE netted.
#: A lexical fence buys zero-noise gating at the price of that hole.
_MATERIALIZERS = {"float"}
_NP_MATERIALIZERS = {"numpy.asarray", "numpy.array", "numpy.atleast_1d"}


class _HostNames:
    """Flow-insensitive per-scope set of names known to hold HOST
    values (pulled through jax.device_get, or plain numpy
    constructions): float()/np.asarray()/.item() on those is host
    arithmetic, not a device sync."""

    def __init__(self):
        self._scopes: list[set[str]] = [set()]

    def push(self):
        self._scopes.append(set())

    def pop(self):
        self._scopes.pop()

    def mark(self, name: str):
        self._scopes[-1].add(name)

    def unmark(self, name: str):
        for s in self._scopes:
            s.discard(name)

    def is_host(self, name: str) -> bool:
        return any(name in s for s in self._scopes)


def _resolve(imports: dict[str, str], node: ast.expr) -> str | None:
    """Dotted path through the import table (the astlint discipline,
    inlined: the cost pass must not import jax to lint sources)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = imports.get(node.id)
    if root is None:
        if parts:
            return None
        root = node.id
    parts.append(root)
    return ".".join(reversed(parts))


def _contains_device_get(node: ast.AST, imports: dict[str, str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if _resolve(imports, sub.func) == "jax.device_get":
                return True
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "device_get":
                return True
    return False


def _operand_is_host(node: ast.expr, imports: dict[str, str],
                     hosts: _HostNames) -> bool:
    """True when the expression provably reads host memory: it is
    routed through jax.device_get itself, or every Name it touches is
    a known host value (and it touches at least one)."""
    if _contains_device_get(node, imports):
        return True
    names = [s for s in ast.walk(node) if isinstance(s, ast.Name)]
    return bool(names) and all(hosts.is_host(n.id) for n in names)


class _SyncFence(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.relpath = relpath
        self.imports: dict[str, str] = {}
        self.hosts = _HostNames()
        self.loop_depth = 0
        self.fn_stack: list[str] = []
        self.findings: list[Finding] = []

    # -- bookkeeping -----------------------------------------------------

    def visit_Import(self, node):
        for alias in node.names:
            self.imports[alias.asname or alias.name.split(".")[0]] = (
                alias.name if alias.asname
                else alias.name.split(".")[0])

    def visit_ImportFrom(self, node):
        if node.level or not node.module:
            return
        for alias in node.names:
            self.imports[alias.asname or alias.name] = (
                f"{node.module}.{alias.name}")

    def _visit_fn(self, node):
        self.fn_stack.append(node.name)
        self.hosts.push()
        # a function body is a fresh sync context: the loop that
        # matters is the one INSIDE the function, not a loop that
        # happens to define it (a def in a loop runs later, not
        # per-iteration)
        outer, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = outer
        self.hosts.pop()
        self.fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_Assign(self, node):
        # host-producing values: a device_get anywhere in the value
        # (the pull itself), or a numpy materializer call. NOT
        # block_until_ready — it returns the DEVICE array, only
        # flushed (a later read still syncs)
        is_host = isinstance(node.value, ast.Call) and (
            _resolve(self.imports, node.value.func)
            in _NP_MATERIALIZERS
            or _contains_device_get(node.value, self.imports))
        for target in node.targets:
            if isinstance(target, ast.Name):
                (self.hosts.mark if is_host
                 else self.hosts.unmark)(target.id)
        self.generic_visit(node)

    def _mark_host_targets(self, target, iter_expr):
        """Loop/comprehension targets drawn from a host iterable (a
        device_get'd pull, or an expression over already-host names)
        are host values inside the body."""
        if not _operand_is_host(iter_expr, self.imports, self.hosts):
            return
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name):
                self.hosts.mark(sub.id)

    def _visit_loop(self, node):
        if isinstance(node, (ast.For, ast.AsyncFor)):
            # the iterable evaluates ONCE — only the body repeats
            self.visit(node.iter)
            self._mark_host_targets(node.target, node.iter)
            self.loop_depth += 1
            for stmt in list(node.body) + list(node.orelse):
                self.visit(stmt)
            self.loop_depth -= 1
        else:  # while: the test re-evaluates per iteration
            self.loop_depth += 1
            self.visit(node.test)
            for stmt in list(node.body) + list(node.orelse):
                self.visit(stmt)
            self.loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def _visit_comp(self, node):
        # a comprehension IS a loop: its element expression and the
        # later generators re-evaluate per item (only the first
        # generator's iterable runs once) — without this, any flagged
        # `for` could be rewritten as a listcomp to dodge the fence
        gens = node.generators
        self.visit(gens[0].iter)
        self._mark_host_targets(gens[0].target, gens[0].iter)
        self.loop_depth += 1
        for i, gen in enumerate(gens):
            if i > 0:
                self.visit(gen.iter)
                self._mark_host_targets(gen.target, gen.iter)
            for cond in gen.ifs:
                self.visit(cond)
        if isinstance(node, ast.DictComp):
            self.visit(node.key)
            self.visit(node.value)
        else:
            self.visit(node.elt)
        self.loop_depth -= 1

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    # -- the fence -------------------------------------------------------

    def _emit(self, node, what: str):
        fn = self.fn_stack[-1] if self.fn_stack else "<module>"
        finding = Finding(
            "SL603", self.relpath, node.lineno, node.col_offset,
            f"per-iteration host sync `{what}` inside a driver loop "
            f"(in `{fn}`): every pass blocks the dispatch pipeline on "
            "a D2H readback — drain at chain ends (`on_chain`) or "
            "through the asynchronous harvester/flight-recorder "
            "instead (docs/performance.md 'Static cost fences')")
        allow = HOST_SYNC_ALLOWED.get((self.relpath, fn))
        if allow:
            finding.suppressed = True
            finding.justification = allow
        self.findings.append(finding)

    def visit_Call(self, node):
        if self.loop_depth:
            resolved = _resolve(self.imports, node.func)
            if resolved in _SYNC_CALL_PATHS:
                self._emit(node, resolved)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_ATTR_LEAVES \
                    and not _operand_is_host(node.func.value,
                                             self.imports, self.hosts):
                self._emit(node, f"...{node.func.attr}()")
            elif resolved in (_MATERIALIZERS | _NP_MATERIALIZERS) \
                    and node.args \
                    and not _operand_is_host(node.args[0],
                                             self.imports, self.hosts):
                self._emit(node, f"{resolved}(...)")
        self.generic_visit(node)


def check_host_sync_source(source: str, relpath: str) -> list[Finding]:
    """SL603 over one file's text; standard suppression comments and
    the HOST_SYNC_ALLOWED registry both mark findings suppressed."""
    tree = ast.parse(source, filename=relpath)
    fence = _SyncFence(relpath)
    fence.visit(tree)
    sup = parse_suppressions(source)
    for f in fence.findings:
        just = sup.lookup(f.rule, f.line)
        if just is not None:
            f.suppressed = True
            f.justification = just
    return sorted(fence.findings, key=lambda f: (f.path, f.line, f.col))


def check_host_sync(repo_root: str | None = None) -> list[Finding]:
    """The tree-wide fence: every DRIVER_MODULES file, findings
    suppressed only by the registry or a justified comment."""
    root = repo_root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    findings: list[Finding] = []
    for rel in DRIVER_MODULES:
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            findings.append(Finding(
                "SL603", rel, 0, 0,
                "driver module missing: the host-sync fence cannot "
                "check it (update costmodel.DRIVER_MODULES)"))
            continue
        with open(path, encoding="utf-8") as fh:
            findings.extend(check_host_sync_source(fh.read(), rel))
    return findings


# --------------------------------------------------------------------------
# the pass driver + report artifact
# --------------------------------------------------------------------------

#: how many boundary rows ride in each PER-ENTRY report section (a
#: readable head next to the metrics); the cross-entry
#: ``fusion_worklist`` is COMPLETE — a consumer working it top-down
#: must never believe a truncated list was everything (the no-silent-
#: caps rule), so each section also carries its ``boundaries_total``
_WORKLIST_PER_ENTRY = 12


def build_cost_report(entries=None, *, budget_findings=None,
                      deltas=None, watermarks=None,
                      sync_findings=None) -> dict:
    """The ``--cost-report`` artifact: per-entry compiled costs, the
    ranked fusion-boundary worklist (the ROADMAP-4 handoff), the
    watermark extrapolation rows, and the host-sync scan. Pre-computed
    pieces are passed in by run_cost_pass so a gating run builds the
    artifact for free; a report-only run computes them here."""
    entries = entries if entries is not None else default_cost_entries()
    if watermarks is None:
        _wf, watermarks = check_watermarks(entries)
    if sync_findings is None:
        sync_findings = check_host_sync()
    if deltas is None and budget_findings is None:
        budget_findings, deltas = check_cost_budgets(entries=entries)

    sections = []
    worklist = []
    for entry in entries:
        costs = entry_costs(entry)
        sections.append({
            "entry": entry.key,
            "traced_shape": {"n": entry.n, "ce": entry.ce},
            "metrics": costs["metrics"],
            "temp_bytes": costs["temp_bytes"],
            "boundary_threshold_elems": costs["threshold_elems"],
            "boundaries_total": len(costs["boundaries"]),
            "boundaries": costs["boundaries"][:_WORKLIST_PER_ENTRY],
        })
        for b in costs["boundaries"]:  # the FULL ranked worklist
            worklist.append(dict(b, entry=entry.key))
    worklist.sort(key=lambda b: (-b["bytes"], b["entry"],
                                 b["producer"]))
    return {
        "version": 1,
        "rules": ["SL601", "SL602", "SL603"],
        "platform": _platform(),
        "entries": sections,
        "fusion_worklist": worklist,
        "watermarks": watermarks,
        "budget_deltas": deltas or [],
        "host_sync": {
            "modules": list(DRIVER_MODULES),
            "active": [f.to_json() for f in sync_findings
                       if not f.suppressed],
            "allowed": [f.to_json() for f in sync_findings
                        if f.suppressed],
        },
        "summary": {
            "entries": len(sections),
            "budget_deltas": len(deltas or []),
            "worklist": len(worklist),
            "watermark_failures": sum(1 for w in watermarks
                                      if not w["ok"]),
            "host_sync_active": sum(1 for f in sync_findings
                                    if not f.suppressed),
        },
    }


def run_cost_pass(selected=frozenset({"SL601", "SL602", "SL603"}),
                  entries=None
                  ) -> tuple[list[Finding], list[dict], dict | None]:
    """SL6xx gate: returns (findings, budget deltas, report). The
    report is built whenever any compiled family ran (so the CI step's
    ``--cost-report`` artifact is free); a pure-SL603 selection skips
    every compile and returns report=None."""
    findings: list[Finding] = []
    deltas: list[dict] = []
    report = None
    compiled_rules = {"SL601", "SL602"} & set(selected)
    watermarks = sync_findings = None
    budget_findings = None
    if compiled_rules:
        entries = entries if entries is not None \
            else default_cost_entries()
        budget_findings, deltas = check_cost_budgets(entries=entries)
        findings.extend(budget_findings)
        wm_findings, watermarks = check_watermarks(entries)
        findings.extend(wm_findings)
    if "SL603" in selected:
        sync_findings = check_host_sync()
        findings.extend(sync_findings)
    if compiled_rules:
        # sync_findings=None (SL603 deselected) lets the report run its
        # own cheap AST scan — the artifact's host_sync section must
        # reflect the tree, not the selection
        report = build_cost_report(
            entries, budget_findings=budget_findings, deltas=deltas,
            watermarks=watermarks, sync_findings=sync_findings)
    return findings, deltas, report
