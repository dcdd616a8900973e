"""shadowlint pass 2: jaxpr audit of the jitted ``tpu/`` entry points.

Abstract-evals each registered kernel entry (small representative
shapes — the graph structure, primitives, and dtypes are shape-
independent) and walks the closed jaxpr plus every nested sub-jaxpr
(jit / while_loop / scan / cond / custom_* bodies) flagging:

- SL201: 64-bit dtypes anywhere in the graph (x64 leak).
- SL202: redundant ``convert_element_type`` chains — a convert whose
  input is itself a single-use convert output and whose composite is a
  dtype round-trip (a -> b -> a). This is the jaxpr signature of
  weak-type churn, the classic silent-recompile trigger.
- SL203: host-callback primitives in the graph.
- SL204: callbacks or device transfers inside a while_loop/scan body —
  one host hop per iteration.
- SL205: constants over a size threshold baked into the graph instead
  of passed as arguments.

The registry (`default_entries`) covers all five kernel modules:
``plane`` (window_step in both qdisc/AQM compile modes + chain_windows
in every presence-switch variant — plain/metrics/guards/workload — +
ingest_rows with all four observability planes threaded), ``tcp``
(event + pull + replay), ``transport`` (the DeviceTransport kernel
set), ``floweng`` (the fused window driver), and ``codel`` (trace
replay + integrated router). Entries carry per-rule allow-lists
with justifications — the pass-2 analogue of the source-comment
suppression syntax, since jaxpr findings have no line to anchor to.
The same registry feeds the SL502 op-budget census and the SL504
shardability report (``analysis/proofs.py``); the SL501 invisibility
proofs trace their own variant surface (`proofs.invisibility_specs`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from .rules import Finding

__all__ = [
    "AuditEntry",
    "audit_all",
    "audit_entry",
    "audit_jaxpr",
    "compiled",
    "default_entries",
    "ensemble_step_build",
    "traced",
    "traced_vmap",
    "vmap_build",
]

# constants above this many bytes should be kernel *arguments*
CONST_BYTES_LIMIT = 256 * 1024

_64BIT = ("float64", "int64", "uint64", "complex128")

# primitive names that cross the device<->host boundary
_CALLBACK_MARKERS = ("callback", "outside_call", "infeed", "outfeed",
                     "debug_print")
_TRANSFER_PRIMS = {"device_put", "convert_device_array", "copy_to_host"}


@dataclass
class AuditEntry:
    """One jitted entry point to audit.

    ``build`` returns a zero-argument trace thunk: a (fn, args) pair
    with every static argument already closed over, so the auditor just
    calls ``jax.make_jaxpr(fn)(*args)``.
    """

    name: str
    module: str
    build: Callable[[], tuple[Callable, tuple]]
    allow: dict[str, str] = field(default_factory=dict)


def _subjaxprs(value):
    """Yield (jaxpr, is_loop_body) for any jaxpr nested in an eqn param."""
    from jax.extend import core
    jaxpr_types = (core.Jaxpr, core.ClosedJaxpr)
    if isinstance(value, jaxpr_types):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _iter_eqns(jaxpr, in_loop: bool):
    """DFS over (eqn, in_loop) across every nested jaxpr."""
    raw = getattr(jaxpr, "jaxpr", jaxpr)  # peel ClosedJaxpr
    for eqn in raw.eqns:
        yield eqn, in_loop
        is_loop = eqn.primitive.name in ("while", "scan")
        for key, value in eqn.params.items():
            for sub in _subjaxprs(value):
                yield from _iter_eqns(sub, in_loop or is_loop)


def _consts_of(jaxpr):
    """(name, array) for every literal const across nested jaxprs."""
    raw = getattr(jaxpr, "jaxpr", jaxpr)
    for const in getattr(jaxpr, "consts", []):
        yield raw, const
    for eqn in raw.eqns:
        for value in eqn.params.values():
            for sub in _subjaxprs(value):
                yield from _consts_of(sub)


def audit_jaxpr(closed_jaxpr, where: str,
                const_bytes_limit: int = CONST_BYTES_LIMIT
                ) -> list[Finding]:
    """Walk one closed jaxpr (and every sub-jaxpr) for SL201-SL205."""
    findings: list[Finding] = []
    seen_64: set[str] = set()
    n_callbacks = 0
    n_loop_hops = 0

    # producer map for convert-chain detection is per-jaxpr; collect
    # convert eqns grouped by their owning jaxpr object id
    converts_by_jaxpr: dict[int, list] = {}

    def visit(jaxpr):
        raw = getattr(jaxpr, "jaxpr", jaxpr)
        converts = converts_by_jaxpr.setdefault(id(raw), [])
        for eqn in raw.eqns:
            if eqn.primitive.name == "convert_element_type":
                converts.append(eqn)
            for value in eqn.params.values():
                for sub in _subjaxprs(value):
                    visit(sub)

    visit(closed_jaxpr)

    for eqn, in_loop in _iter_eqns(closed_jaxpr, False):
        name = eqn.primitive.name
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
            if dtype in _64BIT and dtype not in seen_64:
                seen_64.add(dtype)
                findings.append(Finding(
                    "SL201", where, 0, 0,
                    f"{dtype} value in the device graph (primitive "
                    f"`{name}`); the plane contract is 32-bit"))
        if any(marker in name for marker in _CALLBACK_MARKERS):
            n_callbacks += 1
            if n_callbacks == 1:
                findings.append(Finding(
                    "SL203", where, 0, 0,
                    f"host callback primitive `{name}` in a jitted "
                    "kernel"))
        if in_loop and (name in _TRANSFER_PRIMS
                        or any(m in name for m in _CALLBACK_MARKERS)):
            n_loop_hops += 1
            if n_loop_hops == 1:
                findings.append(Finding(
                    "SL204", where, 0, 0,
                    f"host transfer/callback `{name}` inside a "
                    "while_loop/scan body: one sync per iteration"))

    # SL202: convert chains within one jaxpr. Map each convert's outvar
    # to its eqn; a convert consuming another convert's single-use
    # output where the composite is dtype-identity is redundant churn.
    for converts in converts_by_jaxpr.values():
        by_outvar = {id(eqn.outvars[0]): eqn for eqn in converts}
        for eqn in converts:
            src = eqn.invars[0]
            feeder = by_outvar.get(id(src))
            if feeder is None:
                continue
            d0 = str(feeder.invars[0].aval.dtype)
            d2 = str(eqn.outvars[0].aval.dtype)
            if d0 == d2:
                d1 = str(feeder.outvars[0].aval.dtype)
                findings.append(Finding(
                    "SL202", where, 0, 0,
                    f"convert_element_type round-trip {d0} -> {d1} -> "
                    f"{d2}: weak-type churn; pin the dtype at the "
                    "source"))

    for raw, const in _consts_of(closed_jaxpr):
        try:
            arr = np.asarray(const)
        except TypeError:
            # extended dtypes (PRNG keys) refuse conversion; size via the
            # aval instead
            arr = np.zeros(getattr(const, "shape", ()), np.uint32)
        if arr.nbytes > const_bytes_limit:
            findings.append(Finding(
                "SL205", where, 0, 0,
                f"{arr.nbytes} B constant ({arr.dtype}{list(arr.shape)}) "
                f"baked into the graph (limit {const_bytes_limit} B); "
                "pass it as a kernel argument"))

    return findings


#: the one shared per-process jaxpr cache, keyed "module:name". Every
#: traced analysis pass — the SL2xx audit, the SL501 invisibility
#: proofs, the SL502 census, the SL504 shard report, and the SL505/
#: SL506 provers — re-traces the same audited entries; hoisting one
#: memo here means a full shadowlint run (or the gating CI proof step)
#: traces each entry ONCE. Entry names are stable per process; callers
#: passing ad-hoc entries must give distinct names. Values are
#: (closed_jaxpr, out_shape, args, fn) — the build thunk's fn rides
#: along so `compiled` below can lower the SAME entry without
#: re-running the builder.
_TRACE_CACHE: dict[str, tuple] = {}

#: the compiled-artifact memo on top of the trace cache, keyed
#: (trace_key, platform): the SL6xx cost fences (analysis/costmodel.py)
#: pull XLA cost_analysis(), memory_analysis(), and the optimized HLO
#: text off each audited entry — one lower+compile per entry per
#: platform, shared across SL601 (cost budgets), SL602 (fusion
#: boundaries), and the watermark extrapolation.
_COMPILE_CACHE: dict[tuple[str, str], object] = {}


def traced(key: str, build):
    """(closed_jaxpr, out_shape, args) for one audited entry,
    memoized across every analysis pass."""
    hit = _TRACE_CACHE.get(key)
    if hit is None:
        import jax

        fn, args = build()
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        hit = (closed, out_shape, args, fn)
        _TRACE_CACHE[key] = hit
    return hit[:3]


def vmap_build(build, w: int):
    """Wrap an audit-entry build thunk into its W-world vmapped
    variant: every argument leaf is tree-stacked along a new leading
    world axis and the entry fn is wrapped in ``jax.vmap``. Closed-over
    data (params tables, the RNG root) stays SHARED across worlds — it
    lands in the batched jaxpr as constvars, which is exactly the
    world-free/world-batched split the SL701 axis-provenance walk
    (analysis/batchdim.py) starts from."""
    def vbuild():
        import jax
        import jax.numpy as jnp

        fn, args = build()
        vargs = jax.tree.map(lambda x: jnp.stack([x] * w), args)
        return jax.vmap(fn), vargs

    return vbuild


def traced_vmap(key: str, build, w: int):
    """The ``@vmapW{w}`` trace-cache variant of one audited entry:
    (closed_jaxpr, out_shape, args) of the entry vmapped over ``w``
    stacked worlds, memoized in the SAME per-process cache as the solo
    trace — so the SL701/SL703 batch pass and the SL601 ensemble
    watermark twins each trace a given (entry, world-count) once."""
    return traced(f"{key}@vmapW{w}", vmap_build(build, w))


def ensemble_step_build(w: int, n: int = 4):
    """The ensemble consumer at W worlds — the step
    ``tpu/elastic.drive_ensemble`` vmaps: one loss-enabled
    ``window_step`` per world with PER-WORLD fold_in keys, shifts, and
    windows batched along the leading world axis while the params
    tables stay shared. This is the entry whose SL701 proof covers the
    batched-RNG path (per-world threefry keys), and the one the SL601
    W=2/W=4 watermark twins fence for super-linear ensemble memory."""
    def build():
        import jax
        import jax.numpy as jnp

        from ..tpu import elastic, plane

        params = plane.make_params(
            latency_ns=np.full((n, n), 1_000_000, np.int64),
            loss=np.full((n, n), 0.01, np.float64),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        root = jax.random.key(0)
        keys = elastic.world_keys(root, jnp.arange(w, dtype=jnp.int32))
        states = jax.tree.map(lambda x: jnp.stack([x] * w), state)

        def step(state, key, shift, window):
            return plane.window_step(state, params, key, shift, window,
                                     rr_enabled=False)

        return jax.vmap(step), (states, keys,
                                jnp.zeros((w,), jnp.int32),
                                jnp.full((w,), 10_000_000, jnp.int32))

    return build


def compiled(key: str, build):
    """The compiled XLA executable for one audited entry, memoized
    per (trace_key, platform). Populates/shares the jaxpr trace memo,
    then lowers through ``jit(fn).lower(*args).compile()`` exactly
    once — so a full SL6xx pass (cost budgets + fusion census +
    watermark extrapolation) compiles each registered entry once, not
    once per rule family."""
    import jax

    platform = jax.devices()[0].platform
    cache_key = (key, platform)
    hit = _COMPILE_CACHE.get(cache_key)
    if hit is None:
        traced(key, build)  # one builder call, shared with every pass
        _closed, _shape, args, fn = _TRACE_CACHE[key]
        hit = jax.jit(fn).lower(*args).compile()
        _COMPILE_CACHE[cache_key] = hit
    return hit


def audit_entry(entry: AuditEntry) -> list[Finding]:
    closed, _shape, _args = traced(f"{entry.module}:{entry.name}",
                                   entry.build)
    findings = audit_jaxpr(closed, f"{entry.module}:{entry.name}")
    for f in findings:
        just = entry.allow.get(f.rule)
        if just:
            f.suppressed = True
            f.justification = just
    return findings


# ---------------------------------------------------------------------------
# entry registry: all five tpu kernel modules at small shapes
# ---------------------------------------------------------------------------

class _StubHost:
    def __init__(self, host_id: int, node_id: int):
        self.host_id = host_id
        self.node_id = node_id


class _StubRouting:
    """Minimal RoutingInfo twin for DeviceTransport's constructor."""

    def __init__(self, n_nodes: int):
        self.latency_ns = np.full((n_nodes, n_nodes), 1_000_000, np.int64)
        np.fill_diagonal(self.latency_ns, 5_000)

    def node_index(self, node_id: int) -> int:
        return int(node_id)


def _plane_entry(rr_enabled: bool, router_aqm: bool, no_loss: bool,
                 packed_sort: bool = True, telemetry: bool = False, faults: bool = False,
                 guards: bool = False, trace: bool = False,
                 n: int = 4):
    def build():
        import jax
        import jax.numpy as jnp

        from ..faults.plane import neutral_faults
        from ..guards.plane import make_guards
        from ..telemetry import make_flightrec, make_histograms, \
            make_metrics
        from ..tpu import plane

        m = 3
        params = plane.make_params(
            latency_ns=np.full((m, m), 1_000_000, np.int64),
            loss=np.full((m, m), 0.0 if no_loss else 0.01, np.float64),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
            qdisc_rr=np.array([True, False] * (n // 2)),
            down_bw_bps=np.full(n, 1_000_000_000, np.int64),
            host_node=np.arange(n, dtype=np.int32) % m,
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        root = jax.random.key(0)

        if faults:
            def fn(state, fault_arrays, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=rr_enabled, router_aqm=router_aqm,
                    no_loss=no_loss, packed_sort=packed_sort,
                    faults=fault_arrays)

            return fn, (state, neutral_faults(n, m), jnp.int32(0),
                        jnp.int32(10_000_000))

        if telemetry:
            def fn(state, metrics, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=rr_enabled, router_aqm=router_aqm,
                    no_loss=no_loss, packed_sort=packed_sort,
                    metrics=metrics)

            return fn, (state, make_metrics(n), jnp.int32(0),
                        jnp.int32(10_000_000))

        if trace:
            def fn(state, hist, flightrec, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=rr_enabled, router_aqm=router_aqm,
                    no_loss=no_loss, packed_sort=packed_sort,
                    hist=hist, flightrec=flightrec)

            return fn, (state, make_histograms(n),
                        make_flightrec(0, sample_every=4, ring=64),
                        jnp.int32(0), jnp.int32(10_000_000))

        if guards:
            def fn(state, guard_state, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=rr_enabled, router_aqm=router_aqm,
                    no_loss=no_loss, packed_sort=packed_sort,
                    guards=guard_state)

            return fn, (state, make_guards(n), jnp.int32(0),
                        jnp.int32(10_000_000))

        def fn(state, shift, window):
            return plane.window_step(
                state, params, root, shift, window,
                rr_enabled=rr_enabled, router_aqm=router_aqm,
                no_loss=no_loss, packed_sort=packed_sort)

        return fn, (state, jnp.int32(0), jnp.int32(10_000_000))

    return build


def _routing_entry(stage: str):
    """The routing-stage split (plane section 5): `routing_rank` audits
    the bucketed-order computation (row seq-rank + the diet flat sort
    that carries the payload + bucket bounds), `routing_place` the one
    stacked placement gather — the same split the per-section profiler
    times."""
    def build():
        import jax.numpy as jnp

        from ..tpu import plane

        n, ce, ci = 4, 8, 8
        rng = np.random.default_rng(0)
        sent = jnp.asarray(rng.integers(0, 2, (n, ce)) == 0)
        eg_dst = jnp.asarray(rng.integers(0, n, (n, ce)), jnp.int32)
        eg_seq = jnp.asarray(rng.integers(0, 100, (n, ce)), jnp.int32)
        eg_bytes = jnp.full((n, ce), 1400, jnp.int32)
        eg_sock = jnp.zeros((n, ce), jnp.int32)
        deliver = jnp.asarray(
            rng.integers(0, 10**6, (n, ce)), jnp.int32)
        n_valid = jnp.zeros((n,), jnp.int32)
        if stage == "rank":
            def fn(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver,
                   n_valid):
                return plane._routing_rank(
                    sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver,
                    n_valid, ci)

            return fn, (sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver,
                        n_valid)
        streams, offsets, take_n, _ovf = plane._routing_rank(
            sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver, n_valid, ci)
        z = lambda: jnp.zeros((n, ci), jnp.int32)
        return plane._routing_place, (
            streams, offsets, take_n, n_valid, z(), z(), z(), z(), z(),
            jnp.zeros((n, ci), bool))

    return build


def _chain_entry(variant: str = "plain", n: int = 4):
    """`chain_windows` in each presence-switch compile mode: the chain
    is THE device-resident driver loop, so every pytree that can ride
    its while_loop carry (metrics / guards / the workload generator)
    gets its own audited trace — a host sync smuggled into any carry
    variant fails SL204 here, not in production."""
    def build():
        import jax
        import jax.numpy as jnp

        from ..guards.plane import make_guards
        from ..telemetry import make_metrics
        from ..tpu import plane
        params = plane.make_params(
            latency_ns=np.full((n, n), 1_000_000, np.int64),
            loss=np.zeros((n, n)),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        root = jax.random.key(0)

        def chain(state, shift0, horizon, **kw):
            return plane.chain_windows(
                state, params, root, shift0, jnp.int32(1_000_000),
                jnp.int32(1_000_000), horizon, horizon,
                rr_enabled=False, no_loss=True, **kw)

        args = (state, jnp.int32(0), jnp.int32(50_000_000))
        if variant == "metrics":
            def fn(state, metrics, shift0, horizon):
                return chain(state, shift0, horizon, metrics=metrics)

            return fn, (args[0], make_metrics(n), *args[1:])
        if variant == "guards":
            def fn(state, guards, shift0, horizon):
                return chain(state, shift0, horizon, guards=guards)

            return fn, (args[0], make_guards(n), *args[1:])
        if variant == "flows":
            from ..tpu import flows as flows_mod

            ft = flows_mod.make_flow_tables(
                np.arange(n, dtype=np.int32),
                (np.arange(n, dtype=np.int32) + 1) % n,
                np.full(n, 1400, np.int32))

            def fn(state, fs, shift0, horizon):
                return chain(state, shift0, horizon, flows=(ft, fs))

            return fn, (args[0], flows_mod.make_flow_state(n),
                        *args[1:])
        if variant == "compute":
            from ..tpu import compute as compute_mod

            ct = compute_mod.make_compute_tables(
                np.full((n, 1), 25_000, np.int32), queue_cap=16)

            def fn(state, cs, shift0, horizon):
                return chain(state, shift0, horizon, compute=(ct, cs))

            return fn, (args[0], compute_mod.make_compute_state(ct),
                        *args[1:])
        if variant == "workload":
            from ..workloads import compile_program, parse_scenario
            from ..workloads import device as wdevice

            prog = compile_program(parse_scenario({
                "name": "audit-onoff", "hosts": n, "egress_cap": 8,
                "ingress_cap": 8, "windows": 4,
                "patterns": [{"kind": "onoff", "burst": 1, "rounds": 2,
                              "gap_ns": 200_000,
                              "off_mean_ns": 2_000_000}],
            }))
            wl = wdevice.to_device(prog)

            def fn(state, ws, shift0, horizon):
                return chain(state, shift0, horizon, workload=(wl, ws))

            return fn, (args[0], wdevice.make_workload_state(prog),
                        *args[1:])

        def fn(state, shift0, horizon):
            return chain(state, shift0, horizon)

        return fn, args

    return build


def _ingest_rows_entry():
    """`ingest_rows` with all four observability planes threaded — the
    third kernel of the SL501 proof surface gets the SL2xx audit too
    (widest compile mode: every plane section in the graph)."""
    def build():
        import jax.numpy as jnp

        from ..guards.plane import make_guards
        from ..telemetry import make_flightrec, make_histograms, \
            make_metrics
        from ..tpu import plane

        n, k = 4, 4
        params = plane.make_params(
            latency_ns=np.full((n, n), 1_000_000, np.int64),
            loss=np.zeros((n, n)),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        z = lambda: jnp.zeros((n, k), jnp.int32)

        def fn(state, metrics, guards, hist, flightrec, dst, nbytes,
               prio, seq, valid):
            return plane.ingest_rows(
                state, dst, nbytes, prio, seq,
                jnp.zeros((n, k), bool), valid,
                metrics=metrics, guards=guards, hist=hist,
                flightrec=flightrec)

        return fn, (state, make_metrics(n), make_guards(n),
                    make_histograms(n),
                    make_flightrec(0, sample_every=4, ring=64),
                    z(), z(), z(), z(), jnp.zeros((n, k), bool))

    return build


def _flows_entry(kind: str):
    """The flow plane (docs/robustness.md "Flow plane"): the
    flows-threaded window_step variant plus the standalone flow_step
    composition — both SL2xx-audited and, for the window_step variant,
    the SL501 append-only proof subject (`analysis/proofs.py`)."""
    def build():
        import jax
        import jax.numpy as jnp

        from ..tpu import flows as flows_mod
        from ..tpu import plane

        n = 4
        params = plane.make_params(
            latency_ns=np.full((n, n), 1_000_000, np.int64),
            loss=np.full((n, n), 0.01, np.float64),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        root = jax.random.key(0)
        ft = flows_mod.make_flow_tables(
            np.arange(n, dtype=np.int32),
            (np.arange(n, dtype=np.int32) + 1) % n,
            np.full(n, 1400, np.int32))
        fs = flows_mod.make_flow_state(n)
        if kind == "window":
            def fn(state, fs, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=False, flows=(ft, fs))

            return fn, (state, fs, jnp.int32(0),
                        jnp.int32(10_000_000))
        ci = state.in_src.shape[1]
        delivered = {
            "mask": jnp.zeros((n, ci), bool),
            "src": jnp.zeros((n, ci), jnp.int32),
            "seq": jnp.zeros((n, ci), jnp.int32),
            "sock": jnp.zeros((n, ci), jnp.int32),
            "bytes": jnp.zeros((n, ci), jnp.int32),
            "deliver_rel": jnp.zeros((n, ci), jnp.int32),
        }

        def fn(ft_arrays, fs, state, delivered):
            return flows_mod.flow_step(ft_arrays, fs, state, delivered,
                                       jnp.int32(10_000_000))

        return fn, (ft, fs, state, delivered)

    return build


def _compute_entry(kind: str):
    """The device compute plane (docs/workloads.md "Serving load & the
    compute plane"): the compute-threaded window_step variant plus the
    standalone compute_step FIFO kernel — both SL2xx-audited and, for
    the window_step variant, the SL501 FULL-invisibility proof subject
    (`analysis/proofs.py`): compute taint may reach only the appended
    ComputeState output, never state / delivered / next_event."""
    def build():
        import jax
        import jax.numpy as jnp

        from ..tpu import compute as compute_mod
        from ..tpu import plane

        n = 4
        params = plane.make_params(
            latency_ns=np.full((n, n), 1_000_000, np.int64),
            loss=np.zeros((n, n)),
            up_bw_bps=np.full(n, 1_000_000_000, np.int64),
        )
        state = plane.make_state(n, egress_cap=8, ingress_cap=8,
                                 params=params)
        root = jax.random.key(0)
        ct = compute_mod.make_compute_tables(
            np.full((n, 1), 25_000, np.int32), queue_cap=16)
        cs = compute_mod.make_compute_state(ct)
        if kind == "window":
            def fn(state, cs, shift, window):
                return plane.window_step(
                    state, params, root, shift, window,
                    rr_enabled=False, compute=(ct, cs))

            return fn, (state, cs, jnp.int32(0),
                        jnp.int32(10_000_000))
        ci = state.in_src.shape[1]
        delivered = {
            "mask": jnp.zeros((n, ci), bool),
            "src": jnp.zeros((n, ci), jnp.int32),
            "seq": jnp.zeros((n, ci), jnp.int32),
            "sock": jnp.zeros((n, ci), jnp.int32),
            "bytes": jnp.zeros((n, ci), jnp.int32),
            "deliver_rel": jnp.zeros((n, ci), jnp.int32),
        }

        def fn(ct_arrays, cs, delivered):
            return compute_mod.compute_step(
                ct_arrays, cs, delivered, jnp.int32(0),
                jnp.int32(10_000_000))

        return fn, (ct, cs, delivered)

    return build


def _tcp_entry(kind: str):
    def build():
        import jax.numpy as jnp

        from ..tpu import tcp as dtcp

        c = 4
        plane = dtcp.make_tcp_plane(c, reass_slots=8)
        if kind == "event":
            fn = dtcp.tcp_event_step
            args = (plane, jnp.zeros((c,), jnp.int32),
                    jnp.zeros((c, dtcp.N_FIELDS), jnp.int32),
                    jnp.zeros((c,), jnp.int32))
        elif kind == "pull":
            fn = dtcp.tcp_pull_step
            args = (plane, jnp.zeros((c,), jnp.int32))
        else:  # replay
            t = 3
            fn = dtcp.tcp_replay
            args = (plane, jnp.zeros((c, t), jnp.int32),
                    jnp.zeros((c, t, dtcp.N_FIELDS), jnp.int32),
                    jnp.zeros((c, t), jnp.int32))
        return fn, args

    return build


def _transport_entry(kernel: str):
    def build():
        import jax.numpy as jnp

        from ..tpu.transport import DeviceTransport

        n = 4
        dt = DeviceTransport(
            [_StubHost(i + 1, i % 3) for i in range(n)],
            _StubRouting(3), None, egress_cap=8, ingress_cap=8,
            mode="sync", compact_cap=16)
        # audit the GUARDED + HISTOGRAMMED variants: guard checks and
        # histogram adds are part of the kernel surface whenever the
        # planes are enabled, and the disabled traces are strict
        # subsets (g=None / h=None compile them out)
        dt.enable_guards()
        dt.enable_histograms()
        st, g, h = dt.state, dt._guard, dt._hist
        if kernel == "ingest":
            b = 8
            z = lambda: jnp.zeros((b,), jnp.int32)
            args = (st, g, h, z(), z(), z(), z(), z(), z(),
                    jnp.zeros((b,), bool))
            return dt._k_ingest, args
        if kernel == "step":
            return dt._k_step, (st, g, h, jnp.int32(0),
                                jnp.int32(1_000_000))
        if kernel == "chain":
            i32 = jnp.int32
            return dt._k_chain, (st, g, h, i32(0), i32(1_000_000),
                                 i32(1_000_000), i32(50_000_000),
                                 i32(50_000_000))
        # batch_verify: K windows of B ingest rows
        k, b = 4, 8
        zk = lambda: jnp.zeros((k,), jnp.int32)
        row = {key: jnp.zeros((k, b), jnp.int32)
               for key in ("src", "dst", "seq", "tag", "send", "clamp")}
        row["valid"] = jnp.zeros((k, b), bool)
        args = (st, g, h, zk(), zk(), row, jnp.zeros((k,), jnp.uint32),
                jnp.zeros((k,), jnp.uint32), zk(), jnp.int32(0))
        return dt._k_batch_verify, args

    return build


def _floweng_entry():
    def build():
        import functools

        from ..tpu import floweng

        world = floweng.make_flow_world(
            latency_us=np.full(4, 1000, np.int64),
            size_bytes=np.full(4, 65536, np.int64),
            queue_slots=16, loss=0.01)
        fn = functools.partial(
            floweng.run_windows, n_windows=2, window_us=1000,
            max_events_per_window=8, ack_every=2, sched_batch=2,
            pull_cap=2, gso_segs=4)
        return fn, (world,)

    return build


def _codel_entry(kernel: str):
    def build():
        import jax.numpy as jnp

        from ..tpu import codel

        n, k = 4, 8
        arrival = jnp.full((n, k), codel.I32_MAX, jnp.int32)
        size = jnp.zeros((n, k), jnp.int32)
        if kernel == "codel_drain":
            pops = jnp.full((n, k), codel.I32_MAX, jnp.int32)
            st = codel.make_codel_state(n)
            return codel.codel_drain, (arrival, size, pops, st)
        st = codel.make_router_state(n)
        rate = jnp.full((n,), 125_000, jnp.int32)
        cap = rate + 1500

        def fn(arrival, size, rate, cap, st):
            return codel.router_drain(
                arrival, size, jnp.int32(10_000_000), rate, cap, st)

        return fn, (arrival, size, rate, cap, st)

    return build


def default_entries() -> list[AuditEntry]:
    """The audited kernel surface: every jitted entry point of the five
    tpu/ modules at small representative shapes."""
    entries = [
        AuditEntry("window_step[rr,aqm,loss]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False)),
        AuditEntry("window_step[lean]", "shadow_tpu.tpu.plane",
                   _plane_entry(False, False, True)),
        AuditEntry("window_step[legacy-sort]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False, packed_sort=False)),
        AuditEntry("window_step[telemetry]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False, telemetry=True)),
        AuditEntry("window_step[faults]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False, faults=True)),
        AuditEntry("window_step[guards]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False, guards=True)),
        AuditEntry("window_step[trace]", "shadow_tpu.tpu.plane",
                   _plane_entry(True, True, False, trace=True)),
        AuditEntry("routing_rank", "shadow_tpu.tpu.plane",
                   _routing_entry("rank")),
        AuditEntry("routing_place", "shadow_tpu.tpu.plane",
                   _routing_entry("place")),
        AuditEntry("chain_windows", "shadow_tpu.tpu.plane",
                   _chain_entry()),
        AuditEntry("chain_windows[metrics]", "shadow_tpu.tpu.plane",
                   _chain_entry("metrics")),
        AuditEntry("chain_windows[guards]", "shadow_tpu.tpu.plane",
                   _chain_entry("guards")),
        AuditEntry("chain_windows[workload]", "shadow_tpu.tpu.plane",
                   _chain_entry("workload")),
        AuditEntry("chain_windows[flows]", "shadow_tpu.tpu.plane",
                   _chain_entry("flows")),
        AuditEntry("ingest_rows[planes]", "shadow_tpu.tpu.plane",
                   _ingest_rows_entry()),
        AuditEntry("window_step[flows]", "shadow_tpu.tpu.plane",
                   _flows_entry("window")),
        AuditEntry("flow_step", "shadow_tpu.tpu.flows",
                   _flows_entry("step")),
        AuditEntry("chain_windows[compute]", "shadow_tpu.tpu.plane",
                   _chain_entry("compute")),
        AuditEntry("window_step[compute]", "shadow_tpu.tpu.plane",
                   _compute_entry("window")),
        AuditEntry("compute_step", "shadow_tpu.tpu.compute",
                   _compute_entry("step")),
        AuditEntry("tcp_event_step", "shadow_tpu.tpu.tcp",
                   _tcp_entry("event")),
        AuditEntry("tcp_pull_step", "shadow_tpu.tpu.tcp",
                   _tcp_entry("pull")),
        AuditEntry("tcp_replay", "shadow_tpu.tpu.tcp",
                   _tcp_entry("replay")),
        AuditEntry("ingest", "shadow_tpu.tpu.transport",
                   _transport_entry("ingest")),
        AuditEntry("step_compact", "shadow_tpu.tpu.transport",
                   _transport_entry("step")),
        AuditEntry("chain", "shadow_tpu.tpu.transport",
                   _transport_entry("chain")),
        AuditEntry("batch_verify", "shadow_tpu.tpu.transport",
                   _transport_entry("verify")),
        AuditEntry("run_windows", "shadow_tpu.tpu.floweng",
                   _floweng_entry()),
        AuditEntry("codel_drain", "shadow_tpu.tpu.codel",
                   _codel_entry("codel_drain")),
        AuditEntry("router_drain", "shadow_tpu.tpu.codel",
                   _codel_entry("router_drain")),
    ]
    return entries


def audit_all(entries: list[AuditEntry] | None = None) -> list[Finding]:
    out: list[Finding] = []
    for entry in entries if entries is not None else default_entries():
        out.extend(audit_entry(entry))
    return out
