"""Driver for tgen flow-engine configurations: the YAML, whole runs, the
check against the per-flow reference.

The deployment (graph, hosts, general settings) comes from the config,
the traffic file and `--seed` through the reference's `deployment`; the
driver writes it as the YAML a user would give `shadow_tpu` (the graph
inline as GML, `experimental.use_flow_engine: true`) and runs it on the
program's normal path: the YAML parsed into `ConfigOptions`, then
`Manager(config).run()`, which hands a flow-engine config to
`core/flowplan.run_flow_simulation`.

Set-up parses the YAML and makes one whole warm run, which compiles (or
loads) every program the runs use. The timed window makes whole runs
back to back, each a fresh `Manager`, until `--seconds` have passed, at
least one. Under a profiler trace (`--trace 1`) it makes one run: a run
is millions of small device ops, and every per-layer metric is per run.
The check runs the reference over the last run's flows and compares
flow for flow."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PER_FLOW = {"complete_us": "flow_complete_us",
            "bytes_read": "flow_bytes_read",
            "seg_units": "flow_segments",
            "wire_drops": "flow_wire_drops",
            "retransmits": "flow_retransmits_by_flow"}


def _load_reference(name: str):
    path = os.path.join(HERE, os.pardir, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def yaml_text(dep: dict) -> str:
    """The deployment as a `shadow_tpu` YAML config."""
    bw = dep["bandwidth"]
    gml = ["graph [", f"  directed {int(dep['directed'])}"]
    gml += [f"  node [ id {i} host_bandwidth_up \"{bw}\""
            f" host_bandwidth_down \"{bw}\" ]"
            for i in range(dep["n_nodes"])]
    gml += [f"  edge [ source {i} target {j} latency \"{lat} ms\""
            f" packet_loss {loss} ]" for i, j, lat, loss in dep["edges"]]
    gml.append("]")
    hosts = []
    for h in dep["hosts"]:
        if h["server"] is None:
            proc = (f"{{path: tgen-server, args: ['8888'], start_time: "
                    f"{dep['server_start_s']}s,\n"
                    f"       expected_final_state: running}}")
        else:
            proc = (f"{{path: tgen-client, args: ['{h['server']}', "
                    f"'8888', '{h['size']}', '{h['count']}'], start_time: "
                    f"{h['start_s']}s}}")
        hosts.append(f"  {h['name']}:\n    network_node_id: {h['node']}\n"
                     f"    processes:\n    - {proc}")
    return (f"general: {{stop_time: {dep['stop_s']}s, seed: {dep['seed']}}}\n"
            "experimental: {use_flow_engine: true}\n"
            "network:\n  graph:\n    type: gml\n    inline: |\n"
            + "\n".join("      " + ln for ln in gml)
            + "\nhosts:\n" + "\n".join(hosts) + "\n")


def program_output(stats) -> dict:
    """What the check compares, from one run's `SimStats`."""
    out = {k: np.asarray(getattr(stats, a), np.int64)
           for k, a in PER_FLOW.items()}
    out.update(packets_sent=int(stats.packets_sent),
               packets_dropped=int(stats.packets_dropped),
               failures=len(stats.process_failures),
               sim_time_ns=int(stats.sim_time_ns))
    return out


def tracing_now() -> bool:
    """Whether a `jax.profiler` trace is being collected. JAX has no
    public query, so this reads the profiler's session where JAX keeps
    it, and raises where this JAX keeps none: a traced window has to
    make one run, since a run is millions of device op events."""
    from jax._src import profiler

    state = getattr(profiler, "_profile_state", None)
    if not hasattr(state, "profile_session"):
        raise RuntimeError(
            "jax._src.profiler._profile_state.profile_session is not "
            "there: the tgen driver cannot tell a traced window from "
            "an untraced one")
    return state.profile_session is not None


def make_reference(ref, cfg: dict, dep: dict, control: str | None = None):
    """The reference module's `TgenReference` for a deployment, with
    the engine settings the config states."""
    eng = cfg["engine"]
    return ref.TgenReference(
        dep, queue_slots=eng["queue_slots"],
        max_doublings=eng["max_doublings"],
        opts={k: eng[k] for k in ("ack_every", "gso_segs", "sched_batch",
                                  "pull_cap")},
        control=control)


class Run:
    """One run of a tgen flow-engine cell."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        from shadow_tpu.core.manager import SimStats

        fields = {f.name for f in dataclasses.fields(SimStats)}
        missing = sorted(set(PER_FLOW.values()) - fields)
        if missing:
            # the check compares flow for flow: a program that reports no
            # per-flow counters cannot run this cell
            raise ImportError(f"SimStats has no {', '.join(missing)}")
        self.cfg, self.traffic, self.seed, self.span = cfg, traffic, seed, span
        self.ref = _load_reference(cfg["reference"])
        self.dep = self.ref.deployment(cfg, traffic, seed)
        self.text = yaml_text(self.dep)
        self.stats = self.rec = None

    def _one_run(self):
        from shadow_tpu.core.manager import Manager

        with self.span("bench.run"):
            return Manager(self.config).run()

    def setup(self):
        from shadow_tpu.core.config import load_config_str

        t0 = time.monotonic()
        with self.span("setup.config"):
            self.config = load_config_str(self.text)
        t1 = time.monotonic()
        # the warm run compiles (or loads) each bucket's window program,
        # the probe and the eager ops; every later run reuses them
        with self.span("setup.first_run"):
            self.stats = self._one_run()
        self.setup_phases = {"config_s": t1 - t0,
                             "first_run_s": time.monotonic() - t1}

    def window(self, seconds: float) -> dict:
        """Whole runs back to back until `seconds` have passed, at least
        one (one under a profiler trace); each ends when its results are
        reconciled on the host."""
        runs, failed, events = 0, 0, 0
        one = tracing_now()
        t0 = time.monotonic()
        while True:
            self.stats = self._one_run()
            runs += 1
            failed += len(self.stats.process_failures)
            events += self.stats.events_executed
            if one or time.monotonic() - t0 >= seconds:
                break
        t1 = time.monotonic()
        n_flows = len(self.stats.flow_complete_us)
        counters = self.stats.flow_counters or {}
        rec = {
            "wall_s": t1 - t0,
            "sim_s": runs * self.dep["stop_s"],
            "runs": runs,
            "attempted": runs * n_flows,
            "failed": failed,
            "events": events,
            "run_wall_s": self.stats.wall_seconds,
        }
        rec.update({f"{k}_per_run": v for k, v in counters.items()})
        self.rec = rec
        return rec

    def output(self) -> dict:
        return program_output(self.stats)

    def free(self):
        self.stats = None

    # -- the comparison ----------------------------------------------------
    def check(self, got: dict, control: str | None = None) -> dict:
        """Run the plain reference over every flow and compare. Returns
        {name: (value, limit)}."""
        ref = make_reference(self.ref, self.cfg, self.dep, control)
        want = ref.run()
        sizes = [f["size"] for f in ref.flows]
        found = self.ref.compare(got, want, sizes)
        if self.rec is not None:
            # each bucket's last completion, for floweng.hbm_roofline
            done = want["complete_us"]
            self.rec["buckets"] = [
                {"window_us": w, "flows": len(idx),
                 "last_complete_us": int(max(
                     (done[f] for f in idx if done[f] < self.ref.I32_MAX),
                     default=0))}
                for w, idx in sorted(ref.buckets().items())]
        limits = self.cfg["limits"]
        return {k: (v, limits[k]) for k, v in found.items()}
