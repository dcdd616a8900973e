#!/usr/bin/env python3
"""Bring-up proof on one TPU: the system's main paths, once each,
through the entry points a user calls, checked against the repo's own
references. Run it on the chip (`python chip_smoke.py`); it is the
quickest proof that the program still starts there.

Everything runs in ONE process — the chip belongs to one process at a
time, and nothing here spawns a child that touches JAX. Phases, in
order, each printing its check and its wall/compile seconds:

- corpus: the scenario corpus through `tools/run_scenarios.py --check`;
  every canonical digest must equal `scenarios/GOLDEN.json` (recorded
  on the CPU).
- phold-32k: bench.py's PHOLD chain at its recorded shape (32,768 hosts,
  64-node graph, egress/ingress caps 16/32, 192 windows) through
  `elastic.drive_chained_windows`; the first 16 windows must equal the
  same windows run on the CPU device.
- tgen-atlas-1k-floweng: bench_ladder rung 3f through `cli.main`; every
  transfer completes, and the per-flow completion digest equals the one
  the same config gives under JAX_PLATFORMS=cpu (`FLOWENG_CPU_DIGEST`,
  recorded with `--record-cpu-digest`).
- tgen-mesh-100-transport: bench_ladder rung 2 through `cli.main` on the
  device transport (sync) and on the CPU transport; the sim-stats.json
  files must be equal apart from wall-clock fields.
- native-pairs: 20 pairs of the compiled TCP server/client under the
  seccomp+preload shim on the device transport (sync); every process
  must end in its expected state. The native libraries are rebuilt from
  the committed sources first.

Exits non-zero, with no `ok` line, when JAX finds no TPU, when a phase
raises, or when a comparison differs. The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".smoke_data")  # data dirs (gitignored)
SUMMARY = os.path.join(REPO, "chiprun_out", "chip_smoke.json")

# sha256 over rung 3f's per-flow completion times (int64, plan order)
# under JAX_PLATFORMS=cpu; recorded with `--record-cpu-digest` (PR 21)
FLOWENG_CPU_DIGEST = (
    "a47aa38c909ca050423397edc1724f751f463ef747076eb29c309d8bb1c395e9")

PHOLD = dict(n_hosts=32_768, n_nodes=64, egress_cap=16, ingress_cap=32,
             rounds=192, cpu_rounds=16)
N_PAIRS = 20
PAIR_BYTES = 262_144
DEVICE_TRANSPORT = "use_tpu_transport: true, tpu_transport_mode: sync"

_compile_s = [0.0]
_results: dict = {}


class Mismatch(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Mismatch(what)


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def phase(name: str, fn) -> None:
    """Run one phase; print its check line(s) and its wall/compile s."""
    c0, t0 = _compile_s[0], time.monotonic()
    info = fn()
    wall, comp = time.monotonic() - t0, _compile_s[0] - c0
    _results[name] = dict(info, wall_s=wall, compile_s=comp)
    print(f"[{name}] passed: wall {wall:.1f} s, compile {comp:.1f} s",
          flush=True)


def _cli(cfg_text: str, name: str) -> str:
    """Run one config through the user's entry point; returns its data
    directory."""
    from shadow_tpu import cli

    d = os.path.join(WORK, name)
    os.makedirs(WORK, exist_ok=True)
    cfg = os.path.join(WORK, f"{name}.yaml")
    with open(cfg, "w") as fh:
        fh.write(cfg_text)
    rc = cli.main([cfg, "-d", d, "--force", "--log-level", "warning"])
    check(rc == 0, f"{name}: shadow_tpu exited {rc}")
    return d


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def corpus() -> dict:
    import run_scenarios

    rc = run_scenarios.main(["--check", "-o",
                             os.path.join(WORK, "scenarios.json")])
    check(rc == 0, "corpus: digests differ from scenarios/GOLDEN.json")
    with open(run_scenarios.GOLDEN) as fh:
        n = len(json.load(fh))
    print(f"[corpus] {n} scenario digests equal scenarios/GOLDEN.json",
          flush=True)
    return {"scenarios": n}


def _phold(rounds: int, device=None):
    """One PHOLD run from a fresh world: (digest, delivered, compile s,
    run s). On `device` when given (the CPU twin), else the default."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from bench import phold_chain
    from shadow_tpu.tpu import elastic, profiling
    from shadow_tpu.workloads.runner import digest_pytrees

    ctx = (jax.default_device(device) if device is not None
           else contextlib.nullcontext())
    with ctx:
        world = profiling.build_world(
            PHOLD["n_hosts"], n_nodes=PHOLD["n_nodes"],
            egress_cap=PHOLD["egress_cap"], ingress_cap=PHOLD["ingress_cap"],
            seed=0, warmup_windows=0)
        state = world["state"]
        spawn = jnp.full((PHOLD["n_hosts"],), 10_000, jnp.int32)
        rids = jnp.arange(rounds, dtype=jnp.int32)
        t0 = time.monotonic()
        chain = phold_chain(world).lower(
            state, spawn, None, None, rids).compile()
        compile_s = time.monotonic() - t0

        def chain_fn(state, extras, rids, _per_round):
            spawn, total = extras
            state, spawn, _m, _h, eg, inn, nd = chain(state, spawn, None,
                                                      None, rids)
            return state, (spawn, total + nd), eg, inn

        t0 = time.monotonic()
        state, (spawn, total) = elastic.drive_chained_windows(
            state, (spawn, jnp.int32(0)), chain_fn, n_rounds=rounds,
            chain_len=rounds, window_ns=int(world["window"]))
        jax.block_until_ready(state)
        run_s = time.monotonic() - t0
        digest = digest_pytrees(elastic.canonical_state(state), spawn,
                                total)
    return digest, int(total), compile_s, run_s


def phold_32k() -> dict:
    import jax

    dev = jax.devices()[0]
    label = f"{dev.platform}/{dev.device_kind}"
    n, r = PHOLD["n_hosts"], PHOLD["rounds"]
    digest, delivered, comp, run = _phold(r)
    out = dict(delivered=delivered, compile_s=comp, run_s=run,
               delivered_per_s=delivered / run, digest=digest)
    print(f"[phold-32k] on {label}: {n} hosts x {r} windows, "
          f"{delivered} delivered, {delivered / run:.0f} delivered/s "
          f"(run {run:.2f} s, compile {comp:.1f} s; information, not "
          f"a claim)", flush=True)

    c = PHOLD["cpu_rounds"]
    chip, _d, _c, _r = _phold(c)
    cpu, _d, _c, cpu_s = _phold(c, device=jax.devices("cpu")[0])
    check(chip == cpu, f"phold-32k: first {c} windows differ between "
                       f"{label} ({chip[:16]}) and the CPU ({cpu[:16]})")
    print(f"[phold-32k] first {c} windows: {label} == cpu digest "
          f"{chip[:16]} (cpu run {cpu_s:.1f} s)", flush=True)
    return out


def flow_digest(data_dir: str) -> tuple[str, int]:
    """sha256 over the per-flow completion times (int64, plan order),
    and the flow count."""
    import numpy as np

    with open(os.path.join(data_dir, "sim-stats.json")) as fh:
        stats = json.load(fh)
    done = np.asarray(stats["flow_complete_us"], np.int64)
    check(bool((done < np.iinfo(np.int32).max).all()),
          f"floweng: {int((done >= np.iinfo(np.int32).max).sum())} "
          f"transfers never completed")
    return hashlib.sha256(done.tobytes()).hexdigest(), len(done)


def floweng() -> dict:
    import bench_ladder

    d = _cli(bench_ladder.rung3_config(use_flow_engine=True), "floweng")
    digest, n = flow_digest(d)
    check(digest == FLOWENG_CPU_DIGEST,
          f"tgen-atlas-1k-floweng: completion digest {digest} != the "
          f"CPU's {FLOWENG_CPU_DIGEST}")
    print(f"[tgen-atlas-1k-floweng] {n} transfers complete; completion "
          f"digest {digest[:16]} == CPU digest", flush=True)
    return {"flows": n, "digest": digest}


def transport() -> dict:
    import jax
    import jax.numpy as jnp

    import bench_ladder
    from compare_runs import comparable_stats
    from shadow_tpu.tpu.transport import auto_mode

    dev = comparable_stats(_cli(bench_ladder.rung2_config(
        experimental=DEVICE_TRANSPORT), "transport-device"))
    cpu = comparable_stats(_cli(bench_ladder.rung2_config(),
                                "transport-cpu"))
    check(dev == cpu, "tgen-mesh-100-transport: sim-stats.json differs "
                      "between the device and the CPU transport")
    mode, ms = auto_mode(jax, jnp)
    print(f"[tgen-mesh-100-transport] device (sync) == CPU transport "
          f"sim-stats.json ({dev['packets_sent']} packets); auto would "
          f"pick {mode} (D2H probe {ms:.3f} ms)", flush=True)
    return {"packets": dev["packets_sent"], "auto_mode": mode,
            "d2h_probe_ms": ms}


def native_pairs() -> dict:
    import bench_ladder

    bins = os.path.join(WORK, "native-bin")
    shutil.rmtree(bins, ignore_errors=True)
    os.makedirs(bins)
    server, client = bench_ladder.build_tcp_pair(bins)
    _cli(bench_ladder.interpose_config(N_PAIRS, PAIR_BYTES, server, client,
                                       experimental=DEVICE_TRANSPORT),
         "native-pairs")
    print(f"[native-pairs] {N_PAIRS} TCP pairs ({2 * N_PAIRS} native "
          f"processes under the shim, device transport): every process "
          f"reached its expected state", flush=True)
    return {"pairs": N_PAIRS}


# ---------------------------------------------------------------------------


def _stop_children() -> None:
    """SIGKILL whatever this process left running — the managed
    processes of a phase that failed — so the script stops every
    process it starts."""
    me = str(os.getpid())
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as fh:
                ppid = fh.read().rsplit(")", 1)[1].split()[1]
            if ppid == me:
                os.kill(int(pid), signal.SIGKILL)
        except (OSError, IndexError):
            pass  # gone already


def _setup_paths() -> None:
    sys.path[:0] = [REPO, os.path.join(REPO, "tools")]


def record_cpu_digest() -> int:
    """Print rung 3f's completion digest under JAX_PLATFORMS=cpu — the
    value `FLOWENG_CPU_DIGEST` holds."""
    import jax

    if jax.config.jax_platforms != "cpu":
        print("--record-cpu-digest needs JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    _setup_paths()
    import bench_ladder

    digest, n = flow_digest(_cli(
        bench_ladder.rung3_config(use_flow_engine=True), "floweng-cpu"))
    print(f"{n} flows, CPU completion digest {digest}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--record-cpu-digest", action="store_true",
                    help="print rung 3f's per-flow completion digest on "
                         "the CPU (JAX_PLATFORMS=cpu) and exit")
    args = ap.parse_args(argv)
    if args.record_cpu_digest:
        return record_cpu_digest()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    _setup_paths()
    from shadow_tpu.tpu import require_device

    require_device("chip smoke")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    # native libraries from the committed sources only
    subprocess.run(["make", "-s", "-C",
                    os.path.join(REPO, "shadow_tpu", "interpose"),
                    "clean", "all"], check=True)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)

    try:
        phase("corpus", corpus)
        phase("phold-32k", phold_32k)
        phase("tgen-atlas-1k-floweng", floweng)
        phase("tgen-mesh-100-transport", transport)
        phase("native-pairs", native_pairs)
    finally:
        _stop_children()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    os.makedirs(os.path.dirname(SUMMARY), exist_ok=True)
    with open(SUMMARY, "w") as fh:
        json.dump({"device": device, "phases": _results}, fh, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
