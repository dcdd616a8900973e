#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness knows no cell by name. It reads the cell's entry from
`BENCHMARK.json` and finds the rest by name:

- `benchmark/configs/<file>` (the config's `file`): sizes and the names
  of its driver and reference;
- `benchmark/traffic/<traffic>.json`: the mix's parameters;
- `benchmark/drivers/<driver>.py`: builds the world from `--seed`, runs
  set-up, the timed window, the read-back and the comparison;
- `benchmark/metrics/<metric>.py`: `read(ctx)` reduces the run record or
  the trace to one number, or None when it finds nothing to read.

It refuses (exit 2, no result) a machine without a TPU unless
`JAX_PLATFORMS=cpu` asks for a CPU rehearsal, fewer chips than the cell
asks for, and a device kind missing from `benchmark/peaks.json`.

The last stdout line is one JSON object: correct, attempted, failed,
metrics (end-to-end with `--trace 0`, per-layer with `--trace 1`), device
[, breakdown], checks (each compared number with its limit, last). The
compared numbers also go to stderr as its last lines."""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be made here; exit 2 with no result."""


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise Refused(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    if not os.path.isfile(path):
        raise Refused(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def resolve(root: str, workload: str):
    """(bench, cell, config file, traffic) by name."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = read_json(os.path.join(root, entry["file"]))
    traffic = read_json(os.path.join(root, "benchmark", "traffic",
                                     f"{cell['traffic']}.json"))
    return bench, cell, cfg, traffic


def metrics_for(bench: dict, cell: dict, trace: bool):
    """The cell's end-to-end metrics, or with `trace` its per-layer
    metrics (those listing the cell, or without a list those moving an
    end-to-end metric the cell reports)."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def check_device(root: str, cell: dict):
    """Platform, kind and count as JAX reports them; refuses a machine
    that cannot run the cell."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise Refused(f"JAX found no TPU (platform {platform!r}); set "
                      f"JAX_PLATFORMS=cpu for a CPU rehearsal")
    if len(devs) < cell["chips"]:
        raise Refused(f"cell needs {cell['chips']} chips, JAX found "
                      f"{len(devs)}")
    peaks = None
    if platform == "tpu":
        table = read_json(os.path.join(root, "benchmark", "peaks.json"))
        if devs[0].device_kind not in table:
            raise Refused(f"device kind {devs[0].device_kind!r} is not "
                          f"in benchmark/peaks.json")
        peaks = table[devs[0].device_kind]
    return devs[:cell["chips"]], peaks


def memory_peak(devs):
    peaks = []
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def main(argv=None, root: str = REPO) -> int:
    """One run of one cell; `root` is the checkout (tests point it at a
    shrunken copy). What the run sets in the process (the cache
    directory, JAX's cache threshold, the compile listener) is put back
    when it returns, so an in-process caller keeps its own."""
    import jax

    env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    config_before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    compiles = []

    def on_duration(event, _dur, **_kw):
        if event == COMPILE_EVENT:
            compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    # the compile cache lives inside the checkout at a fixed path, so that
    # only a cell's first run there compiles; JAX fixes its cache
    # directory at its first compile, so this comes before any compile
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    try:
        return _main(argv, root, compiles)
    finally:
        from jax._src import compilation_cache

        jax.monitoring.unregister_event_duration_listener(on_duration)
        for k, v in config_before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        if env_before is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env_before


def _main(argv, root: str, compiles: list) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import tracing

    here = os.path.join(root, "benchmark")
    trace_dir = os.path.join(root, ".bench_trace")
    bench, cell, cfg, traffic = resolve(root, args.workload)
    wanted = metrics_for(bench, cell, bool(args.trace))
    readers = {m["name"]: load_module(
        os.path.join(here, "metrics", f"{m['name']}.py"),
        f"bench_metric_{m['name']}") for m in wanted}
    drv = load_module(os.path.join(here, "drivers", f"{cfg['driver']}.py"),
                      f"bench_driver_{cfg['driver']}")

    import jax

    devs, peaks = check_device(root, cell)
    device_ready_s = time.monotonic() - T_START
    from shadow_tpu.tpu import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    span = jax.profiler.TraceAnnotation
    run = drv.Run(cfg, traffic, args.seed, span)
    run.setup()
    setup_s = time.monotonic() - T_START

    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_w0 = time.monotonic()
    with span("bench.window"):
        rec = run.window(args.seconds)
    t_w1 = time.monotonic()
    if args.trace:
        jax.profiler.stop_trace()
    n_compiles = sum(t_w0 <= t <= t_w1 for t in compiles)
    mem = memory_peak(devs)

    got = run.output()
    run.free()
    gc.collect()
    t_c = time.monotonic()
    checks = run.check(got)
    check_s = time.monotonic() - t_c

    trace = window = None
    if args.trace:
        trace = tracing.load(trace_dir)
        window = tracing.window_of(trace)
    ctx = SimpleNamespace(run=rec, setup_s=setup_s, memory_peak_bytes=mem,
                          trace=trace, window=window,
                          compiles_in_window=n_compiles, peaks=peaks,
                          cfg=cfg, traffic=traffic, cell=cell)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if trace is not None and window is not None:
        busy = tracing.device_busy(trace, window)
        if busy is not None:
            device["busy_s"], device["window_s"] = busy
        out["breakdown"] = {"device_ops": tracing.top_ops(trace, window),
                            "idle_gaps": tracing.idle_gaps(trace, window)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}

    print(json.dumps({"info": {
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "setup_s": setup_s, "device_ready_s": device_ready_s,
        **getattr(run, "setup_phases", {}), "check_s": check_s,
        "compiles_in_window": n_compiles,
        **{k: v for k, v in rec.items() if k != "state_shapes"},
        "events_per_s": rec["events"] / rec["wall_s"]}}), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k} = {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        sys.exit(2)
    except ImportError as e:
        print(f"benchmark cannot run here: {e}", file=sys.stderr)
        sys.exit(2)
