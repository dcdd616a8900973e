"""Driver for PHOLD configurations: the world, the chain, the timed window.

The world is the config's graph (Shadow's `1_gbit_switch`: one node,
one path latency, no loss) with the traffic file's seed packets; the
seed packets' destinations and the loss key come from `--seed`, so every
seed does the same amount of work on another stream. The chain body is `bench.phold_chain`'s,
copied: one `lax.scan` of `window_step` + `respawn_batch` +
`ingest_rows` per `chain_windows` windows, driven one chain per
`elastic.drive_chained_windows` call with a host sync at each chain end.

Set-up builds the world on the device in one jitted call, compiles the
chain and runs the first chain (rounds 0..L-1). The timed window runs
whole chains until `--seconds` have passed, one chain queued ahead. The check replays every
window the program ran (set-up's included) in the plain reference and
compares the final rings and per-host counters."""

from __future__ import annotations

import importlib.util
import os
import time

import numpy as np

MS = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))


def key_words(seed: int):
    """The loss stream's threefry key: the seed's two 32-bit words."""
    return (np.uint32((seed >> 32) & 0xFFFFFFFF),
            np.uint32(seed & 0xFFFFFFFF))


def make_inputs(cfg: dict, traffic: dict, seed: int) -> dict:
    """Everything the program and the reference both start from."""
    N, M = cfg["n_hosts"], cfg["n_nodes"]
    k = traffic["seed_packets_per_host"]
    src = np.repeat(np.arange(N, dtype=np.int64), k)
    return {
        "n_hosts": N, "egress_cap": cfg["egress_cap"],
        "ingress_cap": cfg["ingress_cap"],
        "window_ns": cfg["window_ms"] * MS,
        "key": key_words(seed),
        "latency_ns": np.full((M, M), cfg["latency_ms"] * MS, np.int32),
        "loss": np.full((M, M), cfg["loss"], np.float32),
        "host_node": (np.arange(N) % M).astype(np.int32),
        "up_bw_bps": np.full((N,), cfg["up_bw_bps"], np.int64),
        "packet_bytes": traffic["packet_bytes"],
        "spawn_seq0": traffic["spawn_seq0"],
        "seed_src": src,
        "seed_dst": np.random.default_rng(seed).integers(0, N, N * k),
        "seed_seq": np.arange(N * k, dtype=np.int64),
    }


def _load_reference(name: str):
    path = os.path.join(HERE, os.pardir, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """One run of a PHOLD cell."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.traffic, self.seed, self.span = cfg, traffic, seed, span
        self.L = traffic["chain_windows"]
        self.inputs = make_inputs(cfg, traffic, seed)
        self.rounds = 0  # windows the program has run

    # -- program side -----------------------------------------------------
    def _chain(self, window):
        import jax
        import jax.numpy as jnp

        from shadow_tpu.tpu import ingest_rows, unpack_planes, window_step
        from shadow_tpu.workloads.phold import respawn_batch

        N = self.cfg["n_hosts"]
        kernel = self.cfg["plane_kernel"]
        rr = self.cfg["rr_enabled"]

        def round_fn(carry, round_idx, params, key):
            state, spawn_seq = carry
            shift = jnp.where(round_idx == 0, jnp.int32(0), window)
            out = window_step(state, params, key, shift, window,
                              rr_enabled=rr, kernel=kernel)
            (state, delivered, _next), *_ = unpack_planes(out)
            mask, new_dst, nbytes, seq_vals, ctrl = respawn_batch(
                delivered, spawn_seq, round_idx, N, state.in_src.shape[1])
            out = ingest_rows(state, new_dst, nbytes, seq_vals, seq_vals,
                              ctrl, valid=mask)
            (state,), *_ = unpack_planes(out, n_lead=1)
            spawn_seq = spawn_seq + mask.sum(axis=1, dtype=jnp.int32)
            return (state, spawn_seq), mask.sum(dtype=jnp.int32)

        # the seed's tables and key are arguments, not constants, so one
        # compiled program serves every seed
        def phold_chain(state, spawn_seq, round_ids, params, key_data):
            key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
            (state, spawn_seq), n = jax.lax.scan(
                lambda c, r: round_fn(c, r, params, key),
                (state, spawn_seq), round_ids)
            return state, spawn_seq, n.sum()

        return jax.jit(phold_chain, donate_argnums=(0, 1))

    def setup(self):
        import jax
        import jax.numpy as jnp

        from shadow_tpu.tpu import ingest, make_params, make_state

        cfg, inp = self.cfg, self.inputs
        N, k = cfg["n_hosts"], self.traffic["seed_packets_per_host"]
        CE, CI = cfg["egress_cap"], cfg["ingress_cap"]
        pkt = self.traffic["packet_bytes"]
        params = make_params(inp["latency_ns"], inp["loss"],
                             inp["up_bw_bps"], host_node=inp["host_node"])
        self.params = params
        self.key_data = jnp.asarray(np.array(inp["key"], np.uint32))
        window = jnp.int32(inp["window_ns"])

        @jax.jit
        def build_world(tb_cap, dst):
            state = make_state(N, egress_cap=CE, ingress_cap=CI,
                               initial_tokens=tb_cap)
            src = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
            b = N * k
            state = ingest(state, src, dst, jnp.full((b,), pkt, jnp.int32),
                           jnp.arange(b, dtype=jnp.int32),
                           jnp.arange(b, dtype=jnp.int32),
                           jnp.zeros((b,), bool))
            spawn = jnp.full((N,), self.traffic["spawn_seq0"], jnp.int32)
            return state, spawn

        t0 = time.monotonic()
        with self.span("setup.world"):
            self.state, self.spawn = build_world(
                params.tb_cap, jnp.asarray(inp["seed_dst"], jnp.int32))
            self.delivered = jnp.int32(0)
            jax.block_until_ready(self.state)
        t1 = time.monotonic()
        self.chain = self._chain(window)
        self.state_shapes = [
            (tuple(x.shape), str(x.dtype))
            for x in jax.tree_util.tree_leaves((self.state, self.spawn))]
        # the first chain compiles (or loads) the program and runs
        # rounds 0..L-1; every later chain reuses it
        with self.span("setup.first_chain"):
            self._one_chain()
            jax.block_until_ready((self.state, self.spawn, self.delivered))
        # the driver's round ids for a chain past round 0 are an offset
        # iota, an eager op of its own: warm it, so that nothing
        # compiles in the window
        jax.block_until_ready(jnp.arange(self.L, 2 * self.L,
                                         dtype=jnp.int32))
        self.setup_phases = {"world_s": t1 - t0,
                             "first_chain_s": time.monotonic() - t1}

    def _one_chain(self):
        from shadow_tpu.tpu import elastic

        r0, r1 = self.rounds, self.rounds + self.L

        def chain_fn(state, extras, rids, _pr):
            spawn, total = extras
            state, spawn, n = self.chain(state, spawn, rids, self.params,
                                         self.key_data)
            return state, (spawn, total + n), None, None

        with self.span("chain.dispatch"):
            self.state, (self.spawn, self.delivered) = \
                elastic.drive_chained_windows(
                    self.state, (self.spawn, self.delivered), chain_fn,
                    n_rounds=r1, chain_len=self.L, start_round=r0)
        self.rounds = r1

    def window(self, seconds: float) -> dict:
        """Whole chains until `seconds` have passed, one chain always
        queued ahead of the one the host waits for, so the device never
        waits on the host's wake-up; the last chain is waited for and
        counted."""
        import jax

        r_start = self.rounds
        d_start = int(self.delivered)
        ready = []
        t0 = time.monotonic()
        self._one_chain()
        while True:
            prev = self.delivered  # ready once that chain has run
            self._one_chain()
            with self.span("chain.readback"):
                jax.block_until_ready(prev)
            ready.append(time.monotonic() - t0)
            if ready[-1] >= seconds:
                break
        with self.span("chain.readback"):
            jax.block_until_ready((self.state, self.spawn, self.delivered))
        t1 = time.monotonic()
        ready.append(t1 - t0)
        windows = self.rounds - r_start
        w_ns = self.inputs["window_ns"]
        deliveries = int(self.delivered) - d_start
        return {
            "wall_s": t1 - t0,
            "sim_s": windows * w_ns / 1e9,
            "windows": windows,
            "chains": windows // self.L,
            "attempted": windows,
            "chain_ready_s": [round(t, 4) for t in ready],
            "failed": 0,
            "events": deliveries,
            "state_shapes": self.state_shapes,
        }

    def output(self) -> dict:
        """The program's final state as dense host arrays, live lanes
        front-packed in ring order."""
        import jax

        st, spawn, total = jax.device_get((self.state, self.spawn,
                                           self.delivered))

        def packed(valid, cols):
            o = np.argsort(~valid, axis=1, kind="stable")
            return ({k: np.take_along_axis(np.asarray(v), o, 1)
                     for k, v in cols.items()},
                    np.take_along_axis(valid, o, 1))

        eg, egv = packed(np.asarray(st.eg_valid), dict(
            dst=st.eg_dst, nbytes=st.eg_bytes, prio=st.eg_prio,
            seq=st.eg_seq, ctrl=st.eg_ctrl, tsend=st.eg_tsend,
            clamp=st.eg_clamp, sock=st.eg_sock))
        ing, inv = packed(np.asarray(st.in_valid), dict(
            src=st.in_src, nbytes=st.in_bytes, seq=st.in_seq,
            sock=st.in_sock, deliver=st.in_deliver_rel))
        return dict(
            eg=eg, eg_valid=egv, ing=ing, in_valid=inv,
            hosts=dict(tb_balance=st.tb_balance, tb_rem_ns=st.tb_rem_ns,
                       rng_counter=st.rng_counter, n_sent=st.n_sent,
                       n_loss_dropped=st.n_loss_dropped,
                       n_overflow_dropped=st.n_overflow_dropped,
                       n_delivered=st.n_delivered, spawn_seq=spawn),
            delivered_total=int(total), rounds=self.rounds)

    def free(self):
        self.state = self.spawn = self.delivered = self.chain = None
        self.params = self.key_data = None

    # -- the comparison ----------------------------------------------------
    def check(self, got: dict, control: str | None = None) -> dict:
        """Replay the program's windows in the plain reference and
        compare. Returns {name: (value, limit)}."""
        ref = _load_reference(self.cfg["reference"])
        want = ref.PholdReference(self.inputs, control=control).run(
            got["rounds"]).dense()
        found = ref.compare(got, want)
        limits = self.cfg["limits"]
        return {k: (v, limits[k]) for k, v in found.items()}
