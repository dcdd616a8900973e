"""Batched network-plane state and the per-window step function.

This is the TPU-native re-design of Shadow's per-packet hot path
(`src/main/core/worker.rs:326-410` send_packet, `src/main/network/relay/`
token buckets, per-host event queues) as dense array ops:

- `RoutingInfo` becomes the `[N, N]` latency/loss matrices already produced
  by `shadow_tpu.net.graph` (SURVEY.md §2.5 "this is the table that becomes
  a dense HBM array").
- Per-host rate limiting (`relay/token_bucket.rs`) becomes a vectorized
  token-bucket refill + prefix-sum spend over each host's egress queue.
- Bernoulli path loss from the *source host's* RNG stream
  (`worker.rs:359-375`) becomes counter-based threefry: every egress slot
  derives its key from (root_key, per-host monotone counter), so draws are
  identical under any vectorization or sharding.
- The deliver-time clamp to the round end (`worker.rs:396-399`) is what
  makes window-batched exchange legal; it is applied on-device.
- Cross-host "push to destination queue under mutex" (`worker.rs:629-639`)
  becomes a deterministic sorted scatter into fixed-capacity ingress
  queues; with the host axis sharded over a mesh the scatter is the
  all-to-all the SPMD partitioner lowers to ICI collectives.

Dtype discipline (TPU-first):
- Everything is int32/float32; no x64 dependence.
- Times on-device are *relative to the current window start* and rebased by
  `shift` each round, so int32 ns never overflows (constraint: path
  latency + window length < ~2.1 s, amply true for network sims).
- Invalid/empty slots use INT32_MAX sentinels so min-reductions are clean.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..faults.plane import FaultArrays
from ..guards.plane import GuardState
from ..guards import plane as guards_plane
from ..telemetry import flightrec as flightrec_mod
from ..telemetry import histo
from ..telemetry.flightrec import FlightRecArrays
from ..telemetry.histo import PlaneHistograms
from ..telemetry.metrics import PlaneMetrics
from . import codel

I32_MAX = np.int32(2**31 - 1)
# Bounded per-host socket-slot space for the round-robin qdisc's fairness
# counters; socket ids hash in with `% RR_SOCK_SLOTS` (collisions merge
# flows, as in classic stochastic fair queuing — determinism is unaffected).
RR_SOCK_SLOTS = 16
# eg_clamp sentinel: "clamp this packet's delivery to the end of whatever
# window processes it" (the pure-device mode, where ingest and step share a
# window). Integrated transport passes the send-round end instead, since the
# processing step runs one round later (`worker.rs:396-399` semantics).
NO_CLAMP = np.int32(-(2**30))


class NetPlaneParams(NamedTuple):
    """Static per-simulation data.

    Path properties are NODE-level ([M, M] with a [N] host→node map), the
    shape the GML graph actually has (`net/graph.py` RoutingInfo): real
    topologies have far fewer graph nodes than hosts, so the latency/loss
    tables stay small enough for VMEM residency — a [N, N] host-pair
    gather at 16k hosts would be a 1 GiB HBM table with ~30 ns per random
    lookup dominating the window step. Host-pair matrices still work:
    pass host_node=arange(N) (the make_params default)."""

    latency_ns: jax.Array  # [M, M] int32 — path latency between nodes
    loss: jax.Array  # [M, M] float32 — path loss probability
    host_node: jax.Array  # [N] int32 — graph node index of each host
    tb_rate: jax.Array  # [N] int32 — egress bytes per millisecond (up-bw)
    tb_cap: jax.Array  # [N] int32 — bucket capacity (rate/ms + 1 MTU burst)
    qdisc_rr: jax.Array  # [N] bool — per-host qdisc: round-robin vs FIFO
    dn_rate: jax.Array  # [N] int32 — ingress bytes per millisecond (down-bw)
    dn_cap: jax.Array  # [N] int32 — down bucket capacity (rate/ms + 1 MTU)


class NetPlaneState(NamedTuple):
    """Mutable SoA state, axis 0 = host, sharded over the mesh."""

    # egress queues (outbound, awaiting bandwidth): [N, CE]
    eg_dst: jax.Array  # int32 dest host index (-1 invalid)
    eg_bytes: jax.Array  # int32 total wire size
    eg_prio: jax.Array  # int32 host-assigned FIFO priority
    eg_seq: jax.Array  # int32 per-source packet id (payload correlation)
    eg_ctrl: jax.Array  # bool — control packets are never loss-dropped
    eg_tsend: jax.Array  # int32 ns send time relative to window start
    eg_clamp: jax.Array  # int32 barrier clamp (NO_CLAMP = current window end)
    eg_sock: jax.Array  # int32 emitting-socket id (round-robin qdisc key)
    eg_valid: jax.Array  # bool
    # ingress queues (in flight toward this host): [N, CI]
    in_src: jax.Array  # int32 source host index
    in_bytes: jax.Array  # int32
    in_seq: jax.Array  # int32
    in_sock: jax.Array  # int32 payload tag (socket id / pool slot)
    in_deliver_rel: jax.Array  # int32 ns relative to current window start
    in_valid: jax.Array  # bool
    # scalars per host: [N]
    tb_balance: jax.Array  # int32 token bytes available
    tb_rem_ns: jax.Array  # int32 sub-millisecond refill remainder
    rng_counter: jax.Array  # int32 draws consumed (determinism contract)
    # RR qdisc fairness: [N, RR_SOCK_SLOTS] int32 — virtual finish counter
    # per socket slot (packets this socket has pushed through the qdisc,
    # floored to the active minimum so idle sockets re-join at the current
    # virtual time instead of monopolizing on return)
    rr_sent: jax.Array
    # destination-side router (CoDel AQM + down-bw relay) scalars; active
    # only when window_step compiles with router_aqm=True
    router: codel.RouterDownState
    # counters (per host, int32)
    n_sent: jax.Array
    n_loss_dropped: jax.Array
    n_overflow_dropped: jax.Array
    n_delivered: jax.Array
    # fault-plane drops (injected failures: dead-host egress purge,
    # burst corruption, routing toward a crashed/link-down host) —
    # distinct from n_loss_dropped so injected losses are never
    # misattributed to the Bernoulli loss sample (docs/robustness.md);
    # stays zero when window_step compiles with faults=None
    n_fault_dropped: jax.Array


def make_params(latency_ns: np.ndarray, loss: np.ndarray, up_bw_bps: np.ndarray,
                mtu: int = 1500,
                qdisc_rr: np.ndarray | None = None,
                down_bw_bps: np.ndarray | None = None,
                host_node: np.ndarray | None = None) -> NetPlaneParams:
    """Build params from the routing matrices (`RoutingInfo.latency_ns/loss`,
    node-level [M, M]) and per-host up-bandwidths in bits/sec.

    `host_node` [N] maps each host to its graph-node index; None means the
    matrices are host-pair ([N, N]) and the identity map is used.

    `qdisc_rr` [N] bool selects the per-host queuing discipline
    (`QDiscMode` in `configuration.rs:961`): False = FIFO by packet
    priority, True = round-robin across emitting sockets. Default FIFO.

    `down_bw_bps` [N] feeds the destination-side router's down-bandwidth
    relay bucket (active only when window_step runs with router_aqm=True);
    None = transparent (max rate)."""
    # the path-latency budget (SL506 input-domain registry,
    # analysis/ranges.py `state.in_deliver_rel`): deliver = max(tsend +
    # latency, clamp) with tsend <= window <= I32_MAX//4 stays inside
    # int32 only while latency <= I32_MAX//2 (~1.07 s — beyond any
    # modeled path; the fault plane's lat_mult clamps to the same
    # budget). Was a docstring sentence ("path latency + window length
    # < ~2.1 s"); now refused at construction.
    lat = np.asarray(latency_ns)
    if lat.size and (lat.min() < 0 or lat.max() > (2**31 - 1) // 2):
        raise ValueError(
            f"latency_ns out of the device budget [0, I32_MAX//2 ns]: "
            f"min={lat.min()}, max={lat.max()} — the int32-ns deliver "
            "arithmetic (SL506 range proof, docs/determinism.md) "
            "admits wraparound beyond ~1.07 s of path latency")
    # cap the per-ms rate at 2^30 - mtu so the refill arithmetic in
    # window_step (rate * elapsed_eff <= headroom + rate <= cap + rate)
    # can never overflow int32; 2^30 B/ms ~ 8.6 Tbit/s, beyond any modeled NIC
    rate = np.minimum(
        np.maximum(1, (np.asarray(up_bw_bps) // 8) // 1000), 2**30 - mtu
    ).astype(np.int32)  # B/ms
    if host_node is None:
        host_node = np.arange(np.asarray(latency_ns).shape[0], dtype=np.int32)
    # host count: the host->node map defines it; a scalar bandwidth must
    # broadcast to N (not M — the node tables can be smaller than the fleet)
    n = np.asarray(host_node).shape[0]
    rate = np.broadcast_to(rate, (n,))
    if down_bw_bps is None:
        dn_rate = np.full(n, 2**30 - mtu, np.int32)
    else:
        dn_rate = np.broadcast_to(np.minimum(
            np.maximum(1, (np.asarray(down_bw_bps) // 8) // 1000),
            2**30 - mtu,
        ).astype(np.int32), (n,))
    return NetPlaneParams(
        latency_ns=jnp.asarray(latency_ns, jnp.int32),
        loss=jnp.asarray(loss, jnp.float32),
        host_node=jnp.asarray(host_node, jnp.int32),
        tb_rate=jnp.asarray(rate),
        tb_cap=jnp.asarray(rate + mtu, jnp.int32),
        qdisc_rr=(jnp.asarray(qdisc_rr, bool) if qdisc_rr is not None
                  else jnp.zeros(n, bool)),
        dn_rate=jnp.asarray(dn_rate),
        dn_cap=jnp.asarray(dn_rate + mtu, jnp.int32),
    )


def make_state(n_hosts: int, egress_cap: int = 32, ingress_cap: int = 64,
               initial_tokens: np.ndarray | None = None,
               initial_dn_tokens: np.ndarray | None = None,
               params: NetPlaneParams | None = None) -> NetPlaneState:
    """`params` (or an explicit `initial_dn_tokens`) starts the down-bw
    bucket at full capacity like the CPU TokenBucket — REQUIRED for parity
    whenever window_step runs with router_aqm=True (a zero-token start
    would delay every host's first inbound delivery to the 1 ms refill).

    `egress_cap`/`ingress_cap` need not be guessed right: under the
    elastic capacity policy (`capacity: {mode: elastic}` /
    `tpu/elastic.grow_state`, docs/robustness.md "Elastic capacity")
    drivers double a ring that overflows and re-execute the window from
    the pre-window snapshot, bitwise-identical to a run pre-provisioned
    at the final size. The invalid-lane fills below (-1 dst, I32_MAX
    priority/deliver sentinels, NO_CLAMP) are the canonical dead-lane
    values `elastic.grow_state`/`elastic.canonical_state` reproduce —
    keep the three in sync."""
    if initial_dn_tokens is None and params is not None:
        initial_dn_tokens = np.asarray(params.dn_cap)
    N, CE, CI = n_hosts, egress_cap, ingress_cap
    z = lambda shape: jnp.zeros(shape, jnp.int32)
    return NetPlaneState(
        eg_dst=jnp.full((N, CE), -1, jnp.int32),
        eg_bytes=z((N, CE)),
        eg_prio=jnp.full((N, CE), I32_MAX, jnp.int32),
        eg_seq=z((N, CE)),
        eg_ctrl=jnp.zeros((N, CE), bool),
        eg_tsend=z((N, CE)),
        eg_clamp=jnp.full((N, CE), NO_CLAMP, jnp.int32),
        eg_sock=z((N, CE)),
        eg_valid=jnp.zeros((N, CE), bool),
        in_src=jnp.full((N, CI), -1, jnp.int32),
        in_bytes=z((N, CI)),
        in_seq=z((N, CI)),
        in_sock=z((N, CI)),
        in_deliver_rel=jnp.full((N, CI), I32_MAX, jnp.int32),
        in_valid=jnp.zeros((N, CI), bool),
        tb_balance=(jnp.asarray(initial_tokens, jnp.int32)
                    if initial_tokens is not None else z((N,))),
        tb_rem_ns=z((N,)),
        rng_counter=z((N,)),
        rr_sent=z((N, RR_SOCK_SLOTS)),
        # CPU TokenBucket starts at full capacity; callers running with
        # router_aqm should pass the dn_cap array here for parity
        router=codel.make_router_state(N, initial_dn_tokens),
        n_sent=z((N,)),
        n_loss_dropped=z((N,)),
        n_overflow_dropped=z((N,)),
        n_delivered=z((N,)),
        n_fault_dropped=z((N,)),
    )


def _row_sort(*arrays, keys: int):
    """Sort each row of the given [N, C] arrays lexicographically by the
    first `keys` arrays. Returns the arrays reordered."""
    return jax.lax.sort(arrays, dimension=1, is_stable=True, num_keys=keys)


# --- packed sort keys (the "sort diet") ------------------------------------
# The window step's row sorts used to push every payload column through the
# lax.sort comparator network (12 arrays for the egress qdisc sort). The
# packed-key forms below fuse the (validity, key) pair into ONE uint32 key,
# sort (key, column-index), and apply the resulting per-row permutation to
# the payload columns with `_row_take` — the sorting network carries 2-3
# arrays instead of 7-12, and the permutation only moves values within a
# C-wide row: at ring widths a dense compare-and-select chain, not a TPU
# gather (5-10 ns per element on a v5e). Flat cross-host sorts (routing,
# flat ingest) get the BUCKETED diet instead (`_routing_rank`, `ingest`):
# the group key is a bounded bucket id, so the comparator network's keys
# are only (bucket, order key, slot index). The routing sort carries its
# three payload columns too (a flat gather is the dearer way to order
# them), and the rows are then filled through one stacked gather
# addressed by index arithmetic (`_routing_place`, `ingest`).

_SIGN32 = np.uint32(0x80000000)
_U32_MAX = np.uint32(0xFFFFFFFF)

def _row_take(a, perm):
    """`jnp.take_along_axis(a, perm, axis=1)` for an [N, C] `a` and an
    [N, K] int32 `perm` with entries in [0, C), as C compare-and-select
    passes over the [N, K] output: dense elementwise work that fuses
    into one loop, where XLA's gather costs 5-10 ns per element on a
    TPU v5e (`tools/bench_row_take.py`: the chain is ahead at every
    width measured, 16 to 256 columns). Only slices, broadcasts,
    compares on `perm` and selects touch `a`, so SL505's selection
    witness sees a pure selection of `a`."""
    col = lambda c: jax.lax.broadcast_in_dim(
        jax.lax.slice_in_dim(a, c, c + 1, axis=1), perm.shape, (0, 1))
    out = col(0)
    for c in range(1, a.shape[1]):
        out = jax.lax.select(perm == c, col(c), out)
    return out


def _segment_bounds(keys, n: int):
    """`jnp.searchsorted(keys, arange(n + 1))` for a sorted int32 [B]
    `keys`: bounds[q] = the number of keys below q, so bucket q's
    segment is [bounds[q], bounds[q + 1]). A blocked search in three
    levels over k-wide rows of the keys (padded with I32_MAX to a
    multiple of k * k, R rows): a compare-reduce of each query against
    every k-th row head ([n + 1, R / k]; the R / k heads are one
    strided take, no per-query gather) picks its block of k rows, a
    compare-reduce against that block's k heads picks its row, and one
    against that row's k keys counts within it. Two row gathers of
    [n + 1, k] per call, where the binary search makes
    ceil(log2(B + 1)) scalar gathers of [n + 1] (20 at B = 524,288),
    and XLA's gather costs 5-10 ns per index on a TPU v5e whatever its
    width (`tools/bench_route_place.py --stage bounds`). Every index
    is a count clipped at 0, so each lies in its array by construction.
    k is the least power of two with k * k >= B, at most 128: the
    blocking follows from B alone, and a small world pads to at most
    four times its keys."""
    B = keys.shape[0]
    k = 1
    while k < 128 and k * k < B:
        k *= 2
    R = max(-(-B // (k * k)), 1) * k
    if R * k > B:
        keys = jnp.concatenate(
            [keys, jnp.full((R * k - B,), I32_MAX, keys.dtype)])
    rows = keys.reshape(R, k)
    heads = rows[:, 0]
    q = jnp.arange(n + 1, dtype=jnp.int32)[:, None]
    below = lambda a: jnp.sum(a < q, axis=1, dtype=jnp.int32)
    blk = jnp.maximum(below(heads[::k][None, :]) - 1, 0)
    row = blk * k + jnp.maximum(below(heads.reshape(-1, k)[blk]) - 1, 0)
    return row * k + below(rows[row])


def _assert_bit_budget(*fields):
    """Trace-time guard: the named (bits, what) fields must fit a single
    32-bit packed sort key. Raises at trace time (shapes and capacities
    are static), never at runtime."""
    total = sum(bits for bits, _ in fields)
    if total > 32:
        raise ValueError(
            "packed sort key bit-budget overflow: "
            + " + ".join(f"{what}={bits}b" for bits, what in fields)
            + f" = {total} bits > 32")


def _pack_valid_key(valid, key, *, what="qdisc key"):
    """Fuse (invalid-last, key) into one uint32 sort key: validity in bit
    31, the int32 key in bits 0..30. Exactly order-isomorphic to sorting
    by the (~valid, key) pair as long as keys are non-negative — the
    plane's priority / RR-key domain (monotone counters from 0, I32_MAX
    sentinels). Bit budget (1 validity + 31 key) asserted at trace time."""
    _assert_bit_budget((1, "validity"), (31, what))
    return jnp.where(valid, jnp.uint32(0), _SIGN32) | key.astype(jnp.uint32)


def _pack_time_key(valid, t):
    """Fuse (invalid-last, time) into one uint32 key for FULL-RANGE int32
    times (deliver offsets can be legitimately negative after a window
    rebase): sign-bias the time into unsigned order, invalid slots take
    the all-ones key. Exact for any valid time < I32_MAX — the invalid
    sentinel, unreachable for real deliveries under the int32-ns window
    budget (path latency + window < ~2.1 s)."""
    return jnp.where(valid, t.astype(jnp.uint32) ^ _SIGN32, _U32_MAX)


def _pack_rank_key(valid, rank, width: int):
    """Fuse (invalid-last, column-rank) into one uint32 key; `width` is
    the static column count, so the rank field's bit budget is checked at
    trace time against the capacities that determine it. Used where the
    ONLY ordering requirement is valid-first-in-original-order (the
    ingest_rows merge): the sort then carries a single array and the
    permutation is recovered from the key's low bits."""
    rank_bits = max(int(width - 1).bit_length(), 1)
    _assert_bit_budget((1, "validity"), (rank_bits, f"rank[{width}]"))
    return jnp.where(valid, jnp.uint32(0), _SIGN32) | rank.astype(jnp.uint32)


def _row_perm_sort(packed, *extra_keys):
    """Stable row sort of (packed uint32 key [, extra keys]); returns the
    permutation [N, C] to apply to payload columns via `_row_take`.
    Stability makes the carried column index break ties in original
    order, exactly like the variadic stable sort it replaces."""
    N, C = packed.shape
    col = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (N, C))
    out = jax.lax.sort((packed, *extra_keys, col), dimension=1,
                       is_stable=True, num_keys=1 + len(extra_keys))
    return out[-1]


def _pkt_uniform(rng_root: jax.Array, host: jax.Array,
                 counter: jax.Array) -> jax.Array:
    """Counter-based uniform [0,1) draw per (host, counter) slot.

    One batched threefry_2x32 block cipher over all slots: the (host,
    counter) pair IS the cipher's counter block, so the stream depends
    only on (root_key, host, counter) — identical under any
    vectorization, sharding, or queue occupancy (the determinism
    contract) — while lowering to a single fused elementwise kernel.
    (The per-slot `fold_in` formulation computed 2 full hashes per slot
    through vmap and dominated the whole window step: 40 ms vs 0.1 ms
    for this at 65k slots on a v5e.)
    """
    from jax.extend import random as jex_random

    shape = host.shape
    kd = jax.random.key_data(rng_root).astype(jnp.uint32)
    count = jnp.concatenate([
        host.reshape(-1).astype(jnp.uint32),
        counter.reshape(-1).astype(jnp.uint32),
    ])
    bits = jex_random.threefry_2x32(kd, count)[: host.size].reshape(shape)
    # 24 high-entropy bits -> float32 [0,1) (loss thresholds don't need
    # more resolution than the CPU plane's Python float comparison)
    return (bits >> 8).astype(jnp.float32) * np.float32(1.0 / (1 << 24))


def _scatter_append(group, in_order_rank_src, n_valid, cap, n_groups):
    """Deterministic append-slot allocation for grouped scatter.

    `group` [B]: destination row per item, already SORTED ascending (items
    for the same row in their deterministic order); values >= n_groups mean
    "drop". `n_valid` [n_groups]: current occupancy per row. Returns
    (flat_idx [B] into a [n_groups, cap] buffer with out-of-bounds for
    dropped/overflowed items, ok mask, overflow count per group).
    """
    # rank within group = i - first-occurrence(group[i]); group is sorted,
    # so first-occurrence is a running cummax over segment starts (O(B),
    # vs the O(B log B) searchsorted(group, group) that cost 9.5 ms at 65k)
    idx = jnp.arange(group.shape[0], dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), group[1:] != group[:-1]])
    first = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank = idx - first
    in_range = group < n_groups
    slot = jnp.where(
        in_range, n_valid[jnp.clip(group, 0, n_groups - 1)] + rank, cap
    )
    ok = in_order_rank_src & (slot < cap) & in_range
    flat_idx = jnp.where(ok, group * cap + slot, n_groups * cap)
    overflow = jax.ops.segment_sum(
        (in_order_rank_src & in_range & (slot >= cap)).astype(jnp.int32),
        jnp.clip(group, 0, n_groups - 1),
        num_segments=n_groups,
    )
    return flat_idx, ok, overflow


def ingest(state: NetPlaneState, src: jax.Array, dst: jax.Array,
           nbytes: jax.Array, prio: jax.Array, seq: jax.Array,
           ctrl: jax.Array, valid: jax.Array | None = None,
           send_rel: jax.Array | None = None,
           clamp_rel: jax.Array | None = None,
           sock: jax.Array | None = None, *,
           packed_sort: bool = True,
           metrics: PlaneMetrics | None = None,
           guards: GuardState | None = None):
    """Append a batch of outbound packets ([B] arrays; src = emitting host
    index) to the egress queues. Slots are allocated after the current valid
    entries per row; overflow beyond capacity is counted and dropped.
    `valid` masks out dead batch slots (fixed-shape on-device producers).
    `send_rel` is each packet's emission time relative to the current
    window start (defaults to 0 = window start), giving per-packet deliver
    times that bitwise-match the CPU plane's now + latency.

    `metrics` (static presence) threads the telemetry counters: ring
    overflow drops accumulate into `drop_ring_full` and the call returns
    (state', metrics') instead of state' — the simulation state itself is
    bitwise-unchanged (the drop delta is read off the state's own
    n_overflow_dropped counter).

    `guards` (static presence, docs/robustness.md) threads the runtime
    invariant checks: append conservation (each row gains exactly
    incoming - overflow entries) accumulates into the violation bitmask
    and guards' is appended to the return. Pure reads — the simulation
    state is untouched.

    `packed_sort` (static) selects the bucketed flat-append diet: src is
    a bounded bucket id, so the deterministic (src, seq) append order
    needs only ONE diet sort carrying (bucket, sign-biased seq, batch
    index) plus blocked-search bucket bounds (`_segment_bounds`) for
    the counting placement, and the payload columns land via one fused
    stacked gather straight from the batch layout — vs the 9-array 2-key
    variadic sort it replaces (kept as the parity-test reference under
    `packed_sort=False`, bitwise-identical for in-domain src).

    The CPU syscall plane calls this once per round with everything the
    sockets emitted (double-buffered host arrays in the full system)."""
    N, CE = state.eg_dst.shape
    if valid is not None:
        # dead slots route to src N (out of range) and never place
        src = jnp.where(valid, src, N)
    if send_rel is None:
        send_rel = jnp.zeros_like(seq)
    if clamp_rel is None:
        clamp_rel = jnp.full_like(seq, NO_CLAMP)
    if sock is None:
        sock = jnp.zeros_like(seq)

    n_valid = state.eg_valid.sum(axis=1).astype(jnp.int32)  # [N]
    # rows are front-compacted (window_step re-sorts), so slot placement is
    # append; overflowing packets get an out-of-bounds index and drop
    incoming = None
    if packed_sort:
        # bucketed counting placement (same shape as the routing stage,
        # `_routing_rank`/`_routing_place`): ONE diet sort establishes
        # the (src, seq) append order, `_segment_bounds` bounds each
        # row's segment, and every payload column lands via one fused
        # stacked gather — base entries where the row already had them,
        # the segment's stream items in the appended slots
        B = src.shape[0]
        src_b = jnp.where((src >= 0) & (src < N), src, N)
        pos = jnp.arange(B, dtype=jnp.int32)
        o_src, _, o_pos = jax.lax.sort(
            (src_b, seq.astype(jnp.uint32) ^ _SIGN32, pos),
            dimension=0, is_stable=True, num_keys=2)
        bounds = _segment_bounds(o_src, N)
        offsets, counts = bounds[:-1], bounds[1:] - bounds[:-1]
        take_n = jnp.minimum(counts, jnp.int32(CE) - n_valid)
        overflow = jnp.maximum(counts + n_valid - CE, 0)
        incoming = counts
        flat = lambda a: a.reshape(-1)
        streams = jnp.stack([
            dst[o_pos], nbytes[o_pos], prio[o_pos], seq[o_pos],
            ctrl[o_pos].astype(jnp.int32), send_rel[o_pos],
            clamp_rel[o_pos], sock[o_pos], jnp.ones((B,), jnp.int32)])
        bases = jnp.stack([
            flat(state.eg_dst), flat(state.eg_bytes), flat(state.eg_prio),
            flat(state.eg_seq), flat(state.eg_ctrl.astype(jnp.int32)),
            flat(state.eg_tsend), flat(state.eg_clamp),
            flat(state.eg_sock), flat(state.eg_valid.astype(jnp.int32))])
        combined = jnp.concatenate([bases, streams], axis=1)
        ce_col = jnp.arange(CE, dtype=jnp.int32)[None, :]
        nv = n_valid[:, None]
        append = (ce_col >= nv) & (ce_col < nv + take_n[:, None])
        stream_idx = jnp.clip(offsets[:, None] + ce_col - nv, 0, B - 1)
        rows_i = jnp.arange(N, dtype=jnp.int32)[:, None]
        gidx = jnp.where(append, N * CE + stream_idx,
                         rows_i * CE + ce_col)
        merged = combined[:, gidx]  # one [9, N, CE] gather
        (eg_dst, eg_bytes, eg_prio, eg_seq, eg_ctrl_i, eg_tsend,
         eg_clamp, eg_sock, eg_valid_i) = merged
        eg_ctrl, eg_valid = eg_ctrl_i != 0, eg_valid_i != 0
    else:
        # the pre-diet reference: rank within each src group via one
        # variadic sort carrying every payload column
        (src_s, seq_s, dst_s, bytes_s, prio_s, ctrl_s, tsend_s, clamp_s,
         # shadowlint: disable=SL403 -- pre-diet variadic reference path
         sock_s) = jax.lax.sort(
            (src, seq, dst, nbytes, prio, ctrl, send_rel, clamp_rel, sock),
            dimension=0, is_stable=True, num_keys=2,
        )
        live = jnp.ones_like(src_s, bool)
        flat, ok, overflow = _scatter_append(src_s, live, n_valid, CE, N)
        if guards is not None:
            # incoming per row: live batch slots routed to in-range rows
            # (dead slots went to src N and fall off the segment sum)
            incoming = jax.ops.segment_sum(
                (src_s < N).astype(jnp.int32),
                jnp.clip(src_s, 0, N - 1), num_segments=N)

        def put(buf, vals):
            return buf.reshape(-1).at[flat].set(
                vals, mode="drop").reshape(N, CE)

        eg_dst = put(state.eg_dst, dst_s)
        eg_bytes = put(state.eg_bytes, bytes_s)
        eg_prio = put(state.eg_prio, prio_s)
        eg_seq = put(state.eg_seq, seq_s)
        eg_ctrl = put(state.eg_ctrl, ctrl_s)
        eg_tsend = put(state.eg_tsend, tsend_s)
        eg_clamp = put(state.eg_clamp, clamp_s)
        eg_sock = put(state.eg_sock, sock_s)
        eg_valid = put(state.eg_valid, jnp.ones_like(ok))
    new_state = state._replace(
        eg_dst=eg_dst, eg_bytes=eg_bytes, eg_prio=eg_prio, eg_seq=eg_seq,
        eg_ctrl=eg_ctrl, eg_tsend=eg_tsend, eg_clamp=eg_clamp,
        eg_sock=eg_sock, eg_valid=eg_valid,
        n_overflow_dropped=state.n_overflow_dropped + overflow,
    )
    if guards is not None:
        guards = guards_plane.check_ingest(
            guards,
            occ_before=n_valid,
            occ_after=eg_valid.sum(axis=1, dtype=jnp.int32),
            incoming=incoming, overflow=overflow)
    out = (new_state,)
    if metrics is not None:
        out += (metrics._replace(
            drop_ring_full=metrics.drop_ring_full + overflow),)
    if guards is not None:
        out += (guards,)
    return out if len(out) > 1 else new_state


def chain_windows(state: NetPlaneState, params: NetPlaneParams,
                  rng_root: jax.Array, shift0, window0_ns, runahead_ns,
                  horizon_rel, stop_rel, max_windows: int = 64, *,
                  rr_enabled: bool = True, router_aqm: bool = False,
                  no_loss: bool = False,
                  faults: FaultArrays | None = None,
                  metrics: PlaneMetrics | None = None,
                  guards: GuardState | None = None,
                  hist: PlaneHistograms | None = None,
                  flightrec: FlightRecArrays | None = None,
                  workload=None, flows=None, compute=None, round0=0):
    """Advance consecutive scheduling windows ON DEVICE until one delivers.

    The device-resident analogue of the controller's window chain
    (`controller.rs:87-113`): the first window ([shift0-rebased start,
    +window0_ns)) runs unconditionally; afterwards, while a window
    delivered nothing and the device's next event stays below both
    `horizon_rel` (the earliest CPU-side event) and `stop_rel` (simulation
    end), the next window opens at that next event with length
    min(runahead_ns, stop_rel - start) — exactly the boundaries the CPU
    controller would pick, since runahead only changes at capture time and
    nothing is captured during an idle chain. One `lax.while_loop`, zero
    host round trips for delivery-free windows.

    `horizon_rel`/`stop_rel` are relative to the first window's start and
    must be pre-clamped to <= I32_MAX // 2 by the caller (the chain simply
    stops at the clamp and Python takes over).

    Every `window_step` presence switch threads through the while_loop
    carry with the same static-presence discipline as the step itself
    (docs/observability.md, docs/robustness.md): `metrics`, `guards`,
    `hist`, and `flightrec` pytrees accumulate across every chained
    window with zero added host syncs — the chain is audited per
    variant (`analysis/jaxpr_audit.py` `chain_windows[metrics]` /
    `[guards]` / `[workload]`) so a sync smuggled into the carry fails
    the build. `workload=(wl, ws)` additionally runs the traffic
    generator's `workload_step` after each chained window (its
    emission re-arms the next-event reduction, so a chain never sleeps
    through traffic the generator just queued); `round0` is the
    driver's window counter for `done_win` stamping. `flows=(ft, fs0)`
    threads the device flow plane (docs/robustness.md "Flow plane")
    through the carry the same way — its emission (retransmissions,
    delayed acks) re-arms the next-event reduction too, so an idle
    chain can never sleep through a pending retransmission; mutually
    exclusive with `workload` here (the scenario runner interleaves
    the two through `flow_recv`/`flow_emit` around the phase credits
    instead — workloads/runner.py). `compute=(ct, cs0)` threads the
    device compute plane (`tpu/compute.py`) through the carry the
    same way; it emits no traffic (service completions only gate
    phase credits in the runner's split-form loop), so it never
    re-arms the next-event reduction — an idle chain may sleep
    through a backlog draining, which is fine because nothing
    observes the backlog until the next delivery wakes the chain.

    Returns (state, delivered, off, next_rel, n_windows[, metrics']
    [, guards'][, hist'][, flightrec'][, ws'][, fs'][, cs']) —
    presence outputs appended in `window_step` order, the workload /
    flow / compute state last. `off` is the LAST window's start relative to the
    first window's start — `delivered` times and `next_rel` are
    relative to that last window's start.
    """
    if workload is not None and flows is not None:
        raise ValueError(
            "chain_windows composes workload= or flows=, not both: a "
            "workload riding a flow transport must interleave the "
            "phase credits between flow_recv and flow_emit, which is "
            "the scenario runner's split-form loop "
            "(workloads/runner.py)")
    if workload is not None:
        from ..workloads import device as _wdevice

        wl, ws0 = workload
    else:
        wl = ws0 = None
    if flows is not None:
        ft, fs0 = flows
    else:
        ft = fs0 = None
    if compute is not None:
        ctab, cs0 = compute
    else:
        ctab = cs0 = None

    def step(st, planes, shift, window_ns, ridx):
        m, g, h, fr, ws, fstate, cstate = planes
        out = window_step(st, params, rng_root, shift, window_ns,
                          rr_enabled=rr_enabled, router_aqm=router_aqm,
                          no_loss=no_loss, faults=faults,
                          metrics=m, guards=g, hist=h, flightrec=fr,
                          flows=(ft, fstate) if fstate is not None
                          else None,
                          compute=(ctab, cstate) if cstate is not None
                          else None)
        (st, delivered, next_ev), m, g, h, fr, fstate, cstate = \
            unpack_planes(out, metrics=m, guards=g, hist=h,
                          flightrec=fr, flows=fstate, compute=cstate)
        if fstate is not None:
            from . import flows as _flows_mod

            # the flow emission (retransmits / delayed acks) may have
            # re-armed an empty egress ring, exactly like the workload
            # emission below — and a pending RTO deadline must wake
            # the chain even when NOTHING is in flight (every packet
            # of a window lost): the deadline is a real future event,
            # relative to this window's end = window_ns + rel
            next_ev = jnp.minimum(
                next_ev, jnp.where(st.eg_valid.any(), window_ns,
                                   I32_MAX))
            rto_rel = _flows_mod.next_deadline_rel_ns(ft, fstate)
            # guard the add against the no-deadline sentinel: rel is
            # clamped <= I32_MAX//2 when a timer pends (window_ns <=
            # I32_MAX//4 by the spec budget), and the min below keeps
            # the sentinel lane's add in-range too — its sum is
            # discarded by the where, but the SL506 range proof
            # (analysis/ranges.py `chain_windows[flows]`) covers every
            # computed lane, not just the selected ones
            wake = jnp.where(rto_rel > I32_MAX // 2, I32_MAX,
                             jnp.int32(window_ns)
                             + jnp.minimum(rto_rel, I32_MAX // 2))
            next_ev = jnp.minimum(next_ev, wake)
        if ws is not None:
            wout = _wdevice.workload_step(wl, ws, st, delivered, ridx,
                                          window_ns, metrics=m, guards=g)
            if m is not None and g is not None:
                st, ws, m, g = wout
            elif m is not None:
                st, ws, m = wout
            elif g is not None:
                st, ws, g = wout
            else:
                st, ws = wout
            # the emission may have re-armed an empty egress ring: the
            # next pending event is then this window's end, exactly as
            # window_step would have reported had the packets been
            # queued before the step
            next_ev = jnp.minimum(
                next_ev, jnp.where(st.eg_valid.any(), window_ns,
                                   I32_MAX))
        return st, delivered, next_ev, (m, g, h, fr, ws, fstate, cstate)

    hs = jnp.minimum(jnp.int32(horizon_rel), jnp.int32(stop_rel))

    planes = (metrics, guards, hist, flightrec, ws0, fs0, cs0)
    state, delivered, next_ev, planes = step(
        state, planes, jnp.int32(shift0), jnp.int32(window0_ns),
        jnp.int32(round0))

    def keep_going(delivered, off, next_ev):
        # hs - off > 0 and both < I32_MAX//2, so no overflow anywhere —
        # no longer hand-reasoned: the SL506 range proof
        # (analysis/ranges.py `chain_windows`) closes the whole chain
        # loop's arithmetic by refining the carry intervals with THIS
        # predicate (`next_ev < hs - off` bounds off + next_ev below
        # I32_MAX inside the body, for all inputs in the registered
        # domains)
        return (~delivered["mask"].any()) & (next_ev < hs - off)

    def cond(c):
        _state, delivered, off, next_ev, n, _planes = c
        return keep_going(delivered, off, next_ev) & (n < max_windows)

    def body(c):
        st, _delivered, off, next_ev, n, planes = c
        off2 = off + next_ev
        window = jnp.minimum(jnp.int32(runahead_ns),
                             jnp.int32(stop_rel) - off2)
        st, delivered, next2, planes = step(st, planes, next_ev, window,
                                            jnp.int32(round0) + n)
        return (st, delivered, off2, next2, n + 1, planes)

    state, delivered, off, next_ev, n, planes = jax.lax.while_loop(
        cond, body,
        (state, delivered, jnp.int32(0), next_ev, jnp.int32(1), planes),
    )
    m, g, h, fr, ws, fstate, cstate = planes
    out = (state, delivered, off, next_ev, n)
    out += tuple(p for p in (m, g, h, fr) if p is not None)
    if workload is not None:
        out += (ws,)
    if flows is not None:
        out += (fstate,)
    if compute is not None:
        out += (cstate,)
    return out


_UNSET = object()


def unpack_planes(out, *, metrics=None, guards=None, hist=None,
                  flightrec=None, flows=_UNSET, compute=_UNSET,
                  n_lead=3):
    """Split a `window_step` (n_lead=3) or `ingest_rows` (n_lead=1)
    output into its lead values plus the presence-switch outputs, in
    the ONE declaration order both kernels append them — metrics,
    guards, hist, flightrec[, flows]. Pass the same presence pytrees
    the kernel call received: each non-None plane comes back as its
    output, each None stays None, so a driver writes

        (st, delivered, nxt), m, g, h, fr = unpack_planes(
            out, metrics=m, guards=g, hist=h, flightrec=fr)

    instead of hand-maintaining a per-site pop sequence (a mis-ordered
    pop swaps two pytrees silently until trace time — every window
    driver shares this one unpacker for the same reason they share
    `elastic.drive_chained_windows`).

    `flows` is the FlowState the kernel's ``flows=(ft, fs)`` pair
    carried (the tables are static). Passing it — even as None — adds
    a sixth slot to the return, so flow-plane drivers unpack
    ``(lead), m, g, h, fr, fs = unpack_planes(..., flows=fs)``;
    omitting it keeps the legacy five-slot shape. `compute` is the
    ComputeState of the kernel's ``compute=(ct, cs)`` pair and adds a
    further trailing slot the same way (kernel output order: flows
    then compute, both last)."""
    if type(out) is not tuple:
        # bare state: ingest_rows with no planes threaded returns the
        # NetPlaneState itself — which IS a (named)tuple, so the check
        # must be on the exact type, never isinstance
        out = (out,)
    lead, rest = out[:n_lead], list(out[n_lead:])
    want = [metrics, guards, hist, flightrec]
    if flows is not _UNSET:
        want.append(flows)
    if compute is not _UNSET:
        want.append(compute)
    planes = tuple(rest.pop(0) if p is not None else None
                   for p in want)
    if rest:
        raise TypeError(
            f"unpack_planes: {len(rest)} unclaimed kernel output(s) — "
            f"the presence arguments do not match the kernel call's")
    return (lead, *planes)


def compact_delivered(delivered: dict, cap: int):
    """Compress a [N, CI] delivered dict into fixed-[cap] columns for cheap
    device->host transfer: (count, dst, src, seq, sock, deliver_rel).

    The full delivered arrays are N*CI slots of which only a handful are
    usually due per window; pulling them raw costs a whole-array D2H
    transfer per round (the round-3 rung-3 timeout). A stable argsort on
    ~mask front-packs the due slots in row-major order — dst recovered from
    the flat index — so the host reads 5 short columns and a count. If
    count > cap the tail was truncated: callers must detect that and fall
    back to pulling the full arrays (it means ingress_cap-scale bursts;
    raise the compact cap)."""
    mask = delivered["mask"]
    N, CI = mask.shape
    flat = mask.reshape(-1)
    n = flat.sum(dtype=jnp.int32)
    idx = jnp.argsort(~flat, stable=True)[:cap]
    take = lambda a: a.reshape(-1)[idx]
    dst = (idx // CI).astype(jnp.int32)
    dst = jnp.where(take(mask), dst, -1)  # mark dead slots
    return (n, dst, take(delivered["src"]), take(delivered["seq"]),
            take(delivered["sock"]), take(delivered["deliver_rel"]))


def ingest_rows(state: NetPlaneState, dst: jax.Array, nbytes: jax.Array,
                prio: jax.Array, seq: jax.Array, ctrl: jax.Array,
                valid: jax.Array, send_rel: jax.Array | None = None,
                clamp_rel: jax.Array | None = None,
                sock: jax.Array | None = None, *,
                packed_sort: bool = True,
                gate_idle: bool = True,
                metrics: PlaneMetrics | None = None,
                guards: GuardState | None = None,
                hist: PlaneHistograms | None = None,
                flightrec: FlightRecArrays | None = None):
    """Append per-host batches ([N, K] arrays, row = emitting host) to the
    egress queues. The row-shaped twin of `ingest` for producers that are
    already host-major (on-device respawn loops, per-host socket emitters):
    no flat cross-host sort is needed — one row-wise merge sort appends
    each row's valid entries after the existing ones, in column order.

    `packed_sort` (static) selects the single-key merge: validity + column
    rank packed into one uint32, ONE array through the sorting network, and
    the payload columns permuted by the recovered rank — vs the 10-array
    variadic merge it replaces (kept as the reference path for the parity
    tests). `gate_idle` wraps the merge in a `lax.cond` on "any new valid
    entries", so windows that produce nothing pay one reduction instead of
    a full merge sort; both are bitwise no-ops on the result (rows are
    front-packed, so an entry-free merge is the identity — proven per
    build by the SL505 obligation `ingest_rows[gate_idle]`,
    analysis/condeq.py, docs/determinism.md "Branch gates are
    theorems").

    `metrics` (static presence) accumulates ring-overflow drops into
    `drop_ring_full` and switches the return to (state', metrics'); the
    drop delta is read off the state's own n_overflow_dropped counter, so
    the merge itself — and the simulation state — is untouched.

    `guards` (static presence, docs/robustness.md) appends append-
    conservation checking to the return, exactly like `ingest`: each
    row must gain (incoming valid - overflow) entries. Pure reads.

    `hist` (static presence, docs/observability.md "Distributions and
    the flight recorder") samples the post-append egress occupancy
    into the queue-depth histogram; `flightrec` records an `ingest`
    hop for every sampled appended packet. Both are pure reads over
    values the merge already materialized and append to the return
    after metrics/guards: (state'[, metrics'][, guards'][, hist']
    [, flightrec']). Its ops carry the named scope `ingest_rows`."""
    with jax.named_scope("ingest_rows"):
        N, CE = state.eg_dst.shape
        if send_rel is None:
            send_rel = jnp.zeros_like(seq)
        if clamp_rel is None:
            clamp_rel = jnp.full_like(seq, NO_CLAMP)
        if sock is None:
            sock = jnp.zeros_like(seq)

        cat = lambda a, b: jnp.concatenate([a, b], axis=1)

        def merge(state: NetPlaneState) -> NetPlaneState:
            valid_all = cat(state.eg_valid, valid)
            W = valid_all.shape[1]
            if packed_sort:
                # stable valid-first order == sort by (validity, column rank);
                # the rank rides in the key's low bits, so the single sorted
                # array IS the permutation
                rank = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (N, W))
                key = jax.lax.sort(_pack_rank_key(valid_all, rank, W),
                                   dimension=1, is_stable=True)
                perm = (key & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)[:, :CE]
                take = lambda a, b: _row_take(cat(a, b), perm)
                dst_m = take(state.eg_dst, dst)
                bytes_m = take(state.eg_bytes, nbytes)
                prio_m = take(state.eg_prio, prio)
                seq_m = take(state.eg_seq, seq)
                ctrl_m = take(state.eg_ctrl, ctrl)
                tsend_m = take(state.eg_tsend, send_rel)
                clamp_m = take(state.eg_clamp, clamp_rel)
                sock_m = take(state.eg_sock, sock)
                # every valid key sorts below every invalid one, so the
                # merged row is valid exactly on its first n_valid lanes
                n_valid = valid_all.sum(axis=1, dtype=jnp.int32)
                valid_m = rank[:, :CE] < n_valid[:, None]
                overflow = jnp.maximum(n_valid - CE, 0)
            else:
                inv = (~valid_all).astype(jnp.int32)
                # stable sort by validity alone: existing entries (columns
                # < CE, front-packed) stay ahead of the new ones, new entries
                # keep column order
                (_, dst_f, bytes_f, prio_f, seq_f, ctrl_f, tsend_f, clamp_f,
                 # shadowlint: disable=SL403 -- pre-diet variadic reference
                 sock_f, valid_f) = _row_sort(
                    inv, cat(state.eg_dst, dst), cat(state.eg_bytes, nbytes),
                    cat(state.eg_prio, prio), cat(state.eg_seq, seq),
                    cat(state.eg_ctrl, ctrl), cat(state.eg_tsend, send_rel),
                    cat(state.eg_clamp, clamp_rel), cat(state.eg_sock, sock),
                    valid_all, keys=1,
                )
                overflow = jnp.maximum(
                    valid_f.sum(axis=1, dtype=jnp.int32) - CE, 0)
                dst_m, bytes_m, prio_m = dst_f[:, :CE], bytes_f[:, :CE], \
                    prio_f[:, :CE]
                seq_m, ctrl_m, tsend_m = seq_f[:, :CE], ctrl_f[:, :CE], \
                    tsend_f[:, :CE]
                clamp_m, sock_m, valid_m = clamp_f[:, :CE], sock_f[:, :CE], \
                    valid_f[:, :CE]
            return state._replace(
                eg_dst=dst_m, eg_bytes=bytes_m, eg_prio=prio_m, eg_seq=seq_m,
                eg_ctrl=ctrl_m, eg_tsend=tsend_m, eg_clamp=clamp_m,
                eg_sock=sock_m, eg_valid=valid_m,
                n_overflow_dropped=state.n_overflow_dropped + overflow,
            )

        if not gate_idle:
            new_state = merge(state)
        else:
            new_state = jax.lax.cond(valid.any(), merge, lambda st: st, state)
        overflow_delta = new_state.n_overflow_dropped - state.n_overflow_dropped
        if guards is not None:
            guards = guards_plane.check_ingest(
                guards,
                occ_before=state.eg_valid.sum(axis=1, dtype=jnp.int32),
                occ_after=new_state.eg_valid.sum(axis=1, dtype=jnp.int32),
                incoming=valid.sum(axis=1, dtype=jnp.int32),
                overflow=overflow_delta)
        out = (new_state,)
        if metrics is not None:
            # overflow delta via the state counter: identical through both
            # gate branches (the idle branch's delta is zero by construction)
            out += (metrics._replace(
                drop_ring_full=metrics.drop_ring_full + overflow_delta),)
        if guards is not None:
            out += (guards,)
        if hist is not None:
            # queue-depth sample at the append point (post-merge egress
            # occupancy) — pure read, nothing feeds back
            out += (hist._replace(hist_qdepth=histo.accum_depth(
                hist.hist_qdepth,
                new_state.eg_valid.sum(axis=1, dtype=jnp.int32))),)
        if flightrec is not None:
            # `ingest` hop per sampled ACCEPTED packet, stamped with the
            # UPCOMING window's counter (appends ride between windows) and
            # the emission offset relative to its start. Overflow-dropped
            # batch entries never entered the ring, so they record no hop
            # — their loss is the aggregate drop_ring_full counter, and a
            # phantom `ingest` would read as "queued" to a trace reader.
            # Accepted = the first (CE - occupancy) valid entries per row,
            # exactly the prefix the merge keeps (new entries append after
            # the existing ones in column order).
            rows = jnp.broadcast_to(
                jnp.arange(N, dtype=jnp.int32)[:, None], valid.shape)
            samp = flightrec_mod.sample_mask(flightrec, rows, seq)
            valid_i = valid.astype(jnp.int32)
            new_rank = jnp.cumsum(valid_i, axis=1) - valid_i
            free = (jnp.int32(CE)
                    - state.eg_valid.sum(axis=1, dtype=jnp.int32))
            accepted = valid & (new_rank < free[:, None])
            flat = lambda a: a.reshape(-1)
            flightrec = flightrec_mod.record_events(
                flightrec,
                jnp.full((valid.size,), flightrec_mod.HOP_INGEST, jnp.int32),
                flat(rows), flat(seq), flat(dst), flat(send_rel),
                flat(accepted & samp))
            out += (flightrec,)
        return out if len(out) > 1 else new_state


# ---------------------------------------------------------------------------
# window_step sections. Each stage of the per-window pipeline is a named
# helper so (a) the profiler (`tpu/profiling.py`) can time every section in
# isolation with realistic inputs and (b) alternative forms (the packed
# sort diet) swap in per-section without touching the rest of the
# pipeline. window_step composes them; the section boundaries are exactly
# the numbered comments the monolithic body used.
# ---------------------------------------------------------------------------


def _refill_tokens(state: NetPlaneState, params: NetPlaneParams, shift_ns,
                   *, faults: FaultArrays | None = None):
    """Section 1b: lazy 1ms-interval token refill (`relay/token_bucket.rs`);
    the sub-ms remainder carries across rounds so short windows don't leak
    bandwidth. Returns (balance, tb_rem_ns).

    `faults` (static presence) applies per-host bandwidth degradation:
    the refill rate is divided by `bw_div` (the rate-proportional part
    of the capacity scales with it, the MTU burst term does not).
    `bw_div=1` is bitwise-identity with faults=None."""
    rate, cap = params.tb_rate, params.tb_cap
    if faults is not None:
        rate = jnp.maximum(rate // jnp.maximum(faults.bw_div, 1), 1)
        cap = rate + (params.tb_cap - params.tb_rate)
    rem_total = state.tb_rem_ns + (shift_ns % 1_000_000)
    elapsed_ms = (shift_ns // 1_000_000) + (rem_total // 1_000_000)
    tb_rem_ns = rem_total % 1_000_000
    # refill only up to the headroom, clamping elapsed BEFORE multiplying:
    # rate * elapsed_eff <= headroom + rate <= cap + rate, inside int32 for
    # any rate <= 2^30 - MTU (make_params guarantees it) — the naive
    # balance + rate*fill_ms wrapped negative for rates near 1e9 B/ms and
    # stalled every egress queue for one round. The headroom form of the
    # final clamp (min(u, c) == c - max(c - u, 0) for a non-negative
    # refund) keeps every intermediate interval-bounded: the SL506 range
    # proof (analysis/ranges.py, window_step entries) closes this whole
    # section as a theorem instead of this comment's relational argument.
    headroom = jnp.maximum(cap - state.tb_balance, 0)
    need_ms = (headroom + rate - 1) // rate
    elapsed_eff = jnp.minimum(elapsed_ms, need_ms)
    balance = cap - jnp.maximum(headroom - rate * elapsed_eff, 0)
    return balance, tb_rem_ns


def _qdisc_keys(state: NetPlaneState, params: NetPlaneParams, *,
                rr_enabled: bool):
    """Section 2a: per-slot qdisc sort keys. FIFO = packet priority;
    round-robin = virtual-finish counter per socket slot (the [N, CE, CE]
    pairwise rank tensors — the dominant per-window cost when N < CE^2,
    which is why all-FIFO callers compile with rr_enabled=False). Returns
    (qkey1, qkey2, rr_aux) with rr_aux = (rr_base, vtime) bookkeeping the
    RR advance needs later (None when rr_enabled=False)."""
    if not rr_enabled:
        return state.eg_prio, jnp.zeros_like(state.eg_sock), None
    S = RR_SOCK_SLOTS
    sock_slot = jnp.where(state.eg_valid, state.eg_sock % S, S - 1)
    # active sockets re-join at the current virtual time (start-time
    # fair queuing floor) so a returning socket gets its fair turn, not
    # a burst; rows with nothing queued reset to 0 (counters only mean
    # anything relative to each other, and the rebase below keeps every
    # value within ~CE of zero, so int32 never wraps)
    slot_onehot = sock_slot[:, :, None] == jnp.arange(S, dtype=jnp.int32)
    active = (slot_onehot & state.eg_valid[:, :, None]).any(axis=1)
    vtime = jnp.where(active, state.rr_sent, I32_MAX).min(axis=1)  # [N]
    vtime = jnp.where(active.any(axis=1), vtime, 0)
    rr_base = jnp.maximum(state.rr_sent, vtime[:, None])  # [N, S]
    same_sock = sock_slot[:, :, None] == sock_slot[:, None, :]
    both_valid = state.eg_valid[:, :, None] & state.eg_valid[:, None, :]
    earlier = state.eg_seq[:, None, :] < state.eg_seq[:, :, None]
    rr_rank = jnp.sum(same_sock & both_valid & earlier, axis=2,
                      dtype=jnp.int32)
    rr_key = _row_take(rr_base, sock_slot) + rr_rank
    rr_mode = params.qdisc_rr[:, None]
    qkey1 = jnp.where(rr_mode, rr_key, state.eg_prio)
    qkey2 = jnp.where(rr_mode, state.eg_sock, 0)
    return qkey1, qkey2, (rr_base, vtime)


def _egress_order(state: NetPlaneState, qkey1, qkey2, eg_tsend_rb,
                  eg_clamp_rb, *, rr_enabled: bool, packed_sort: bool):
    """Section 2b: the qdisc sort. Orders each egress row valid-first by
    (qkey1, qkey2). Packed form: ONE uint32 (validity | qkey1) key plus —
    only under RR, where socket ids break rr-key ties — qkey2, with the
    payload columns permuted afterwards; vs the 12-array variadic sort
    (kept as the parity-reference path). Returns the 9 sorted columns
    (prio, sock, dst, bytes, seq, ctrl, tsend, clamp, valid).

    FIFO packed rows additionally gate the sort on a cheap
    already-ordered check (the steady-state fast path): the leftover
    prefix left by `_compact_egress` is in (validity | priority) order
    already, and monotone-priority producers (the PHOLD respawn, the
    workload emitters — seq-derived priorities) append in order too,
    so most windows' rows arrive with a non-decreasing packed key. A
    stable sort of a non-decreasing key with the column-index tiebreak
    IS the identity, so both branches are bitwise-equal always — the
    gate can only change speed, never a bit (same contract as
    `ingest_rows`' gate_idle; proven structurally per build by SL505
    `_egress_order[fifo-ordered]`, analysis/condeq.py)."""
    if packed_sort:
        packed = _pack_valid_key(state.eg_valid, qkey1)
        if not rr_enabled:
            ordered = (packed[:, :-1] <= packed[:, 1:]).all()

            def ident(_):
                return (state.eg_prio, state.eg_sock, state.eg_dst,
                        state.eg_bytes, state.eg_seq, state.eg_ctrl,
                        eg_tsend_rb, eg_clamp_rb, state.eg_valid)

            def do_sort(packed):
                perm = _row_perm_sort(packed)
                take = lambda a: _row_take(a, perm)
                return (take(state.eg_prio), take(state.eg_sock),
                        take(state.eg_dst), take(state.eg_bytes),
                        take(state.eg_seq), take(state.eg_ctrl),
                        take(eg_tsend_rb), take(eg_clamp_rb),
                        take(state.eg_valid))

            return jax.lax.cond(ordered, ident, do_sort, packed)
        extra = (qkey2,) if rr_enabled else ()
        perm = _row_perm_sort(packed, *extra)
        take = lambda a: _row_take(a, perm)
        return (take(state.eg_prio), take(state.eg_sock),
                take(state.eg_dst), take(state.eg_bytes),
                take(state.eg_seq), take(state.eg_ctrl),
                take(eg_tsend_rb), take(eg_clamp_rb), take(state.eg_valid))
    inv = (~state.eg_valid).astype(jnp.int32)
    (_, _, _, eg_prio, eg_sock, eg_dst, eg_bytes, eg_seq, eg_ctrl,
     # shadowlint: disable=SL403 -- pre-diet variadic reference path
     eg_tsend, eg_clamp, eg_valid) = _row_sort(
        inv, qkey1, qkey2, state.eg_prio, state.eg_sock, state.eg_dst,
        state.eg_bytes, state.eg_seq, state.eg_ctrl, eg_tsend_rb,
        eg_clamp_rb, state.eg_valid, keys=3,
    )
    return (eg_prio, eg_sock, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend,
            eg_clamp, eg_valid)


def _token_gate(eg_valid, eg_bytes, balance):
    """Section 2c: prefix-sum token-bucket gate over the sorted egress.
    Returns (sendable, balance_after)."""
    cum = jnp.cumsum(jnp.where(eg_valid, eg_bytes, 0), axis=1)
    sendable = eg_valid & (cum <= balance[:, None])
    spent = jnp.where(sendable, eg_bytes, 0).sum(axis=1)
    return sendable, balance - spent


def _rr_advance(eg_sock, eg_valid, sendable, rr_aux):
    """Section 2d: advance the RR virtual-finish counters by packets
    pushed through, then rebase to the floor so counters stay bounded
    (per the dtype discipline)."""
    S = RR_SOCK_SLOTS
    rr_base, vtime = rr_aux
    sent_slot = jnp.where(eg_valid, eg_sock % S, S - 1)
    sent_per_sock = jnp.sum(
        (sent_slot[:, :, None] == jnp.arange(S, dtype=jnp.int32))
        & sendable[:, :, None], axis=1, dtype=jnp.int32)
    return rr_base - vtime[:, None] + sent_per_sock


def _loss_latency(state: NetPlaneState, params: NetPlaneParams, rng_root,
                  eg_dst, eg_ctrl, eg_tsend, eg_clamp, sendable, window_ns,
                  *, no_loss: bool, faults: FaultArrays | None = None):
    """Section 3: Bernoulli path-loss draw + latency lookup through the
    node-level tables (host -> node, then the [M, M] path matrices — vs a
    [N, N] host-pair gather whose per-element HBM cost dominated the step
    at 4k+ hosts). Returns (sent, lost, rng_counter', deliver_rel); with
    `faults` threaded (static presence) the return gains a `corrupt`
    mask after `lost`: (sent, lost, corrupt, rng_counter', deliver_rel).

    Fault handling here: (a) burst corruption — an extra Bernoulli drawn
    from an INDEPENDENT counter-based stream (host index offset by N, so
    the loss stream is untouched and a corruption schedule never changes
    which packets the base world loss-drops); control packets exempt,
    like path loss. (b) per-link latency degradation — `lat_mult` as an
    integer multiplier with the latency pre-clamped to the int32 window
    budget so the multiply can never wrap; `mult=1` is bitwise identity.
    """
    N, CE = eg_dst.shape
    host_idx = jnp.arange(N, dtype=jnp.int32)[:, None]
    dst_clipped = jnp.clip(eg_dst, 0, N - 1)
    node_src = params.host_node[:, None]  # [N, 1]
    node_dst = params.host_node[dst_clipped]  # [N, CE]
    if no_loss:
        # transport mode: the loss draw happened on the CPU at capture
        # (loss matrix is all zero) — skip the gather and the RNG entirely
        lost = jnp.zeros_like(sendable)
        sent = sendable
    else:
        counter = state.rng_counter[:, None] + jnp.arange(CE, dtype=jnp.int32)
        u = _pkt_uniform(rng_root, jnp.broadcast_to(host_idx, (N, CE)),
                         counter)
        p_loss = params.loss[jnp.broadcast_to(node_src, (N, CE)), node_dst]
        lost = sendable & (u < p_loss) & ~eg_ctrl
        sent = sendable & ~lost
    corrupt = None
    if faults is not None:
        counter2 = state.rng_counter[:, None] + jnp.arange(CE,
                                                           dtype=jnp.int32)
        u2 = _pkt_uniform(rng_root,
                          jnp.broadcast_to(host_idx + N, (N, CE)), counter2)
        corrupt = (sendable & ~lost & ~eg_ctrl
                   & (u2 < faults.corrupt_p[:, None]))
        sent = sent & ~corrupt
    # draws consumed only for slots that attempted transmission, keeping the
    # stream independent of queue occupancy beyond the sendable prefix
    rng_counter = state.rng_counter + sendable.sum(axis=1, dtype=jnp.int32)

    latency = params.latency_ns[jnp.broadcast_to(node_src, (N, CE)), node_dst]
    if faults is not None:
        mult = jnp.maximum(faults.lat_mult[
            jnp.broadcast_to(node_src, (N, CE)), node_dst], 1)
        degraded = jnp.minimum(latency, (I32_MAX // 2) // mult) * mult
        latency = jnp.where(mult > 1, degraded, latency)
    # send time + latency, but no earlier than the round barrier the packet
    # was sent under (`worker.rs:396-399`); NO_CLAMP means "this window's
    # end" (pure-device mode, where ingest and step share the window)
    clamp_eff = jnp.where(eg_clamp == NO_CLAMP, window_ns, eg_clamp)
    deliver_rel = jnp.maximum(eg_tsend + latency, clamp_eff)
    if faults is not None:
        return sent, lost, corrupt, rng_counter, deliver_rel
    return sent, lost, rng_counter, deliver_rel


def _compact_ingress(state: NetPlaneState, in_deliver, *, packed_sort: bool):
    """Section 4: compact surviving ingress, front-packed by deliver time
    for the scatter. Packed form: one uint32 (validity | sign-biased
    deliver) key + permutation; reference form: the 7-array variadic sort.
    Returns (deliver_c, src_c, seq_c, sock_c, bytes_c, valid_c,
    n_valid_in).

    The packed form gates the sort on an already-ordered check: after
    the first window, the surviving ingress is EXACTLY what
    `_release_due` (or the AQM keep-compaction) left — front-packed
    ascending by deliver, garbage lanes behind — and the window rebase
    is monotone, so the packed key arrives non-decreasing and the sort
    is the identity. A stable 1-key sort of a non-decreasing key with
    the column tiebreak IS the identity (equal keys keep column
    order), so the branches are bitwise-equal for every input — the
    gate trades a [N, CI] compare for the dominant steady-state row
    sort. Proven structurally per build (SL505
    `_compact_ingress[ordered]`: the sort-of-sorted rewrite + a
    selection witness, analysis/condeq.py)."""
    key_deliver = jnp.where(state.in_valid, in_deliver, I32_MAX)
    if packed_sort:
        packed = _pack_time_key(state.in_valid, key_deliver)
        ordered = (packed[:, :-1] <= packed[:, 1:]).all()

        def ident(_):
            return (key_deliver, state.in_src, state.in_seq,
                    state.in_sock, state.in_bytes, state.in_valid)

        def do_sort(packed):
            perm = _row_perm_sort(packed)
            take = lambda a: _row_take(a, perm)
            return (take(key_deliver), take(state.in_src),
                    take(state.in_seq), take(state.in_sock),
                    take(state.in_bytes), take(state.in_valid))

        (in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
         in_valid_c) = jax.lax.cond(ordered, ident, do_sort, packed)
    else:
        inv_in = (~state.in_valid).astype(jnp.int32)
        (_, in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
         # shadowlint: disable=SL403 -- pre-diet variadic reference path
         in_valid_c) = _row_sort(
            inv_in, key_deliver, state.in_src, state.in_seq, state.in_sock,
            state.in_bytes, state.in_valid, keys=2,
        )
    n_valid_in = in_valid_c.sum(axis=1).astype(jnp.int32)  # [N]
    return (in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
            in_valid_c, n_valid_in)


def _seq_row_perm(eg_seq):
    """[N, CE] seq-order position -> original column, per row: the stable
    rank of each slot within its row by (seq, column), from an
    [N, CE, CE] pairwise compare, inverted. The qdisc sort left rows in
    priority order, not seq order, and equal (dst, deliver) arrivals
    from one source must land by seq."""
    N, CE = eg_seq.shape
    col = jnp.arange(CE, dtype=jnp.int32)
    earlier = ((eg_seq[:, None, :] < eg_seq[:, :, None])
               | ((eg_seq[:, None, :] == eg_seq[:, :, None])
                  & (col[None, None, :] < col[None, :, None])))
    rank = jnp.sum(earlier, axis=2, dtype=jnp.int32)  # [N, CE]
    rows = jnp.arange(N, dtype=jnp.int32)[:, None]
    # rank is a permutation per row ((seq, col) pairs are distinct),
    # so the scatter inverts it: row_perm[n, rank[n, c]] = c
    return jnp.zeros((N, CE), jnp.int32).at[rows, rank].set(
        jnp.broadcast_to(col, (N, CE)))


def _routing_order(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel):
    """Bucketed routing, phase A: establish the deterministic global
    arrival order and sort the payload into it. The order the CPU
    plane's event queue imposes per destination is (deliver, src, seq);
    the legacy path realizes it as one flat 4-key variadic sort over
    [N*CE] slots. Here:

    - a row-local stable seq RANK (an [N, CE, CE] pairwise compare —
      CE is small, so this beats a row sort the same way the RR qdisc's
      rank tensors do) permutes each source row into seq order, so the
      flat slot index itself encodes the (src, seq) tiebreak;
    - ONE flat sort then orders by the routing key triple — destination
      bucket, sign-biased deliver time, flat slot index — and carries
      the three payload columns (seq, sock, bytes), row-permuted like
      the keys, as its payload: the deliver column is recovered from its
      key and the source row from the slot index, so the arrival-ordered
      streams come out of the sort itself. On a TPU a flat gather costs
      5-10 ns per index and the sort far less
      (`tools/bench_route_place.py`);
    - each bucket's [start, count) segment of the sorted sequence comes
      from a blocked search of the bucket ids over the sorted keys
      (`_segment_bounds`: a compare-reduce against every 128th row
      head, then two [N + 1, 128] row gathers, each consumed by a
      compare-reduce — vs the binary search's 20 scalar gathers of
      N + 1 indices at B = 524,288, and vs an O(B) histogram
      scatter-add).

    Non-sent slots (and any out-of-domain dst) route to the sentinel
    bucket N, which sorts last and is never placed. Returns (streams —
    the arrival-ordered (src, seq, sock, bytes, deliver) columns, each
    [B], offsets/counts [N] — each bucket's segment of the sorted
    order)."""
    N, CE = eg_dst.shape
    B = N * CE
    row_perm = _seq_row_perm(eg_seq)
    take = lambda a: _row_take(a, row_perm).reshape(-1)
    # the bucket is elementwise in (sent, dst), so it is built before the
    # row permutation: one take, not two
    bucket = jnp.where(sent & (eg_dst >= 0) & (eg_dst < N), eg_dst, N)
    deliver_key = take(deliver_rel).astype(jnp.uint32) ^ _SIGN32
    pos = jnp.arange(B, dtype=jnp.int32)
    # (dst, deliver, pos) is a TOTAL order (pos is distinct), so the
    # unstable sort with pos promoted to a key returns exactly the
    # stable 2-key permutation — and skips the stable-sort machinery,
    # measurably cheaper through XLA:CPU's comparator path
    o_dst, o_key, o_pos, o_seq, o_sock, o_bytes = jax.lax.sort(
        (take(bucket), deliver_key, pos, take(eg_seq), take(eg_sock),
         take(eg_bytes)),
        dimension=0, is_stable=False, num_keys=3)
    o_deliver = (o_key ^ _SIGN32).astype(jnp.int32)
    bounds = _segment_bounds(o_dst, N)
    offsets, counts = bounds[:-1], bounds[1:] - bounds[:-1]
    streams = (o_pos // CE, o_seq, o_sock, o_bytes, o_deliver)
    return streams, offsets, counts


def _routing_rank(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel,
                  n_valid_in, ingress_cap: int):
    """Section 5a (packed): counting placement over the bucketed order.
    Each destination row accepts the first `take` items of its bucket's
    sorted segment — exactly the items whose in-bucket rank fits the
    row's free slots — so placement reduces to per-bucket [N] arithmetic
    over the segment bounds; no per-item destination indices are ever
    materialized. Returns (streams, offsets, take [N], overflow [N]);
    `streams` are `_routing_order`'s arrival-ordered payload columns."""
    streams, offsets, counts = _routing_order(
        sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel)
    # per-bucket arithmetic is exact: occupancy never exceeds capacity,
    # so free = CI - n_valid >= 0; arrivals past the free slots drop
    take_n = jnp.minimum(counts, jnp.int32(ingress_cap) - n_valid_in)
    overflow = jnp.maximum(counts + n_valid_in - ingress_cap, 0)
    return streams, offsets, take_n, overflow


def _routing_place(streams, offsets, take_n, n_valid_in, in_deliver_c,
                   in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c):
    """Section 5b (packed): land the arrival-ordered streams with ONE
    gather of stacked [B, 5] stream rows — no flat scatters at all.
    Each merged ingress row is a select between its existing entries
    and its bucket's contiguous segment of the stream. The gather reads
    the streams alone: one over the streams concatenated with the
    ingress rows compiles to about five times the code
    (`tools/bench_route_place.py`), and generated code counts in the
    device's memory."""
    CI = in_src_c.shape[1]
    B = streams[0].shape[0]
    ci_col = jnp.arange(CI, dtype=jnp.int32)[None, :]
    nv = n_valid_in[:, None]
    append = (ci_col >= nv) & (ci_col < nv + take_n[:, None])
    # append lane c of row d reads stream slot offsets[d] + (c - nv[d]);
    # non-append lanes keep the base value (compaction garbage included,
    # exactly like the reference scatters, which never touch them)
    stream_idx = jnp.clip(offsets[:, None] + ci_col - nv, 0, B - 1)
    arrivals = jnp.stack(streams, axis=1)[stream_idx]  # [N, CI, 5]
    bases = (in_src_c, in_seq_c, in_sock_c, in_bytes_c,
             jnp.where(in_valid_c, in_deliver_c, I32_MAX))
    return (*(jnp.where(append, arrivals[..., k], b)
              for k, b in enumerate(bases)),
            append | in_valid_c)


def _routing_rank_legacy(sent, eg_dst, eg_seq, eg_bytes, eg_sock,
                         deliver_rel, n_valid_in, ingress_cap: int):
    """Section 5a (reference): the pre-diet flat variadic sort — every
    payload column rides the 4-key comparator network — plus the grouped
    scatter-append ranks. Kept compiled-in under `packed_sort=False` as
    the bitwise parity reference for the bucketed path."""
    N, CE = eg_dst.shape
    host_idx = jnp.arange(N, dtype=jnp.int32)[:, None]
    flat_sent = sent.reshape(-1)
    flat_dst = jnp.where(flat_sent, eg_dst.reshape(-1), N)  # N = "nowhere"
    (o_dst, o_deliver, o_src, o_seq, o_bytes, o_sock,
     # shadowlint: disable=SL403 -- pre-diet variadic reference path
     o_sent) = jax.lax.sort(
        (flat_dst, deliver_rel.reshape(-1),
         jnp.broadcast_to(host_idx, (N, CE)).reshape(-1),
         eg_seq.reshape(-1), eg_bytes.reshape(-1), eg_sock.reshape(-1),
         flat_sent),
        dimension=0, is_stable=True, num_keys=4,
    )
    flat_idx, ok, overflowed = _scatter_append(o_dst, o_sent, n_valid_in,
                                               ingress_cap, N)
    return (flat_idx, ok, o_deliver, o_src, o_seq, o_bytes, o_sock,
            overflowed)


def _routing_place_legacy(flat_idx, ok, o_deliver, o_src, o_seq, o_bytes,
                          o_sock, in_deliver_c, in_src_c, in_seq_c,
                          in_sock_c, in_bytes_c, in_valid_c):
    """Section 5b (reference): per-column scatters from the sorted
    payload of `_routing_rank_legacy`."""
    N, CI = in_src_c.shape

    def scatter(buf, vals):
        return buf.reshape(-1).at[flat_idx].set(
            vals, mode="drop").reshape(N, CI)

    in_src_m = scatter(in_src_c, o_src)
    in_seq_m = scatter(in_seq_c, o_seq)
    in_sock_m = scatter(in_sock_c, o_sock)
    in_bytes_m = scatter(in_bytes_c, o_bytes)
    in_deliver_m = scatter(
        jnp.where(in_valid_c, in_deliver_c, I32_MAX), o_deliver
    )
    # non-ok slots carry an out-of-bounds flat_idx, so only accepted
    # arrivals flip their slot valid
    in_valid_m = scatter(in_valid_c, jnp.ones_like(ok))
    return (in_src_m, in_seq_m, in_sock_m, in_bytes_m, in_deliver_m,
            in_valid_m)


def _route_scatter(sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
                   in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
                   in_valid_c, n_valid_in, *, packed_sort: bool = True):
    """Section 5: route sent packets into destination ingress queues,
    in the deterministic per-destination (deliver, src, seq) insertion
    order the CPU plane's event queue imposes.

    Two implementations, bitwise-identical for in-domain inputs
    (dst in [0, N), the only thing callers produce):

    - `packed_sort=True` (default): BUCKETED counting placement — dst is
      a bounded key, so the rank computation is one diet flat sort over
      (bucket, deliver, slot-index) plus blocked-search bucket bounds,
      and the payload columns land via one fused stacked gather
      (`_routing_rank` / `_routing_place`). A hypothetical sent packet
      with an out-of-range dst lands in the not-placeable bucket here;
      the reference path drops it through an out-of-bounds scatter
      index instead (same state, the overflow counter may differ for
      that impossible input).
    - `packed_sort=False`: the pre-diet flat 4-key variadic sort, the
      parity-test reference.

    Returns the merged ingress columns + per-host overflow."""
    CI = in_src_c.shape[1]
    if packed_sort:
        with jax.named_scope("routing_rank"):
            streams, offsets, take_n, overflowed = _routing_rank(
                sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel,
                n_valid_in, CI)
        with jax.named_scope("routing_place"):
            merged = _routing_place(
                streams, offsets, take_n, n_valid_in, in_deliver_c,
                in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c)
        return (*merged, overflowed)
    with jax.named_scope("routing_rank"):
        (flat_idx, ok, o_deliver, o_src, o_seq, o_bytes, o_sock,
         overflowed) = _routing_rank_legacy(
            sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
            n_valid_in, CI)
    with jax.named_scope("routing_place"):
        merged = _routing_place_legacy(
            flat_idx, ok, o_deliver, o_src, o_seq, o_bytes, o_sock,
            in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
            in_valid_c)
    return (*merged, overflowed)


def _release_due(in_deliver_m, in_src_m, in_seq_m, in_sock_m, in_bytes_m,
                 in_valid_m, window_ns, *, packed_sort: bool = True):
    """Section 5b (direct mode): split the merged ingress into this
    window's due deliveries and the surviving queue. One sort serves both:
    not-due first keyed by deliver time keeps the survivors front-packed;
    the due block lands at the row tail in deterministic (deliver_t, src,
    seq) presentation order. The packed form fuses the (is_due, deliver)
    key pair into ONE uint32 via modular subtraction — is_due is exactly
    `deliver < window_ns`, so `biased(deliver) - biased(window_ns)` in
    wrapping uint32 arithmetic sends not-due entries to [0, ..) and due
    entries to the wrapped top of the range, each ascending in deliver:
    precisely the (is_due, deliver) composite order — and carries the
    column index through the now-total-order unstable sort instead of
    the payload columns (the deliver column itself is recovered from
    the wrapped key by adding the bias back). Returns (delivered dict,
    due, surviving ingress columns)."""
    in_deliver_key = jnp.where(in_valid_m, in_deliver_m, I32_MAX)
    due = in_valid_m & (in_deliver_key < window_ns)
    is_due = due.astype(jnp.int32)
    if packed_sort:
        N, CI = due.shape
        col = jnp.broadcast_to(jnp.arange(CI, dtype=jnp.int32), (N, CI))
        w_bias = jnp.int32(window_ns).astype(jnp.uint32) ^ _SIGN32
        wkey = (in_deliver_key.astype(jnp.uint32) ^ _SIGN32) - w_bias
        # (wkey, src, seq, col) is total (col distinct), so the
        # unstable 4-key sort equals the stable (is_due, deliver, src,
        # seq) sort the reference path computes
        (wkey_s, d_src, d_seq, perm) = jax.lax.sort(
            (wkey, in_src_m, in_seq_m, col),
            dimension=1, is_stable=False, num_keys=4,
        )
        d_t = ((wkey_s + w_bias) ^ _SIGN32).astype(jnp.int32)
        take = lambda a: _row_take(a, perm)
        d_sock, d_bytes = take(in_sock_m), take(in_bytes_m)
        d_valid = take(in_valid_m)
        # `due` is elementwise in (valid, deliver key), and d_t is the
        # permuted deliver key exactly, so no take is needed for it
        d_due = d_valid & (d_t < window_ns)
    else:
        (_, d_t, d_src, d_seq, d_sock, d_bytes, d_due,
         # shadowlint: disable=SL403 -- pre-diet variadic reference path
         d_valid) = _row_sort(
            is_due, in_deliver_key, in_src_m,
            in_seq_m, in_sock_m, in_bytes_m, due, in_valid_m, keys=4,
        )
    delivered = {
        "mask": d_due, "src": d_src, "seq": d_seq, "sock": d_sock,
        "bytes": d_bytes, "deliver_rel": d_t,
    }
    in_valid_new = d_valid & ~d_due
    in_deliver_new = jnp.where(in_valid_new, d_t, I32_MAX)
    return (delivered, due, in_deliver_new, d_src, d_seq, d_sock, d_bytes,
            in_valid_new)


def _compact_egress(eg_prio, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend,
                    eg_clamp, eg_sock, eg_valid_left, *, packed_sort: bool):
    """Section 6: compact leftover egress so rows stay front-packed for
    ingest. Packed form: one uint32 (validity | priority-sentinel) key +
    permutation; reference form: the 10-array variadic sort."""
    eg_prio_left = jnp.where(eg_valid_left, eg_prio, I32_MAX)
    if packed_sort:
        perm = _row_perm_sort(_pack_time_key(eg_valid_left, eg_prio_left))
        take = lambda a: _row_take(a, perm)
        return (take(eg_prio_left), take(eg_dst), take(eg_bytes),
                take(eg_seq), take(eg_ctrl), take(eg_tsend),
                take(eg_clamp), take(eg_sock), take(eg_valid_left))
    (_, eg_prio_c, eg_dst_c, eg_bytes_c, eg_seq_c, eg_ctrl_c, eg_tsend_c,
     # shadowlint: disable=SL403 -- pre-diet variadic reference path
     eg_clamp_c, eg_sock_c, eg_valid_c) = _row_sort(
        (~eg_valid_left).astype(jnp.int32), eg_prio_left, eg_dst, eg_bytes,
        eg_seq, eg_ctrl, eg_tsend, eg_clamp, eg_sock, eg_valid_left, keys=2,
    )
    return (eg_prio_c, eg_dst_c, eg_bytes_c, eg_seq_c, eg_ctrl_c,
            eg_tsend_c, eg_clamp_c, eg_sock_c, eg_valid_c)


def _accumulate_metrics(metrics: PlaneMetrics, state: NetPlaneState,
                        sent, lost, due, overflowed, delivered,
                        in_valid_m, router_dropped_delta,
                        fault_dropped_delta, eg_bytes) -> PlaneMetrics:
    """Section 8 (telemetry, compiled in only when a metrics pytree is
    threaded): pure jnp adds over values the step already materialized.
    Nothing here feeds back into simulation state — the parity tests in
    tests/test_telemetry.py pin that metrics-on == metrics-off bitwise —
    and nothing reads back to the host (the no-host-sync rule,
    docs/observability.md)."""
    sent_n = sent.sum(axis=1, dtype=jnp.int32)
    due_n = due.sum(axis=1, dtype=jnp.int32)
    return PlaneMetrics(
        pkts_out=metrics.pkts_out + sent_n,
        bytes_out=metrics.bytes_out
        + jnp.where(sent, eg_bytes, 0).sum(axis=1, dtype=jnp.int32),
        pkts_in=metrics.pkts_in + due_n,
        bytes_in=metrics.bytes_in
        + jnp.where(delivered["mask"], delivered["bytes"], 0)
        .sum(axis=1, dtype=jnp.int32),
        drop_ring_full=metrics.drop_ring_full + overflowed,
        drop_qdisc=metrics.drop_qdisc + router_dropped_delta,
        drop_loss=metrics.drop_loss
        + lost.sum(axis=1, dtype=jnp.int32),
        drop_fault=metrics.drop_fault + fault_dropped_delta,
        retransmits=metrics.retransmits,
        # high-water marks at the PEAK points: egress occupancy entering
        # the window (ingest already ran), ingress after this window's
        # arrivals merged but before the due release
        max_eg_depth=jnp.maximum(
            metrics.max_eg_depth,
            state.eg_valid.sum(axis=1, dtype=jnp.int32)),
        max_in_depth=jnp.maximum(
            metrics.max_in_depth,
            in_valid_m.sum(axis=1, dtype=jnp.int32)),
        windows=metrics.windows + 1,
        events=metrics.events + sent_n.sum() + due_n.sum(),
        sort_slots=metrics.sort_slots
        + state.eg_valid.sum(dtype=jnp.int32)
        + state.in_valid.sum(dtype=jnp.int32),
    )


def window_step(state: NetPlaneState, params: NetPlaneParams, rng_root: jax.Array,
                shift_ns: jax.Array, window_ns: jax.Array, *,
                rr_enabled: bool = True, router_aqm: bool = False,
                no_loss: bool = False, packed_sort: bool = True,
                kernel: str = "xla",
                faults: FaultArrays | None = None,
                metrics: PlaneMetrics | None = None,
                guards: GuardState | None = None,
                hist: PlaneHistograms | None = None,
                flightrec: FlightRecArrays | None = None,
                flows=None, compute=None):
    """Advance one scheduling round [t, t + window_ns).

    `rr_enabled` is a static (trace-time) switch: False compiles the
    FIFO-only qdisc without the RR rank/one-hot tensors — use it when no
    host configures round-robin (e.g. the integrated DeviceTransport,
    where the CPU NIC owns qdisc ordering). The RR path materializes
    [N, CE, CE] pairwise tensors, which DOMINATE the per-window cost
    whenever N < CE^2; callers with all-FIFO configs should pass False.

    `router_aqm` (static) switches the destination side from direct
    due-release to the full inbound pipeline (`host.rs:810-865`): router
    CoDel -> down-bandwidth relay -> delivery, via the fused micro-step
    kernel in `tpu.codel.router_drain`. In this mode a packet's stored
    time is its ARRIVAL at the destination router; delivery happens when
    the relay forwards it (same instant when tokens allow, later when the
    down-bw bucket or CoDel interferes), and CoDel may drop it instead
    (counted in state.router.dropped). The CPU relay's bootstrap-period
    rate-limit bypass is not modeled on device.

    `no_loss` (static) compiles out the loss draw + loss-table gather for
    callers whose loss matrix is all zero (the integrated DeviceTransport,
    where the CPU drew loss at capture). rng_counter still advances so
    state stays bitwise-comparable with a loss-enabled run.

    `packed_sort` (static) selects the packed-key sort diet for the row
    sorts (sections 2b, 4, 5b-AQM, 6) AND the bucketed counting
    placement for the flat routing stage (section 5, `_routing_rank` /
    `_routing_place`) — bitwise-identical ordering, far fewer arrays
    through the comparator networks; False compiles the original
    variadic sorts (the parity-test reference). `kernel` accepts "xla"
    alone: the step has one implementation, and the argument stays only
    for callers that still name it.

    `metrics` (static presence switch) threads the telemetry counters
    (`telemetry/metrics.PlaneMetrics`) through the step: per-host
    traffic/drop/depth counters and per-window scalars accumulate with
    pure jnp adds over values the step already materialized — zero extra
    host syncs, donation-compatible, and bitwise-invisible to the
    simulation state (tests/test_telemetry.py). With metrics=None
    (default) the telemetry section is compiled out entirely.

    `faults` (static presence switch) threads the fault plane
    (`faults/plane.FaultArrays`, docs/robustness.md): crashed /
    link-down hosts stop transmitting (their queued egress drops) and
    stop accepting new routing (packets toward them drop), per-link
    latency multiplies, per-host egress bandwidth divides, and burst
    corruption applies an extra Bernoulli drop from an independent
    counter stream. All fault drops accumulate in `n_fault_dropped`
    (and the telemetry `drop_fault` bucket), never in the loss-sample
    counter. With faults=None (default) every fault branch is compiled
    out — bitwise-identical to the pre-fault plane — and neutral masks
    (`neutral_faults`) are bitwise-identity too (tests/test_faults.py).

    `guards` (static presence switch, docs/robustness.md) threads the
    runtime invariant plane (`guards/plane.GuardState`): conservation
    laws, ring structure, packed-key bit budget, RNG monotonicity, and
    the virtual-clock check accumulate per-host violation bitmasks with
    pure jnp compares over values the step already materialized —
    nothing raises inside jit, nothing feeds back into simulation
    state, and guards=None compiles the section out entirely (bitwise-
    identical; pinned by tests/test_guards.py).

    `hist` (static presence switch, docs/observability.md
    "Distributions and the flight recorder") threads the log2-bucketed
    `PlaneHistograms`: delivery latency (deliver - send, attributed to
    the destination), egress-queue sojourn (attributed to the source),
    and a per-window queue-depth sample accumulate with pure jnp
    one-hot sums / int32 scatter-adds over values the step already
    materialized — bitwise-invisible to simulation state, metrics, and
    guards (tests/test_flightrec.py). hist=None compiles the section
    out.

    `flightrec` (static presence switch, same doc) threads the sampled
    flight recorder (`telemetry/flightrec.py`): packets whose
    (src, seq) hashes into the seeded 1/K sampling stream record their
    per-hop events (routed, delivered, dropped-with-reason, AQM
    verdict) into the device-side trace ring, drained asynchronously
    at harvest boundaries. The sampling draw is an independent
    counter-based stream (like fault corruption), so recording never
    perturbs the simulation.

    `flows` (static presence switch, docs/robustness.md "Flow plane")
    threads the device flow plane as a ``(FlowTables, FlowState)``
    pair (`tpu/flows.py`): this window's deliveries feed per-flow
    cumulative-ack / in-order-credit processing, expired RTO deadlines
    rewind go-back-N with exponential backoff, and the window's
    emissions (retransmissions + delayed acks) append through the
    normal ingest path — ordinary packets, visible to every other
    plane. Unlike the observability planes this one legitimately
    WRITES sim state, but only the egress append columns + the
    overflow counter (the SL501 append-only obligation
    `window_step[flows]`, same theorem as the workload generator);
    threading tables whose flows are all inactive is bitwise-inert
    (tests/test_flows.py). flows=None compiles the section out. The
    returned state's next_event was
    reduced BEFORE the flow emission; chained callers re-arm it like
    the workload emission (`chain_windows`).

    `compute` (static presence switch, docs/workloads.md "Serving
    load & the compute plane") threads the device compute plane as a
    ``(ComputeTables, ComputeState)`` pair (`tpu/compute.py`): this
    window's deliveries feed each host's bounded-FIFO service station
    (busy-until clock, closed-form completion times, queueing-delay /
    sojourn histograms). Pure reads over the delivered dict the step
    already materialized; writes ONLY the ComputeState' appended last
    — the SL501 full-invisibility obligation `window_step[compute]`
    proves no compute taint reaches the lead outputs. The
    delivery-AND-service phase coupling lives in the scenario runner
    (`compute.gate_credits`), never here. compute=None compiles the
    section out.

    `shift_ns` = this window's start minus the previous window's start;
    stored relative times are rebased by it. Returns
    (state', delivered, next_event_rel) — plus metrics', guards',
    hist', and/or flightrec' appended in that order when the
    respective pytrees were passed (the flow plane's FlowState', when
    threaded, appends next; the compute plane's ComputeState'
    appends last) — where `delivered` is a dict of
    [N, CI] arrays masked by delivered['mask'] (packets that arrived
    within this window, in deterministic (deliver_t, src, seq) order
    per host) and `next_event_rel` is the min pending delivery time
    relative to the new window start (INT32_MAX when idle).
    """
    if kernel != "xla":
        raise ValueError(f"unknown plane kernel {kernel!r}: the window "
                         "step has one kernel, 'xla'")
    N, CE = state.eg_dst.shape

    # Each numbered section runs under a `jax.named_scope`, named after
    # `tpu/profiling.py`'s section where one exists. The scope rides the
    # ops' HLO metadata (`op_name`) into a profiler trace, where
    # `benchmark/scopes.py` adds up device time per section; it adds
    # no equation and no op.

    # --- 1. rebase clocks + refill token buckets -----------------------
    with jax.named_scope("rebase_refill"):
        in_deliver = jnp.where(state.in_valid, state.in_deliver_rel - shift_ns,
                               I32_MAX)
        balance, tb_rem_ns = _refill_tokens(state, params, shift_ns,
                                            faults=faults)
        rt = codel.rebase_router_state(state.router, shift_ns, params.dn_rate,
                                       params.dn_cap)

    # --- 2. egress: qdisc order, token-bucket gate ----------------------
    # Two qdiscs (`network_interface.c:205-303`, `QDiscMode`): FIFO sends
    # valid-first by ascending packet priority; round-robin interleaves
    # emitting sockets, taking one packet from each in turn (FIFO within a
    # socket by per-source seq, which is monotone in emission order).
    # Send times / clamps of leftover packets were taken relative to the
    # window they were ingested in; rebase them too.
    with jax.named_scope("egress_order"):
        eg_tsend_rb = jnp.where(state.eg_valid, state.eg_tsend - shift_ns, 0)
        eg_clamp_rb = jnp.where(
            state.eg_valid & (state.eg_clamp != NO_CLAMP),
            state.eg_clamp - shift_ns, state.eg_clamp,
        )
        qkey1, qkey2, rr_aux = _qdisc_keys(state, params,
                                           rr_enabled=rr_enabled)
        (eg_prio, eg_sock, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend,
         eg_clamp, eg_valid) = _egress_order(
            state, qkey1, qkey2, eg_tsend_rb, eg_clamp_rb,
            rr_enabled=rr_enabled, packed_sort=packed_sort)
        if faults is not None:
            # 2f. a crashed / link-down host transmits nothing: its queued
            # egress drops HERE, before the token gate (dead hosts spend
            # no bandwidth), counted once per slot — the slots leave the
            # queue, so a multi-window outage never double-counts
            up_src = (faults.host_alive & faults.link_up)[:, None]
            fault_purged = eg_valid & ~up_src
            eg_valid = eg_valid & up_src
        sendable, balance = _token_gate(eg_valid, eg_bytes, balance)
        rr_sent = (_rr_advance(eg_sock, eg_valid, sendable, rr_aux)
                   if rr_enabled else state.rr_sent)

    # --- 3. loss sampling + latency lookup ------------------------------
    with jax.named_scope("loss_latency"):
        if faults is not None:
            sent, lost, corrupt, rng_counter, deliver_rel = _loss_latency(
                state, params, rng_root, eg_dst, eg_ctrl, eg_tsend, eg_clamp,
                sendable, window_ns, no_loss=no_loss, faults=faults)
            # 3f. routing toward a crashed / link-down destination drops (the
            # fault withdraws the route); packets already in the dst's ingress
            # ring are untouched — the crash does not reach into the wire
            up = faults.host_alive & faults.link_up
            dst_ok = up[jnp.clip(eg_dst, 0, N - 1)] & (eg_dst >= 0) \
                & (eg_dst < N)
            blocked_dst = sent & ~dst_ok & (eg_dst >= 0) & (eg_dst < N)
            sent = sent & dst_ok
            # per-host fault-drop attribution: purge + corruption to the
            # SOURCE (its packets died on its own NIC), routing blocks to
            # the DESTINATION (the crash that ate them is the dst's)
            fault_drops = (
                fault_purged.sum(axis=1, dtype=jnp.int32)
                + corrupt.sum(axis=1, dtype=jnp.int32)
                + jnp.zeros((N,), jnp.int32).at[
                    jnp.clip(eg_dst, 0, N - 1).reshape(-1)].add(
                    blocked_dst.reshape(-1), mode="drop")
            )
        else:
            sent, lost, rng_counter, deliver_rel = _loss_latency(
                state, params, rng_root, eg_dst, eg_ctrl, eg_tsend, eg_clamp,
                sendable, window_ns, no_loss=no_loss)

    # --- 4 + 5. compact surviving ingress, then route sent packets into
    # destination ingress queues. Routing happens BEFORE the due check
    # so a packet whose deliver time falls inside this window
    # (integrated transport: sent last round, clamped to this window's
    # start) is released THIS round, matching the CPU plane's
    # push-then-execute ordering.
    with jax.named_scope("ingress_compact"):
        (in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
         in_valid_c, n_valid_in) = _compact_ingress(
            state, in_deliver, packed_sort=packed_sort)
    (in_src_m, in_seq_m, in_sock_m, in_bytes_m, in_deliver_m,
     in_valid_m, overflowed) = _route_scatter(
        sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
        in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
        in_valid_c, n_valid_in, packed_sort=packed_sort)
    CI = in_src_m.shape[1]

    # --- 5b. destination side: release what this window hands the hosts --
    if router_aqm:
        with jax.named_scope("codel_drain"):
            # Full inbound pipeline: stored times are router-arrival times.
            # FIFO order at the router = (arrival, src, seq), the same order
            # the CPU plane's event queue feeds route_incoming_packet.
            inv_m = (~in_valid_m).astype(jnp.int32)
            arr_key = jnp.where(in_valid_m, in_deliver_m, I32_MAX)
            (_, arr_s, src_s2, seq_s2, sock_s2, bytes_s2, valid_s2) = _row_sort(
                inv_m, arr_key, in_src_m, in_seq_m, in_sock_m, in_bytes_m,
                in_valid_m, keys=4,
            )
            rt2, rstatus, r_dt, co_mask, co_t, c_idx = codel.router_drain(
                arr_s, bytes_s2, window_ns, params.dn_rate, params.dn_cap, rt,
            )
            # a row entry cached at window end leaves the queue: its identity
            # moves into the router scalars until the relay resumes
            new_cached = c_idx >= 0
            ci = jnp.clip(c_idx, 0, CI - 1)[:, None]
            take = lambda a: _row_take(a, ci)[:, 0]
            rt2 = rt2._replace(
                cached_src=jnp.where(new_cached, take(src_s2), rt.cached_src),
                cached_seq=jnp.where(new_cached, take(seq_s2), rt.cached_seq),
                cached_sock=jnp.where(new_cached, take(sock_s2), rt.cached_sock),
            )
            # delivered = forwarded row entries + (maybe) the prior window's
            # relay-cached packet, presented in (deliver_t, src, seq) order
            fwd_rows = rstatus == codel.STATUS_DELIVERED
            d_mask0 = jnp.concatenate([fwd_rows, co_mask[:, None]], axis=1)
            d_src0 = jnp.concatenate([src_s2, rt.cached_src[:, None]], axis=1)
            d_seq0 = jnp.concatenate([seq_s2, rt.cached_seq[:, None]], axis=1)
            d_sock0 = jnp.concatenate([sock_s2, rt.cached_sock[:, None]], axis=1)
            d_bytes0 = jnp.concatenate([bytes_s2, rt.cached_bytes[:, None]],
                                       axis=1)
            d_t0 = jnp.concatenate(
                [jnp.where(fwd_rows, r_dt, I32_MAX),
                 jnp.where(co_mask, co_t, I32_MAX)[:, None]], axis=1)
            (_, d_t, d_src, d_seq, d_sock, d_bytes, d_due) = _row_sort(
                (~d_mask0).astype(jnp.int32), d_t0, d_src0, d_seq0, d_sock0,
                d_bytes0, d_mask0, keys=4,
            )
            delivered = {
                "mask": d_due, "src": d_src, "seq": d_seq, "sock": d_sock,
                "bytes": d_bytes, "deliver_rel": d_t,
            }
            due = d_due  # for the n_delivered counter
            # surviving queue = the untouched FIFO suffix, re-front-packed
            keep = valid_s2 & (rstatus == codel.STATUS_QUEUED)
            if packed_sort:
                # sort-diet form: ONE (validity | arrival) packed key +
                # permutation (kept arrivals are real times < I32_MAX, so
                # the pack is exactly the (~keep, key) order)
                perm_keep = _row_perm_sort(_pack_time_key(keep, arr_s))
                take_keep = lambda a: _row_take(a, perm_keep)
                in_deliver_new = take_keep(jnp.where(keep, arr_s, I32_MAX))
                in_src_new, in_seq_new = take_keep(src_s2), take_keep(seq_s2)
                in_sock_new, in_bytes_new = (take_keep(sock_s2),
                                             take_keep(bytes_s2))
                in_valid_new = take_keep(keep)
            else:
                (_, in_deliver_new, in_src_new, in_seq_new, in_sock_new,
                 # shadowlint: disable=SL403 -- pre-diet variadic reference
                 in_bytes_new, in_valid_new) = _row_sort(
                    (~keep).astype(jnp.int32), jnp.where(keep, arr_s, I32_MAX),
                    src_s2, seq_s2, sock_s2, bytes_s2, keep, keys=2,
                )
            rt_out = rt2
    else:
        with jax.named_scope("release_due"):
            (delivered, due, in_deliver_new, in_src_new, in_seq_new,
             in_sock_new, in_bytes_new, in_valid_new) = _release_due(
                in_deliver_m, in_src_m, in_seq_m, in_sock_m, in_bytes_m,
                in_valid_m, window_ns, packed_sort=packed_sort)
            rt_out = rt

    # --- 6. compact leftover egress so rows stay front-packed for ingest
    with jax.named_scope("egress_compact"):
        # egress queue keeps only what didn't go out (compacted after
        # routing, which still indexes this ordering)
        eg_valid_left = eg_valid & ~sendable
        (eg_prio_c, eg_dst_c, eg_bytes_c, eg_seq_c, eg_ctrl_c, eg_tsend_c,
         eg_clamp_c, eg_sock_c, eg_valid_c) = _compact_egress(
            eg_prio, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend, eg_clamp,
            eg_sock, eg_valid_left, packed_sort=packed_sort)

    # --- 7. stats + next-event reduction --------------------------------
    with jax.named_scope("next_event"):
        per_host_in_next = jnp.where(in_valid_new, in_deliver_new,
                                     I32_MAX).min(axis=1)
        if router_aqm:
            # a relay-cached packet blocks its whole row until the resume fires
            per_host_in_next = jnp.where(rt_out.has_cached, rt_out.resume,
                                         per_host_in_next)
        next_event = jnp.minimum(
            per_host_in_next.min(),
            jnp.where(eg_valid_c.any(), window_ns, I32_MAX),
        )

        new_state = state._replace(
            eg_dst=eg_dst_c, eg_bytes=eg_bytes_c, eg_prio=eg_prio_c,
            eg_seq=eg_seq_c, eg_ctrl=eg_ctrl_c, eg_tsend=eg_tsend_c,
            eg_clamp=eg_clamp_c, eg_sock=eg_sock_c, eg_valid=eg_valid_c,
            in_src=in_src_new, in_bytes=in_bytes_new, in_seq=in_seq_new,
            in_sock=in_sock_new, in_deliver_rel=in_deliver_new,
            in_valid=in_valid_new,
            tb_balance=balance, tb_rem_ns=tb_rem_ns, rng_counter=rng_counter,
            rr_sent=rr_sent, router=rt_out,
            n_sent=state.n_sent + sent.sum(axis=1, dtype=jnp.int32),
            n_loss_dropped=state.n_loss_dropped + lost.sum(axis=1, dtype=jnp.int32),
            n_overflow_dropped=state.n_overflow_dropped + overflowed,
            n_delivered=state.n_delivered + due.sum(axis=1, dtype=jnp.int32),
            **({"n_fault_dropped": state.n_fault_dropped + fault_drops}
               if faults is not None else {}),
        )
    if metrics is not None:
        # --- 8. telemetry accumulation (static; compiled out when off) --
        with jax.named_scope("metrics"):
            metrics = _accumulate_metrics(
                metrics, state, sent, lost, due, overflowed, delivered,
                in_valid_m, rt_out.dropped - state.router.dropped,
                fault_drops if faults is not None
                else jnp.zeros((N,), jnp.int32), eg_bytes)
    if guards is not None:
        # --- 9. guard plane (static; compiled out when off) -------------
        # pure reads over values the step already materialized; nothing
        # here can perturb the simulation stream (docs/determinism.md)
        with jax.named_scope("guards"):
            arrivals = jnp.zeros((N,), jnp.int32).at[
                jnp.clip(eg_dst, 0, N - 1).reshape(-1)].add(
                sent.reshape(-1), mode="drop")
            eg_left = sendable.sum(axis=1, dtype=jnp.int32)
            if faults is not None:
                eg_left = eg_left + fault_purged.sum(axis=1, dtype=jnp.int32)
            cached_zero = jnp.zeros((N,), jnp.int32)
            guards = guards_plane.check_window(
                guards,
                state=state,
                eg_occ_in=state.eg_valid.sum(axis=1, dtype=jnp.int32),
                eg_left_this_window=eg_left,
                in_occ_in=state.in_valid.sum(axis=1, dtype=jnp.int32),
                arrivals=arrivals,
                overflowed=overflowed,
                delivered=due.sum(axis=1, dtype=jnp.int32),
                qdisc_delta=(rt_out.dropped - state.router.dropped
                             if router_aqm else cached_zero),
                cached_in=(state.router.has_cached.astype(jnp.int32)
                           if router_aqm else cached_zero),
                cached_out=(rt_out.has_cached.astype(jnp.int32)
                            if router_aqm else cached_zero),
                new_state=new_state,
                rng_delta=rng_counter - state.rng_counter,
                egress_cap=CE, shift_ns=shift_ns, window_ns=window_ns)
    if hist is not None:
        # --- 10. latency/depth histograms (static; compiled out when
        # off) — pure reads over already-materialized values, like the
        # metrics section (docs/observability.md "Distributions and
        # the flight recorder")
        with jax.named_scope("hist"):
            hist = PlaneHistograms(
                # deliver - send: wire latency + the round-barrier clamp,
                # attributed to the DESTINATION (the consumer's view —
                # "p99 delivery latency under incast" is a per-receiver
                # question); int32 scatter-adds commute exactly
                hist_delivery_ns=histo.accum_scatter(
                    hist.hist_delivery_ns, eg_dst,
                    histo.bucket_index(deliver_rel - eg_tsend), sent),
                # egress sojourn: a packet carried from k windows back has
                # a negative rebased send time; -tsend is exactly how long
                # it waited for bandwidth (fresh sends land in bucket 0)
                hist_sojourn_ns=histo.accum_rows(
                    hist.hist_sojourn_ns,
                    histo.bucket_index(-eg_tsend), sent),
                hist_qdepth=histo.accum_depth(
                    hist.hist_qdepth,
                    state.eg_valid.sum(axis=1, dtype=jnp.int32)
                    + in_valid_m.sum(axis=1, dtype=jnp.int32)),
            )
    if flightrec is not None:
        # --- 11. sampled flight recorder (static; compiled out when
        # off): per-hop events for the ~1/K packets whose (src, seq)
        # hashes into the seeded sampling stream — an independent
        # counter-based draw, so recording never perturbs the
        # simulation (docs/determinism.md). Candidate classes
        # concatenate in a fixed layout order (routed, loss-drop,
        # fault-drop, delivered, AQM-drop), so the ring content is a
        # pure function of the event stream. Ring-overflow drops at
        # routing are aggregate-counted only (metrics drop_ring_full);
        # a per-slot overflow identity is not materialized.
        with jax.named_scope("flightrec"):
            rows_e = jnp.broadcast_to(
                jnp.arange(N, dtype=jnp.int32)[:, None], eg_dst.shape)
            samp_eg = flightrec_mod.sample_mask(flightrec, rows_e, eg_seq)
            flat = lambda a: a.reshape(-1)
            kind_of = lambda k, ref: jnp.full((ref.size,), k, jnp.int32)
            ev_kind = [kind_of(flightrec_mod.HOP_ROUTED, eg_dst),
                       kind_of(flightrec_mod.HOP_DROP_LOSS, eg_dst)]
            ev_src = [flat(rows_e), flat(rows_e)]
            ev_seq = [flat(eg_seq), flat(eg_seq)]
            ev_dst = [flat(eg_dst), flat(eg_dst)]
            ev_t = [flat(eg_tsend), flat(eg_tsend)]
            ev_mask = [flat(sent & samp_eg), flat(lost & samp_eg)]
            if faults is not None:
                # every fault-drop class the step distinguishes: source
                # purge (crashed/link-down sender), burst corruption, AND
                # the destination-blocked route withdrawal — a sampled
                # packet eaten by its destination's crash must record a
                # drop_fault hop, not silently vanish from the hop stream
                # while metrics.drop_fault counts it
                ev_kind.append(kind_of(flightrec_mod.HOP_DROP_FAULT, eg_dst))
                ev_src.append(flat(rows_e))
                ev_seq.append(flat(eg_seq))
                ev_dst.append(flat(eg_dst))
                ev_t.append(flat(eg_tsend))
                ev_mask.append(flat(
                    (fault_purged | corrupt | blocked_dst) & samp_eg))
            d_rows = jnp.broadcast_to(
                jnp.arange(N, dtype=jnp.int32)[:, None],
                delivered["mask"].shape)
            samp_d = flightrec_mod.sample_mask(
                flightrec, delivered["src"], delivered["seq"])
            ev_kind.append(kind_of(flightrec_mod.HOP_DELIVERED,
                                   delivered["mask"]))
            ev_src.append(flat(delivered["src"]))
            ev_seq.append(flat(delivered["seq"]))
            ev_dst.append(flat(d_rows))
            ev_t.append(flat(delivered["deliver_rel"]))
            ev_mask.append(flat(delivered["mask"] & samp_d))
            if router_aqm:
                a_rows = jnp.broadcast_to(
                    jnp.arange(N, dtype=jnp.int32)[:, None], src_s2.shape)
                samp_a = flightrec_mod.sample_mask(flightrec, src_s2, seq_s2)
                ev_kind.append(kind_of(flightrec_mod.HOP_DROP_AQM, src_s2))
                ev_src.append(flat(src_s2))
                ev_seq.append(flat(seq_s2))
                ev_dst.append(flat(a_rows))
                ev_t.append(flat(arr_s))
                ev_mask.append(flat(valid_s2
                                    & (rstatus == codel.STATUS_DROPPED)
                                    & samp_a))
            flightrec = flightrec_mod.record_events(
                flightrec,
                jnp.concatenate(ev_kind), jnp.concatenate(ev_src),
                jnp.concatenate(ev_seq), jnp.concatenate(ev_dst),
                jnp.concatenate(ev_t), jnp.concatenate(ev_mask))
            flightrec = flightrec_mod.advance_window(flightrec)
    fs_out = None
    if flows is not None:
        # --- 12. device flow plane (static; compiled out when off):
        # RTO retransmit + congestion backpressure, docs/robustness.md
        # "Flow plane". Ack/credit processing reads the delivered dict
        # the step just released; emission (retransmissions, delayed
        # acks) appends through the normal ingest path AFTER every
        # observability section, so the guards' window conservation
        # checked the pre-append state and the append itself threads
        # check_ingest like any producer. The flow plane's writes
        # confine to the egress columns + the overflow counter (the
        # SL501 append-only obligation `window_step[flows]`); NOTE
        # next_event was reduced before the append — chained callers
        # re-arm it exactly like the workload emission (the min with
        # window_ns in `chain_windows`).
        with jax.named_scope("flows"):
            from . import flows as flows_mod  # lazy: flows.py imports plane

            ft, fs = flows
            fout = flows_mod.flow_step(
                ft, fs, new_state, delivered, window_ns,
                metrics=metrics, guards=guards, flightrec=flightrec)
            new_state, fs_out = fout[0], fout[1]
            rest = list(fout[3:])
            if metrics is not None:
                metrics = rest.pop(0)
            if guards is not None:
                guards = rest.pop(0)
            if flightrec is not None:
                flightrec = rest.pop(0)
    cs_out = None
    if compute is not None:
        # --- 13. device compute plane (static; compiled out when
        # off): bounded-FIFO service occupancy over this window's
        # deliveries, docs/workloads.md "Serving load & the compute
        # plane". Pure reads of the delivered dict; writes only the
        # ComputeState appended last — the SL501 full-invisibility
        # obligation `window_step[compute]` (analysis/proofs.py).
        with jax.named_scope("compute"):
            from . import compute as compute_mod  # lazy: compute imports plane

            ctab, cstate = compute
            cs_out = compute_mod.compute_step(ctab, cstate, delivered,
                                              shift_ns, window_ns)
    out = (new_state, delivered, next_event)
    for plane_out in (metrics, guards, hist, flightrec):
        if plane_out is not None:
            out += (plane_out,)
    if flows is not None:
        out += (fs_out,)
    if compute is not None:
        out += (cs_out,)
    return out
