"""Plain reference for the tgen flow-engine cells.

Every client pulls one fixed-size transfer from its server (Shadow's
`src/test/tgen` fixed-size transfer test). Pairs never interact, so the
reference runs one flow at a time, in plain Python, and compares flow
for flow with what the program reports. It imports nothing of the
program.

It builds its inputs itself from the config, the traffic file and
`--seed` (`deployment`): the graph, the hosts, and each flow's path
latency and loss by shortest path over latency, ties broken by loss,
loss composed along the path as 1 - prod(1 - p) and held at float32,
the routing table's precision.

Each side of a flow is a TCP connection written out here (`Conn`), with
its constants and the rule each comes from:

- three-way handshake, FIN close with TIME_WAIT, RST on a segment to a
  closed connection (RFC 793);
- window scaling (RFC 7323) and SACK (RFC 2018), both offered on the SYN
  and taken up when the peer offers them; timestamps echo the peer's
  latest and give the RTT samples;
- Reno slow start, congestion avoidance, fast retransmit on the third
  duplicate ACK (RFC 5681), NewReno partial ACKs (RFC 6582), initial
  window 10 segments (RFC 6928);
- RTO per RFC 6298 at millisecond grain: first sample srtt = R, rttvar
  = R/2; then rttvar = 3/4 rttvar + |srtt - R|/4, srtt = 7/8 srtt + R/8;
  RTO = srtt + 4 max(rttvar, 50 ms) (Linux's floor on the deviation),
  held to [200 ms, 120 s]; doubled per expiry, restored from the
  estimate by new data ACKed; no sample while backed off (Karn); after
  an expiry every unacknowledged byte is sent again (go-back-N) from a
  window of 10;
- at most 6 SYN and 15 data retransmissions, then the connection gives
  up (Linux's tcp_syn_retries and tcp_retries2, RFC 1122 4.2.3.5).

Sequence numbers are stream offsets: the SYN takes offset -1 and the
FIN the offset after the last byte. TCP's decisions depend only on
differences, so the initial sequence number (a hash of the lane and
`general.seed` in the engine) moves no result, and the reference draws
none.

On top of that machine it writes out, again, the flow engine's
documented contract (`tpu/floweng.py`'s module docstring and the code
it describes):

- flows go into worlds by latency bucket: windows of 1, 2, 5 or 20 ms,
  the widest that the flow's smaller one-way latency admits; inside a
  world, flow i (in plan order) is the lane pair (2i, 2i+1): the client
  opens actively and reads, the server accepts and writes;
- a fixed one-way latency per direction, in whole microseconds; each
  lane's segments reach its peer's FIFO ring at send time + latency; a
  full ring drops the segment, and a world whose run dropped any is run
  again with rings twice as large (at most `max_doublings` times);
- windows [k W, (k+1) W): a lane processes its own events in time order
  in fused steps: up to `sched_batch` scheduled events (ring arrival,
  then RTO, TIME_WAIT expiry, persist, open at equal times) at
  max(event time, the lane's clock); then the app at the lane's clock
  (greedy read, buffer-refill write, close at EOF or once all is
  written); then up to `pull_cap` segments pulled, while the lane has
  one to send; steps repeat until neither lane has work before the
  window's end;
- timers fire at whole-millisecond deadlines (now in ms + the timeout);
  the RTO and TIME_WAIT share one deadline;
- data goes out as TSO-style macro-segments of up to `gso_segs` x MSS;
  retransmissions of the first hole stay one MSS;
- a pure ACK of in-order data may wait until `ack_every` segments are
  unacknowledged or the window's end, where every waiting ACK goes out;
- wire loss per MSS unit: unit k of a lane's n-th emission is lost when
  a splitmix hash of (lane, n x gso_segs + k) falls under the path's
  loss as a u32 threshold; a macro-segment ships its runs before the
  first and between the first and second lost unit, and the rest of
  the burst is charged to the wire;
- the out-of-order store unites overlapping or touching ranges, holds
  at most 32 of them, and drops a range that finds no slot; the
  sender's SACK scoreboard holds 16 ranges: a block inside a held range
  is ignored, one that overlaps or touches a range widens the first
  such range (and no other), else takes the first free slot, else is
  dropped;
- at each window's end every lane's clock moves up to the window's end;
- a world runs in dispatches of about one simulated second, up to the
  first whole dispatch past `stop_time`; a flow whose lanes are both
  quiet (nothing queued, no timer but TIME_WAIT's, closed or in
  TIME_WAIT) has nothing left to change.

`compare` gives the three numbers that decide `correct`. A `control`
reference breaks one thing and has to read not correct:
"plan_order_loss" keys loss by the flow's index in plan order, not its
lane in its bucket's world (a stated guarantee); "float16_loss" holds
path loss at float16, the precision below the routing table's.
"""

from __future__ import annotations

import heapq
from collections import deque, namedtuple

import numpy as np

I32_MAX = 2**31 - 1
M32 = 0xFFFFFFFF
MS_US = 1000
WINDOW_LADDER = (1_000, 2_000, 5_000, 20_000)
CONTROLS = ("plan_order_loss", "float16_loss")

# -- TCP constants ------------------------------------------------------------
MSS = 1460                 # Ethernet's 1500-byte MTU less 40 B of IPv4+TCP (RFC 6691)
SEND_BUFFER = 131_072      # socket send buffer, bytes (Shadow's default)
RECV_BUFFER = 174_760      # socket receive buffer, bytes (Shadow's default)
MAX_WSCALE = 14            # RFC 7323 2.3
MAX_WINDOW_FIELD = 0xFFFF  # the 16-bit window field (RFC 793)
INITIAL_WINDOW = 10        # segments (RFC 6928)
SSTHRESH_INF = 2**31 - 1   # "arbitrarily high" (RFC 5681 3.1)
DUPACK_THRESHOLD = 3       # fast retransmit (RFC 5681 3.2)
RTO_INIT_MS = 1_000        # RFC 6298 2.1
RTO_MIN_MS = 200           # Linux TCP_RTO_MIN (RFC 6298 2.4 allows below 1 s)
RTO_MAX_MS = 120_000       # Linux TCP_RTO_MAX (RFC 6298 2.5: at least 60 s)
RTTVAR_FLOOR_MS = RTO_MIN_MS // 4  # Linux tcp_rtt_estimator's mdev floor
SYN_RETRIES = 6            # Linux tcp_syn_retries (RFC 1122 4.2.3.5)
DATA_RETRIES = 15          # Linux tcp_retries2 (RFC 1122 4.2.3.5)
TIME_WAIT_MS = 60_000      # 2 MSL as Linux holds it (RFC 793: MSL 2 min)
SACK_BLOCKS = 3            # blocks a segment carries beside timestamps (RFC 2018 3)
SACK_SLOTS = 16            # ranges the sender's scoreboard holds (engine contract)
REASS_SLOTS = 32           # ranges the out-of-order store holds (engine contract)
ECONNREFUSED, ECONNRESET, ETIMEDOUT = 111, 104, 110

FIN, SYN, RST, PSH, ACK = 1, 2, 4, 8, 16

CLOSED, SYN_SENT, SYN_RCVD, ESTABLISHED = (
    "CLOSED", "SYN_SENT", "SYN_RCVD", "ESTABLISHED")
FIN_WAIT_1, FIN_WAIT_2, CLOSING, TIME_WAIT = (
    "FIN_WAIT_1", "FIN_WAIT_2", "CLOSING", "TIME_WAIT")
CLOSE_WAIT, LAST_ACK = "CLOSE_WAIT", "LAST_ACK"
SYNCHRONIZED = (ESTABLISHED, FIN_WAIT_1, FIN_WAIT_2, CLOSING, TIME_WAIT,
                CLOSE_WAIT, LAST_ACK)
SENDING = (ESTABLISHED, CLOSE_WAIT, FIN_WAIT_1, CLOSING, LAST_ACK)
HANDSHAKE = (SYN_SENT, SYN_RCVD)

SLOW_START, AVOIDANCE, RECOVERY = 0, 1, 2

# seq: stream offset of the first byte (-1 on a SYN); ack: the next
# offset wanted (the FIN counts one); wnd: the window field as sent;
# plen: payload bytes; ts / tsecr: timestamp and its echo (0 = none);
# sack: ((start, end), ...); wscale: the SYN's shift (None elsewhere)
Seg = namedtuple("Seg", "flags seq ack wnd plen ts tsecr sack wscale sack_ok")


# -- the deployment, from the config, the traffic and the seed ----------------

def deployment(cfg: dict, traffic: dict, seed: int) -> dict:
    """Nodes, edges, hosts and general settings of one run.

    The graph is a full mesh over `n_nodes`, drawn from the config's
    `graph_seed` (a deployment's graph is a fixed file): every edge
    draws a latency in whole ms from [lo, hi) and a loss from [lo, hi),
    written with four decimals; each node's self-loop has `self_loop_ms`
    and no loss. Undirected graphs draw one edge per node pair, directed
    ones one per ordered pair. Server s sits on node s mod n_nodes;
    client i on node i mod n_nodes, targets server i mod n_servers and
    starts at start_s[0] + (i mod start_s[1]) seconds. `seed` is the
    run's `general.seed`."""
    rng = np.random.default_rng(cfg["graph_seed"])
    n = cfg["n_nodes"]
    directed = bool(cfg.get("directed", False))
    lat_lo, lat_hi = cfg["latency_ms"]
    loss_lo, loss_hi = cfg["loss"]
    edges = []
    for i in range(n):
        for j in range(0 if directed else i, n):
            if i == j:
                lat, loss = cfg["self_loop_ms"], 0.0
            else:
                lat = int(rng.integers(lat_lo, lat_hi))
                loss = float(rng.uniform(loss_lo, loss_hi))
            edges.append((i, j, lat, f"{loss:.4f}"))
    n_servers = cfg["n_servers"]
    hosts = [{"name": f"server{s}", "node": s % n, "server": None}
             for s in range(n_servers)]
    base, spread = traffic["start_s"]
    for i in range(cfg["n_hosts"] - n_servers):
        hosts.append({"name": f"client{i}", "node": i % n,
                      "server": f"server{i % n_servers}",
                      "size": traffic["transfer_bytes"],
                      "count": traffic["transfers_per_client"],
                      "start_s": base + i % spread})
    return {"n_nodes": n, "directed": directed, "edges": edges,
            "bandwidth": cfg["bandwidth"], "hosts": hosts,
            "server_start_s": traffic["server_start_s"],
            "stop_s": traffic["stop_s"], "seed": seed % 2**31}


def shortest_paths(dep: dict):
    """{(src node, dst node): (latency ns, loss)} for every pair:
    Dijkstra by (latency, loss); a node to itself by its self-loop."""
    n = dep["n_nodes"]
    adj = [dict() for _ in range(n)]
    loops = {}
    for i, j, lat_ms, loss_txt in dep["edges"]:
        w = (lat_ms * 1_000_000, float(loss_txt))
        pairs = [(i, j)] if dep["directed"] else [(i, j), (j, i)]
        for a, b in pairs:
            if a == b:
                loops[a] = w
            elif b not in adj[a] or w < adj[a][b]:
                adj[a][b] = w
    out = {}
    for src in range(n):
        best = {src: (0, 0.0)}
        heap = [(0, 0.0, src)]
        done = set()
        while heap:
            lat, loss, u = heapq.heappop(heap)
            if u in done:
                continue
            done.add(u)
            for v, (el, ep) in adj[u].items():
                cand = (lat + el, 1.0 - (1.0 - loss) * (1.0 - ep))
                if v not in best or cand < best[v]:
                    best[v] = cand
                    heapq.heappush(heap, (*cand, v))
        for dst, (lat, loss) in best.items():
            if dst != src:
                out[src, dst] = (lat, loss)
        out[src, src] = loops[src]
    return out


def bucket_window(lat_us: int) -> int:
    w = min(lat_us, WINDOW_LADDER[-1])
    best = 0
    for step in WINDOW_LADDER:
        if step <= w:
            best = step
    return best if best else int(w)


def flow_plan(dep: dict, loss_dtype=np.float32) -> list:
    """One dict per client in host order: size, start, the two
    directions' latency (us) and loss (held at `loss_dtype`), and its
    bucket's window."""
    paths = shortest_paths(dep)
    held = np.dtype(loss_dtype).type
    node = {h["name"]: h["node"] for h in dep["hosts"]}
    flows = []
    for h in dep["hosts"]:
        if h["server"] is None:
            continue
        if h["count"] != 1:
            raise ValueError("the flow engine runs one transfer per client")
        a, b = h["node"], node[h["server"]]
        lat_ns, loss = paths[a, b]
        back_ns, loss_back = paths[b, a]
        f = {"client": h["name"], "server": h["server"], "size": h["size"],
             "start_us": h["start_s"] * 1_000_000,
             "lat_us": lat_ns // 1000, "lat_back_us": back_ns // 1000,
             "loss": float(held(loss)),
             "loss_back": float(held(loss_back))}
        f["window_us"] = bucket_window(min(f["lat_us"], f["lat_back_us"]))
        flows.append(f)
    return flows


# -- the wire's loss draw ------------------------------------------------------

def wire_draw(lane: int, counter: int) -> int:
    """Uniform u32 of (lane, emission unit): the wire's loss draw."""
    z = (lane * 0x9E3779B9 + counter * 0x85EBCA6B + 0x6A09E667) & M32
    z = ((z ^ (z >> 16)) * 0x21F0AAAD) & M32
    z = ((z ^ (z >> 15)) * 0x735A2D97) & M32
    return z ^ (z >> 15)


def loss_threshold(p: float) -> int:
    return int(min(max(p * 2.0**32, 0.0), 2**32 - 1))


# -- the TCP machine -----------------------------------------------------------

class Store:
    """The receiver's out-of-order store, {start: length}: overlapping or
    touching ranges are united; at most `slots` ranges, a range with no
    slot is dropped."""

    __slots__ = ("ranges", "slots")

    def __init__(self, slots: int = REASS_SLOTS):
        self.ranges: dict[int, int] = {}
        self.slots = slots

    def insert(self, off: int, n: int) -> None:
        end = off + n
        touch = [s for s, m in self.ranges.items()
                 if s <= end and off <= s + m]
        if touch:
            lo = min(off, *touch)
            hi = max(end, *(s + self.ranges[s] for s in touch))
            for s in touch:
                del self.ranges[s]
            self.ranges[lo] = hi - lo
        elif len(self.ranges) < self.slots:
            self.ranges[off] = n

    def drain(self, off: int) -> int:
        """The offset past every byte held contiguously from `off`; the
        ranges it passes leave the store."""
        moved = True
        while moved:
            moved = False
            for s, m in list(self.ranges.items()):
                if s <= off < s + m:
                    off = s + m
                    del self.ranges[s]
                    moved = True
        for s in [s for s, m in self.ranges.items() if s + m <= off]:
            del self.ranges[s]
        return off

    def held(self) -> int:
        return sum(self.ranges.values())

    def blocks(self) -> tuple:
        """The lowest SACK_BLOCKS ranges, touching ones merged."""
        merged: list[list[int]] = []
        for s, m in sorted(self.ranges.items()):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], s + m)
            else:
                merged.append([s, s + m])
        return tuple((s, e) for s, e in merged[:SACK_BLOCKS])


class Scoreboard:
    """The sender's record of SACKed ranges: SACK_SLOTS slots [s, e),
    empty when e <= s."""

    __slots__ = ("s", "e")

    def __init__(self):
        self.s = [0] * SACK_SLOTS
        self.e = [0] * SACK_SLOTS

    def _live(self):
        return [i for i in range(SACK_SLOTS) if self.e[i] > self.s[i]]

    def insert(self, start: int, end: int, una: int) -> None:
        start = max(start, una)
        if start >= end:
            return
        live = self._live()
        if any(self.s[i] <= start and end <= self.e[i] for i in live):
            return
        for i in live:
            if start <= self.e[i] and self.s[i] <= end:
                self.s[i] = min(self.s[i], start)
                self.e[i] = max(self.e[i], end)
                return
        for i in range(SACK_SLOTS):
            if self.e[i] <= self.s[i]:
                self.s[i], self.e[i] = start, end
                return

    def prune(self, una: int) -> None:
        for i in self._live():
            self.s[i] = max(self.s[i], una)
            if self.s[i] >= self.e[i]:
                self.s[i] = self.e[i] = 0

    def next_unsacked(self, off: int) -> tuple[int, int]:
        """(the first offset >= `off` held by no range, bytes from there
        to the next range above it or None)."""
        for _ in range(SACK_SLOTS):
            moved = False
            for i in self._live():
                if self.s[i] <= off < self.e[i]:
                    off = self.e[i]
                    moved = True
            if not moved:
                break
        above = [self.s[i] - off for i in self._live() if self.s[i] > off]
        return off, (min(above) if above else None)


def _least(*xs):
    return min(x for x in xs if x is not None)


class Conn:
    """One side of a connection. `lane.t` is its clock in microseconds;
    the app calls `open_active`/`open_passive`, `read`, `write` and
    `close`; the wire asks `next_kind` and pulls `next_segment`, and
    hands arrivals to `on_segment`; the lane fires `fire_rto`,
    `fire_persist` and `expire_time_wait` at their deadlines."""

    def __init__(self, lane, gso_segs: int):
        self.lane, self.gso = lane, gso_segs
        self.state, self.error = CLOSED, None
        # send side, stream offsets
        self.snd_una = self.snd_nxt = self.snd_max = 0
        self.snd_wnd = MSS
        self.stream_len = 0
        self.fin_requested = self.fin_sent = self.fin_acked = False
        self.syn_outstanding, self.syn_sends = False, 0
        self.retx_pending = self.probe_pending = self.rst_pending = False
        self.recover = 0   # NewReno: snd_nxt when recovery began
        self.gbn_high = 0  # sends below this after an RTO are resends
        self.board = Scoreboard()
        self.retransmits = 0
        # receive side
        self.rcv_nxt = 0
        self.store = Store()
        self.ordered = 0   # in-order bytes the app has not read
        self.fin_received, self.fin_at = False, None
        self.ack_pending = False
        # options
        ws = 0
        while (RECV_BUFFER >> ws) > MAX_WINDOW_FIELD and ws < MAX_WSCALE:
            ws += 1
        self.my_wscale, self.peer_wscale, self.wscale_ok = ws, 0, False
        self.sack_ok = False
        self.ts_recent = 0
        # RTT (ms), Reno (segments), timers (ms deadlines)
        self.srtt = self.rttvar = 0
        self.rto, self.backoffs = RTO_INIT_MS, 0
        self.cwnd, self.ssthresh = INITIAL_WINDOW, SSTHRESH_INF
        self.phase, self.dupacks, self.avoid_acked = SLOW_START, 0, 0
        self.rto_armed, self.rto_deadline_ms = False, 0
        self.persist_armed, self.persist_deadline_ms = False, 0

    def now_ms(self) -> int:
        return self.lane.t // MS_US

    # -- the app ---------------------------------------------------------
    def open_active(self) -> None:
        self.state = SYN_SENT
        self.arm_rto()

    def open_passive(self, syn: Seg) -> None:
        self.rcv_nxt = 0
        self.take_syn_options(syn)
        self.snd_wnd = syn.wnd  # never scaled on a SYN
        self.ts_recent = syn.ts
        self.state = SYN_RCVD
        self.arm_rto()

    def take_syn_options(self, syn: Seg) -> None:
        if syn.wscale is not None:
            self.peer_wscale = min(syn.wscale, MAX_WSCALE)
            self.wscale_ok = True
        else:
            self.my_wscale = 0
        self.sack_ok = syn.sack_ok

    def read(self, n: int) -> int:
        got = min(n, self.ordered)
        self.ordered -= got
        if got:
            self.ack_pending = True  # the window opened
        return got

    def send_space(self) -> int:
        return max(0, SEND_BUFFER - (self.stream_len - self.snd_una))

    def write(self, n: int) -> None:
        self.stream_len += n
        if self.snd_wnd == 0 and self.state in SYNCHRONIZED:
            self.arm_persist()

    def close(self) -> None:
        if self.state == CLOSED:
            return
        if self.state == SYN_SENT:
            self.enter_closed(None)
            return
        if self.fin_requested:
            return
        self.fin_requested = True
        if self.state in (ESTABLISHED, SYN_RCVD):
            self.state = FIN_WAIT_1
        elif self.state == CLOSE_WAIT:
            self.state = LAST_ACK

    # -- what goes out next ------------------------------------------------
    def next_kind(self):
        if self.rst_pending:
            return "rst"
        if self.state in HANDSHAKE and not self.syn_outstanding:
            return "syn"
        if self.state == SYN_SENT:
            return None
        if self.retx_pending and self.snd_nxt > self.snd_una:
            return "retransmit"
        if self.probe_pending and self.stream_len > self.snd_nxt:
            return "probe"
        if (self.state in SENDING and self.snd_nxt < self.stream_len
                and self.snd_nxt - self.snd_una < self.send_window()):
            return "data"
        if (self.fin_requested and not self.fin_sent
                and self.snd_nxt >= self.stream_len
                and self.state in (FIN_WAIT_1, LAST_ACK, CLOSING)):
            return "fin"
        if self.ack_pending and self.state != CLOSED:
            return "ack"
        return None

    def send_window(self) -> int:
        return min(self.cwnd * MSS, self.snd_wnd)

    def next_segment(self) -> Seg:
        kind = self.next_kind()
        before = self.snd_nxt
        seg = getattr(self, "build_" + kind)()
        if kind in ("data", "fin") and before < self.gbn_high:
            self.retransmits += 1  # a go-back-N resend
        return seg

    def seg(self, flags, seq, plen=0, syn=False) -> Seg:
        space = max(0, RECV_BUFFER - self.ordered - self.store.held())
        if syn or not self.wscale_ok:
            wnd = min(space, MAX_WINDOW_FIELD)
        else:
            wnd = min(space >> self.my_wscale, MAX_WINDOW_FIELD)
        sack = self.store.blocks() if self.sack_ok and not syn else ()
        return Seg(flags, seq, self.rcv_nxt + self.fin_received, wnd, plen,
                   self.now_ms() & M32, self.ts_recent, sack,
                   self.my_wscale if syn else None, syn)

    def build_syn(self) -> Seg:
        self.syn_outstanding = True
        self.syn_sends += 1
        if self.syn_sends > 1:
            self.retransmits += 1
        self.ack_pending = False
        return self.seg(SYN if self.state == SYN_SENT else SYN | ACK, -1,
                        syn=True)

    def build_data(self) -> Seg:
        off, cap = self.board.next_unsacked(self.snd_nxt)
        if off != self.snd_nxt:
            self.snd_nxt = off
            self.snd_max = max(self.snd_max, off)
        n = _least(MSS * self.gso, self.stream_len - off,
                   self.send_window() - (off - self.snd_una), cap)
        if n <= 0:
            return self.build_ack()
        self.snd_nxt = off + n
        self.snd_max = max(self.snd_max, self.snd_nxt)
        self.ack_pending = False
        if not self.rto_armed:
            self.arm_rto()
        push = PSH if self.snd_nxt >= self.stream_len else 0
        return self.seg(ACK | push, off, n)

    def build_retransmit(self) -> Seg:
        self.retx_pending = False
        self.retransmits += 1
        off = self.snd_una
        n = _least(MSS, self.stream_len - off,
                   self.board.next_unsacked(off)[1])
        if n <= 0:
            return self.build_fin(again=True) if self.fin_sent \
                else self.build_ack()
        if not self.rto_armed:
            self.arm_rto()
        return self.seg(ACK, off, n)

    def build_probe(self) -> Seg:
        """Zero-window probe: one byte past the window."""
        self.probe_pending = False
        off = self.snd_nxt
        self.snd_nxt = off + 1
        self.snd_max = max(self.snd_max, self.snd_nxt)
        if not self.rto_armed:
            self.arm_rto()
        return self.seg(ACK, off, 1)

    def build_fin(self, again: bool = False) -> Seg:
        if not again:
            self.fin_sent = True
            self.snd_nxt = self.stream_len + 1
            self.snd_max = max(self.snd_max, self.snd_nxt)
        self.ack_pending = False
        if not self.rto_armed:
            self.arm_rto()
        return self.seg(FIN | ACK, self.stream_len)

    def build_ack(self) -> Seg:
        self.ack_pending = False
        return self.seg(ACK, min(self.snd_nxt,
                                 self.stream_len + self.fin_sent))

    def build_rst(self) -> Seg:
        self.rst_pending = False
        seg = Seg(RST | ACK, min(self.snd_nxt, self.stream_len),
                  self.rcv_nxt + self.fin_received, 0, 0, 0, 0, (), None,
                  False)
        self.enter_closed(ECONNRESET)
        return seg

    # -- what comes in ---------------------------------------------------
    def on_segment(self, seg: Seg) -> None:
        if self.state == CLOSED:
            if not seg.flags & RST:
                self.rst_pending = True
            return
        if seg.ts:
            self.ts_recent = seg.ts
        if self.state == SYN_SENT:
            self.on_handshake_reply(seg)
            return
        if seg.flags & RST:
            self.enter_closed(None if self.state == TIME_WAIT
                              else ECONNRESET)
            return
        if seg.flags & SYN:
            if self.state == SYN_RCVD and seg.seq == -1:
                self.syn_outstanding = False  # the SYN again: answer again
            elif self.state == TIME_WAIT:
                pass
            elif seg.seq < self.rcv_nxt:
                self.ack_pending = True  # an old SYN: our ACK was lost
            else:
                self.rst_pending = True
            return
        if seg.flags & ACK:
            self.on_ack(seg)
        if seg.plen:
            self.on_payload(seg)
        if seg.flags & FIN:
            fin = seg.seq + seg.plen
            if self.fin_at is None:
                self.fin_at = fin if fin >= self.rcv_nxt else self.rcv_nxt
            self.ack_pending = True
            self.take_fin()

    def on_handshake_reply(self, seg: Seg) -> None:
        if seg.flags & RST:
            if seg.flags & ACK and seg.ack == 0:
                self.enter_closed(ECONNREFUSED)
            return
        if seg.flags & SYN and seg.flags & ACK:
            if seg.ack != 0:
                self.rst_pending = True
                return
            self.rcv_nxt = 0
            self.syn_outstanding = False
            self.take_syn_options(seg)
            self.snd_wnd = seg.wnd
            self.state = ESTABLISHED
            self.ack_pending = True
            self.rto_armed = False
            self.rtt_sample(seg)
        # a bare SYN (simultaneous open) cannot come: only the client
        # opens actively

    def on_ack(self, seg: Seg) -> None:
        ack = seg.ack
        if ack > max(self.snd_nxt, self.snd_max):
            return  # acknowledges what was never sent (RFC 793)
        if self.state == SYN_RCVD and ack >= 0:
            self.state = ESTABLISHED
            self.rto_armed = False
            self.rtt_sample(seg)
        if self.sack_ok:
            limit = max(self.snd_nxt, self.snd_max)
            for s, e in seg.sack[:SACK_BLOCKS]:
                if 0 <= s < e <= limit:
                    self.board.insert(s, e, self.snd_una)
        fin_end = self.stream_len + 1 if self.fin_sent else None
        wnd = seg.wnd << (self.peer_wscale if self.wscale_ok else 0)
        if ack > self.snd_una:
            acked = min(ack, self.stream_len) - self.snd_una
            self.snd_una = min(ack, self.stream_len)
            if fin_end is not None and ack >= fin_end:
                self.fin_acked = True
            self.snd_nxt = max(self.snd_nxt, self.snd_una)
            self.board.prune(self.snd_una)
            if acked > 0:
                n = (acked + MSS - 1) // MSS
                if self.phase == RECOVERY and ack < self.recover:
                    # NewReno partial ACK: the next hole is lost too
                    self.cwnd = max(1, self.cwnd - n + 1)
                    self.retx_pending = True
                else:
                    self.on_new_ack(n)
                    self.retx_pending = False
            else:
                self.retx_pending = False
            self.rtt_sample(seg)
            if self.backoffs:  # progress: the RTO from the estimate again
                self.backoffs = 0
                self.set_rto(self.rto_of_estimate() if self.srtt
                             else RTO_INIT_MS)
            if self.snd_nxt > self.snd_una or (self.fin_sent
                                               and not self.fin_acked):
                self.arm_rto()
            else:
                self.rto_armed = False
            if self.fin_acked:
                if self.state == FIN_WAIT_1:
                    self.state = FIN_WAIT_2
                elif self.state == CLOSING:
                    self.enter_time_wait()
                elif self.state == LAST_ACK:
                    self.enter_closed(None)
        elif (ack == self.snd_una and not seg.plen
              and self.snd_nxt > self.snd_una and wnd == self.snd_wnd
              and wnd > 0):
            if self.on_dupack():
                self.retx_pending = True
                self.recover = self.snd_nxt
        self.snd_wnd = wnd
        if self.snd_wnd == 0 and self.stream_len > self.snd_nxt:
            self.arm_persist()

    def on_payload(self, seg: Seg) -> None:
        self.ack_pending = True
        if self.state == TIME_WAIT:
            return
        off, n = seg.seq, seg.plen
        if off < self.rcv_nxt:
            if self.rcv_nxt - off >= n:
                return  # all of it is here already
            n -= self.rcv_nxt - off
            off = self.rcv_nxt
        end = self.rcv_nxt + max(0, RECV_BUFFER - self.ordered
                                 - self.store.held())
        if off >= end:
            return
        self.store.insert(off, min(n, end - off))
        new = self.store.drain(self.rcv_nxt)
        self.ordered += new - self.rcv_nxt
        self.rcv_nxt = new
        self.take_fin()

    def take_fin(self) -> None:
        if self.fin_received or self.fin_at is None \
                or self.fin_at > self.rcv_nxt:
            return
        self.fin_received = True
        if self.state == ESTABLISHED:
            self.state = CLOSE_WAIT
        elif self.state == FIN_WAIT_1:
            if self.fin_acked:
                self.enter_time_wait()
            else:
                self.state = CLOSING
        elif self.state == FIN_WAIT_2:
            self.enter_time_wait()

    # -- RTT, RTO and Reno -----------------------------------------------
    def rtt_sample(self, seg: Seg) -> None:
        if not seg.tsecr or self.backoffs:
            return  # no echo, or backed off (Karn)
        r = max(1, self.now_ms() - seg.tsecr)
        if self.srtt == 0:
            self.srtt, self.rttvar = r, r // 2
        else:
            self.rttvar = 3 * self.rttvar // 4 + abs(self.srtt - r) // 4
            self.srtt = 7 * self.srtt // 8 + r // 8
        self.set_rto(self.rto_of_estimate())

    def rto_of_estimate(self) -> int:
        return self.srtt + 4 * max(self.rttvar, RTTVAR_FLOOR_MS)

    def set_rto(self, ms: int) -> None:
        self.rto = min(max(ms, RTO_MIN_MS), RTO_MAX_MS)

    def back_off(self) -> None:
        self.backoffs += 1
        self.set_rto(self.rto * 2)

    def on_new_ack(self, n: int) -> None:
        self.dupacks = 0
        if self.phase == RECOVERY:
            self.cwnd = self.ssthresh
            self.enter_avoidance(n)
        elif self.phase == SLOW_START:
            if self.cwnd + n >= self.ssthresh:
                left = self.cwnd + n - self.ssthresh
                self.cwnd = self.ssthresh
                self.enter_avoidance(left)
            else:
                self.cwnd += n
        else:
            self.avoid(n)

    def enter_avoidance(self, carried: int) -> None:
        self.phase, self.avoid_acked = AVOIDANCE, 0
        if carried:
            self.avoid(carried)

    def avoid(self, n: int) -> None:
        """One more segment of window per window's worth acknowledged."""
        self.avoid_acked += n
        while self.avoid_acked >= self.cwnd:
            self.avoid_acked -= self.cwnd
            self.cwnd += 1

    def on_dupack(self) -> bool:
        """True on the duplicate ACK that starts fast retransmit."""
        if self.phase == RECOVERY:
            self.cwnd += 1  # each further duplicate: one more in flight
            return False
        self.dupacks += 1
        if self.dupacks != DUPACK_THRESHOLD:
            return False
        self.ssthresh = self.cwnd // 2 + 1
        self.cwnd = self.ssthresh + DUPACK_THRESHOLD
        self.phase = RECOVERY
        return True

    # -- timers ------------------------------------------------------------
    def arm_rto(self) -> None:
        self.rto_armed = True
        self.rto_deadline_ms = self.now_ms() + self.rto

    def arm_persist(self) -> None:
        if not self.persist_armed:
            self.persist_armed = True
            self.persist_deadline_ms = self.now_ms() + self.rto

    def fire_rto(self) -> None:
        if self.state == CLOSED:
            return
        self.rto_armed = False
        if not (self.snd_nxt > self.snd_una
                or (self.fin_sent and not self.fin_acked)
                or self.state in HANDSHAKE):
            return
        limit = SYN_RETRIES if self.state in HANDSHAKE else DATA_RETRIES
        if self.backoffs >= limit:
            self.enter_closed(ETIMEDOUT)
            return
        self.back_off()
        self.dupacks = 0
        self.ssthresh = self.cwnd // 2 + 1
        self.cwnd, self.phase = INITIAL_WINDOW, SLOW_START
        if self.state in HANDSHAKE:
            self.syn_outstanding = False  # send the SYN again
        else:
            # go-back-N: send every unacknowledged byte again
            self.gbn_high = max(self.gbn_high, self.snd_nxt)
            self.snd_nxt = self.snd_una
            self.retx_pending = False
            if self.fin_sent and not self.fin_acked:
                self.fin_sent = False
            if self.snd_wnd == 0 and self.stream_len > self.snd_nxt:
                self.arm_persist()
        self.arm_rto()

    def fire_persist(self) -> None:
        if self.state == CLOSED:
            return
        self.persist_armed = False
        if self.snd_wnd == 0 and self.stream_len > self.snd_nxt:
            self.probe_pending = True
            self.back_off()
            self.arm_persist()

    def enter_time_wait(self) -> None:
        self.state = TIME_WAIT
        self.rto_armed = False
        self.rto_deadline_ms = self.now_ms() + TIME_WAIT_MS

    def expire_time_wait(self) -> None:
        self.enter_closed(None)

    def enter_closed(self, error) -> None:
        self.state = CLOSED
        if error is not None:
            self.error = error
        self.rto_armed = False

    # -- the wire's view of a segment --------------------------------------
    @staticmethod
    def paylen(seg: Seg) -> int:
        return seg.plen

    @staticmethod
    def cut(seg: Seg, start: int, n: int) -> Seg:
        """Payload bytes [start, start + n) of `seg` as a segment."""
        return seg._replace(seq=seg.seq + start, plen=n)


# -- the engine's windows around two machines ----------------------------------

class Lane:
    __slots__ = ("conn", "peer", "ring", "t", "lane_id", "opened",
                 "close_sent", "written", "read_bytes", "total", "t_start",
                 "latency_us", "loss_u32", "n_segments", "seg_units",
                 "wire_drops", "q_dropped", "unacked", "complete_us")

    def __init__(self, draw_id, total, t_start, latency_us, loss, gso_segs,
                 machine):
        self.lane_id = draw_id
        self.t = 0
        self.conn = machine(self, gso_segs)
        self.ring = deque()
        self.opened = self.close_sent = False
        self.written = self.read_bytes = 0
        self.total, self.t_start = total, t_start
        self.latency_us = latency_us
        self.loss_u32 = loss_threshold(loss)
        self.n_segments = self.seg_units = self.wire_drops = 0
        self.q_dropped = self.unacked = 0
        self.complete_us = I32_MAX


class FlowSim:
    """One flow's two lanes through the engine's windows."""

    def __init__(self, f: dict, draw_idx: int, queue_slots: int,
                 opts: dict, machine=Conn):
        g = opts["gso_segs"]
        # lane 2i: the client, opens and reads; 2i+1: the server, writes
        self.a = Lane(2 * draw_idx, 0, f["start_us"], f["lat_us"],
                      f["loss"], g, machine)
        self.b = Lane(2 * draw_idx + 1, f["size"], I32_MAX,
                      f["lat_back_us"], f["loss_back"], g, machine)
        self.a.peer, self.b.peer = self.b, self.a
        self.Q = queue_slots
        self.W = f["window_us"]
        self.opts = opts

    # -- per-lane pieces ---------------------------------------------------
    @staticmethod
    def deadlines(ln):
        """(arrival, RTO, TIME_WAIT expiry, persist, open) in us, I32_MAX
        for none."""
        c = ln.conn
        rto = c.rto_deadline_ms * MS_US
        return (ln.ring[0][0] if ln.ring else I32_MAX,
                rto if c.rto_armed else I32_MAX,
                rto if c.state == TIME_WAIT else I32_MAX,
                c.persist_deadline_ms * MS_US if c.persist_armed
                else I32_MAX,
                I32_MAX if ln.opened else ln.t_start)

    def sched_event(self, ln, end) -> bool:
        c = ln.conn
        arr, rto, tw, ps, op = self.deadlines(ln)
        st = min(arr, rto, tw, ps, op)
        if st >= end:
            return False
        ln.t = max(st, ln.t)
        if st == arr:
            _, seg = ln.ring.popleft()
            if ln.opened:
                c.on_segment(seg)
                if c.paylen(seg):
                    ln.unacked += 1
            elif seg.flags & SYN:
                c.open_passive(seg)
                ln.opened = True
        elif st == rto:
            c.fire_rto()
        elif st == tw:
            c.expire_time_wait()
        elif st == ps:
            c.fire_persist()
        else:
            c.open_active()
            ln.opened = True
        return True

    @staticmethod
    def app(ln):
        c = ln.conn
        if ln.opened and c.ordered > 0:
            ln.read_bytes += c.read(c.ordered)
            peer_total = ln.peer.total
            if ln.complete_us == I32_MAX and peer_total > 0 \
                    and ln.read_bytes >= peer_total:
                ln.complete_us = ln.t
        healthy = c.error is None
        state_ok = c.state in (ESTABLISHED, CLOSE_WAIT)
        n = min(c.send_space(), ln.total - ln.written)
        if state_ok and healthy and ln.opened and n > 0:
            c.write(n)
            ln.written += n
        if ln.close_sent or not ln.opened or not healthy:
            return
        if ln.total > 0:
            go = ln.written >= ln.total and c.state == ESTABLISHED
        else:
            go = (c.fin_received and c.ordered == 0
                  and c.store.held() == 0 and state_ok)
        if go:
            c.close()
            ln.close_sent = True

    @staticmethod
    def wanted(ln, ack_every: int) -> bool:
        if not ln.opened:
            return False
        c = ln.conn
        kind = c.next_kind()
        if kind is None:
            return False
        if kind == "ack" and c.state == ESTABLISHED \
                and not c.fin_received and c.error is None \
                and c.store.held() == 0 \
                and 1 <= ln.unacked < ack_every:
            return False  # a delayed ACK
        return True

    def pull(self, ln):
        g = self.opts["gso_segs"]
        c = ln.conn
        seg = c.next_segment()
        paylen = c.paylen(seg)
        units = max((paylen + MSS - 1) // MSS, 1)
        lost = []
        if ln.loss_u32 > 0:
            base = ln.n_segments * g
            lost = [k for k in range(units)
                    if wire_draw(ln.lane_id, base + k) < ln.loss_u32]
        parts = []
        if not lost:
            delivered = units
            parts.append(seg)
        elif paylen == 0:
            delivered = 0
        else:
            f0 = lost[0]
            f1 = lost[1] if len(lost) > 1 else units
            len_a = min(f0 * MSS, paylen)
            start_b = (f0 + 1) * MSS
            len_b = max(min(f1 * MSS, paylen) - start_b, 0)
            delivered = f0 + (len_b + MSS - 1) // MSS
            if len_a > 0:
                parts.append(c.cut(seg, 0, len_a))
            if len_b > 0:
                parts.append(c.cut(seg, start_b, len_b))
        peer = ln.peer
        for p in parts:
            if len(peer.ring) < self.Q:
                peer.ring.append((ln.t + ln.latency_us, p))
            else:
                ln.q_dropped += 1
        ln.wire_drops += units - delivered
        ln.n_segments += 1
        ln.seg_units += units
        ln.unacked = 0

    def pull_phase(self, ln, ack_every: int):
        for _ in range(self.opts["pull_cap"]):
            if not self.wanted(ln, ack_every):
                return
            self.pull(ln)

    @staticmethod
    def quiet(ln) -> bool:
        c = ln.conn
        return (not ln.ring and not c.rto_armed and not c.persist_armed
                and c.state in (CLOSED, TIME_WAIT)
                and not (c.ack_pending and c.state != CLOSED))

    # -- the run -------------------------------------------------------------
    def run(self, horizon_windows: int, step_limit: int = 100_000):
        W, lanes = self.W, (self.a, self.b)
        ack_every, batch = self.opts["ack_every"], self.opts["sched_batch"]
        k = 0
        while k < horizon_windows:
            if self.a.opened and self.quiet(self.a) and self.quiet(self.b):
                break
            nxt = min(min(self.deadlines(ln)) for ln in lanes)
            busy = any(self.wanted(ln, ack_every) for ln in lanes)
            if not busy:
                if nxt >= I32_MAX:
                    break
                k = max(k, nxt // W)
                if k >= horizon_windows:
                    break
                for ln in lanes:  # the skipped windows' barriers
                    ln.t = max(ln.t, k * W)
            end = (k + 1) * W
            steps = 0
            while any(min(self.deadlines(ln)) < end
                      or self.wanted(ln, ack_every) for ln in lanes):
                for ln in lanes:
                    for _ in range(batch):
                        if not self.sched_event(ln, end):
                            break
                for ln in lanes:
                    self.app(ln)
                for ln in lanes:
                    self.pull_phase(ln, ack_every)
                steps += 1
                if steps > step_limit:
                    raise RuntimeError("flow never settles in a window")
            if steps:
                for ln in lanes:
                    self.pull_phase(ln, 1)
            elif any(self.wanted(ln, 1) for ln in lanes):
                raise RuntimeError("a flow holds an ACK into a window "
                                   "it did no work in")
            for ln in lanes:
                ln.t = max(ln.t, end)
            k += 1
        a, b = self.a, self.b
        return {"complete_us": a.complete_us, "bytes_read": a.read_bytes,
                "seg_units": a.seg_units + b.seg_units,
                "wire_drops": a.wire_drops + b.wire_drops,
                "retransmits": a.conn.retransmits + b.conn.retransmits,
                "queue_drops": a.q_dropped + b.q_dropped}


DEFAULT_OPTS = {"ack_every": 2, "gso_segs": 16, "sched_batch": 8,
                "pull_cap": 8}


class TgenReference:
    """The reference run of one deployment: `run()` gives per-flow
    arrays in plan order and the reconciled totals. `machine` is the
    class of each side's TCP (`Conn`)."""

    def __init__(self, dep: dict, queue_slots: int = 256,
                 max_doublings: int = 3, opts: dict | None = None,
                 control: str | None = None, machine=Conn):
        if control not in (None, *CONTROLS):
            raise ValueError(f"unknown control {control!r}")
        self.dep = dep
        self.flows = flow_plan(
            dep, np.float16 if control == "float16_loss" else np.float32)
        self.queue_slots = queue_slots
        self.max_doublings = max_doublings
        self.opts = {**DEFAULT_OPTS, **(opts or {})}
        self.control = control
        self.machine = machine

    def buckets(self) -> dict:
        out: dict[int, list[int]] = {}
        for f, fl in enumerate(self.flows):
            out.setdefault(fl["window_us"], []).append(f)
        return out

    def horizon(self, window_us: int) -> int:
        """Windows the host driver runs at most: whole dispatches of
        about one simulated second, the first past stop_time included."""
        chunk = max(1, 1_000_000 // window_us)
        stop_us = self.dep["stop_s"] * 1_000_000
        n_chunks = int(stop_us / 1e6 * 1e6 / (window_us * chunk)) + 1
        return n_chunks * chunk

    def run(self) -> dict:
        """Every flow, and the reconciled totals."""
        F = len(self.flows)
        keys = ("complete_us", "bytes_read", "seg_units", "wire_drops",
                "retransmits", "queue_drops")
        out = {k: np.zeros(F, np.int64) for k in keys}
        for window_us, idx in self.buckets().items():
            H = self.horizon(window_us)
            Q = self.queue_slots
            for attempt in range(self.max_doublings + 1):
                res = {}
                for i, f in enumerate(idx):
                    draw = f if self.control == "plan_order_loss" else i
                    res[f] = FlowSim(self.flows[f], draw, Q, self.opts,
                                     self.machine).run(H)
                dropped = sum(r["queue_drops"] for r in res.values())
                if dropped == 0 or attempt == self.max_doublings:
                    break
                Q *= 2
            for f, r in res.items():
                for k in keys:
                    out[k][f] = r[k]
        size = np.array([f["size"] for f in self.flows], np.int64)
        out["packets_sent"] = int(out["seg_units"].sum())
        out["packets_dropped"] = int(out["wire_drops"].sum()
                                     + out["queue_drops"].sum())
        out["failures"] = int((out["bytes_read"] < size).sum())
        out["sim_time_ns"] = self.dep["stop_s"] * 1_000_000_000
        return out


PER_FLOW = ("complete_us", "bytes_read", "seg_units", "wire_drops",
            "retransmits")


def compare(got: dict, want: dict, sizes) -> dict:
    """{flows_differing, bytes_gap, stats_gap} of the program's output
    `got` against the reference's `want`. `bytes_gap` holds every flow
    to its size, whatever the reference read."""
    differ = np.zeros(len(sizes), bool)
    for k in PER_FLOW:
        differ |= (np.asarray(got[k], np.int64)
                   != np.asarray(want[k], np.int64))
    sizes = np.asarray(sizes, np.int64)
    return {
        "flows_differing": int(differ.sum()),
        "bytes_gap": int(np.abs(np.asarray(got["bytes_read"], np.int64)
                                - sizes).sum()),
        "stats_gap": (abs(got["packets_sent"] - want["packets_sent"])
                      + abs(got["packets_dropped"] - want["packets_dropped"])
                      + abs(got["failures"] - want["failures"])
                      + int(got["sim_time_ns"] != want["sim_time_ns"])),
    }
