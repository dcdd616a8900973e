"""Plain reference for the PHOLD cells.

A straightforward numpy implementation of the network plane's window
semantics on the PHOLD workload, written from the semantics and not from
the program: it imports nothing of `shadow_tpu` and takes nothing the
program made. It keeps only the live packets (a few per host), as flat
arrays with (host, position) coordinates, and replays the run window by
window from the same inputs the benchmark hands the program.

Per window [t, t + W), for every host:
- rebase stored times by the shift; refill the token bucket in whole
  milliseconds (sub-millisecond remainder carried);
- egress in FIFO order (priority, then ring position); a prefix-sum token
  gate decides what is sendable;
- each sendable packet draws u from threefry2x32(key, (host, counter)),
  counter = the host's draw count + its position in the ordered ring;
  it is lost when u < loss[node(src), node(dst)] and it is not control;
- delivery = max(send + latency, W) for unclamped packets;
- arrivals enter the destination's ingress ring after what is there, in
  (deliver, src, seq, source ring position) order, up to the ring size;
  the rest are counted as overflow at the destination;
- everything due (deliver < W) is delivered in (deliver, src, seq,
  position) order; the rest stays, re-packed in that order;
- PHOLD: each delivery at host h makes one packet from h to
  (src*40503 + seq*1566083941 + round*97) mod N in int32 arithmetic,
  seq = h's spawn counter + its rank among h's deliveries, appended to h's
  egress ring up to its size (the rest counted as overflow at h).

`control` breaks one stated guarantee, for the control run that has to
come out as not correct: "tiebreak" presents deliveries due at the same
time in (seq, src) order instead of (src, seq) (event-order
determinism), as a sort key packed with its fields swapped would."""

from __future__ import annotations

import numpy as np

I32_MAX = 2**31 - 1
NO_CLAMP = -(2**30)
MS = 1_000_000
MTU = 1500


def wrap32(x):
    """int64 array -> the int32 value two's-complement arithmetic keeps."""
    x = np.asarray(x, np.int64)
    return ((x + 2**31) % 2**32) - 2**31


def threefry2x32(k0: int, k1: int, x0, x1):
    """Threefry-2x32, 20 rounds (Salmon et al., SC'11), on uint32
    arrays; returns the two output words."""
    x0 = np.asarray(x0, np.uint32).copy()
    x1 = np.asarray(x1, np.uint32).copy()
    ks = [np.uint32(k0), np.uint32(k1),
          np.uint32(k0) ^ np.uint32(k1) ^ np.uint32(0x1BD11BDA)]
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))

    def rounds(x0, x1, rs):
        for r in rs:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        return x0, x1

    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        x0, x1 = rounds(x0, x1, rot[i % 2])
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def uniform(key, host, counter):
    """The loss draw: 24 high bits of the first threefry word, in
    float32 [0, 1)."""
    bits, _ = threefry2x32(key[0], key[1], host.astype(np.uint32),
                           counter.astype(np.uint32))
    return (bits >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-24)


def _group_rank(group):
    """Rank of each item within its run of equal `group` values (group
    sorted ascending)."""
    n = group.shape[0]
    if n == 0:
        return np.zeros(0, np.int64)
    idx = np.arange(n)
    start = np.r_[True, group[1:] != group[:-1]]
    first = np.maximum.accumulate(np.where(start, idx, 0))
    return idx - first


def _take(d: dict, o):
    return {k: v[o] for k, v in d.items()}


def _cat(a: dict, b: dict):
    return {k: np.concatenate([a[k], b[k]]) for k in a}


class PholdReference:
    """State of the whole world; `step(shift, round)` advances one
    window."""

    def __init__(self, inp: dict, *, control: str | None = None):
        self.N, self.CE, self.CI = inp["n_hosts"], inp["egress_cap"], \
            inp["ingress_cap"]
        self.W = int(inp["window_ns"])
        self.key = (int(inp["key"][0]), int(inp["key"][1]))
        self.lat = np.asarray(inp["latency_ns"], np.int64)
        self.loss = np.asarray(inp["loss"], np.float32)
        self.node = np.asarray(inp["host_node"], np.int64)
        self.pkt_bytes = int(inp["packet_bytes"])
        self.control = control
        N = self.N
        bw = np.asarray(inp["up_bw_bps"], np.int64)
        self.rate = np.minimum(np.maximum(1, (bw // 8) // 1000),
                               2**30 - MTU).astype(np.int64)
        self.cap = self.rate + MTU
        self.bal = self.cap.copy()
        z = lambda: np.zeros(N, np.int64)
        self.rem, self.rng = z(), z()
        self.n_sent, self.n_loss, self.n_over, self.n_deliv = z(), z(), z(), z()
        self.spawn = np.full(N, int(inp["spawn_seq0"]), np.int64)
        self.delivered_total = 0
        # initial egress: the seed packets, ring position = rank by seq
        src, dst, seq = (np.asarray(inp[k], np.int64)
                         for k in ("seed_src", "seed_dst", "seed_seq"))
        o = np.lexsort((seq, src))
        src, dst, seq = src[o], dst[o], seq[o]
        pos = _group_rank(src)
        keep = pos < self.CE
        self.n_over += np.bincount(src[~keep], minlength=N)
        src, dst, seq, pos = src[keep], dst[keep], seq[keep], pos[keep]
        n = src.shape[0]
        self.eg = dict(host=src, pos=pos, dst=dst,
                       nbytes=np.full(n, self.pkt_bytes, np.int64),
                       prio=seq.copy(), seq=seq, ctrl=np.zeros(n, bool),
                       tsend=np.zeros(n, np.int64),
                       clamp=np.full(n, NO_CLAMP, np.int64),
                       sock=np.zeros(n, np.int64))
        e = np.zeros(0, np.int64)
        self.ing = dict(host=e, pos=e, src=e, nbytes=e, seq=e, sock=e,
                        deliver=e)

    # -- one window -------------------------------------------------------
    def step(self, shift: int, ridx: int):
        N, W = self.N, self.W
        eg, ing = self.eg, self.ing
        # 1. rebase, refill
        ing["deliver"] = ing["deliver"] - shift
        eg["tsend"] = eg["tsend"] - shift
        eg["clamp"] = np.where(eg["clamp"] != NO_CLAMP,
                               eg["clamp"] - shift, eg["clamp"])
        rem_total = self.rem + shift % MS
        elapsed = shift // MS + rem_total // MS
        self.rem = rem_total % MS
        head = np.maximum(self.cap - self.bal, 0)
        need = (head + self.rate - 1) // self.rate
        eff = np.minimum(elapsed, need)
        self.bal = self.cap - np.maximum(head - self.rate * eff, 0)

        # 2. FIFO order, token gate
        eg = _take(eg, np.lexsort((eg["pos"], eg["prio"], eg["host"])))
        h = eg["host"]
        j = _group_rank(h)
        csum = np.cumsum(eg["nbytes"])
        first = np.arange(h.shape[0]) - j  # each host's first packet
        cum = csum - (csum - eg["nbytes"])[first]
        sendable = cum <= self.bal[h]
        self.bal = self.bal - np.bincount(
            h, weights=np.where(sendable, eg["nbytes"], 0),
            minlength=N).astype(np.int64)

        # 3. loss draw, latency
        u = uniform(self.key, h, self.rng[h] + j)
        ns, nd = self.node[h], self.node[eg["dst"]]
        lost = sendable & (u < self.loss[ns, nd]) & ~eg["ctrl"]
        sent = sendable & ~lost
        self.rng = self.rng + np.bincount(h, weights=sendable,
                                          minlength=N).astype(np.int64)
        clamp_eff = np.where(eg["clamp"] == NO_CLAMP, W, eg["clamp"])
        deliver = np.maximum(eg["tsend"] + self.lat[ns, nd], clamp_eff)
        self.n_sent += np.bincount(h, weights=sent, minlength=N).astype(
            np.int64)
        self.n_loss += np.bincount(h, weights=lost, minlength=N).astype(
            np.int64)

        # 4. ingress re-packed by (deliver, position)
        ing = _take(ing, np.lexsort((ing["pos"], ing["deliver"],
                                     ing["host"])))
        ing["pos"] = _group_rank(ing["host"])
        n_in = np.bincount(ing["host"], minlength=N)

        # 5. route arrivals after what each ring holds
        a = dict(host=eg["dst"][sent], src=h[sent],
                 nbytes=eg["nbytes"][sent], seq=eg["seq"][sent],
                 sock=eg["sock"][sent], deliver=deliver[sent], j=j[sent])
        a = _take(a, np.lexsort((a["j"], a["seq"], a["src"], a["deliver"],
                                 a["host"])))
        r = _group_rank(a["host"])
        room = self.CI - n_in[a["host"]]
        ok = r < room
        self.n_over += np.bincount(a["host"][~ok], minlength=N)
        a = _take(a, ok)
        a["pos"] = n_in[a["host"]] + r[ok]
        del a["j"]
        ing = _cat(ing, a)

        # 5b. release what is due
        due = ing["deliver"] < W
        if self.control == "tiebreak":
            o = np.lexsort((ing["pos"], ing["src"], ing["seq"],
                            ing["deliver"], due, ing["host"]))
        else:
            o = np.lexsort((ing["pos"], ing["seq"], ing["src"],
                            ing["deliver"], due, ing["host"]))
        ing, due = _take(ing, o), due[o]
        d = _take(ing, due)
        self.ing = _take(ing, ~due)
        self.ing["pos"] = _group_rank(self.ing["host"])
        self.n_deliv += np.bincount(d["host"], minlength=N)
        self.delivered_total += int(d["host"].shape[0])

        # 6. what stays in egress, re-packed by (priority, position)
        left = _take(eg, ~sendable)
        left["pos"] = _group_rank(left["host"])

        # PHOLD respawn at each receiving host
        hr = d["host"]
        rk = _group_rank(hr)
        n_eg = np.bincount(left["host"], minlength=N)
        ndst = wrap32(d["src"] * 40503 + d["seq"] * 1566083941
                      + ridx * 97) % N
        nseq = self.spawn[hr] + rk
        ok = n_eg[hr] + rk < self.CE
        self.n_over += np.bincount(hr[~ok], minlength=N)
        n = int(ok.sum())
        new = dict(host=hr[ok], pos=(n_eg[hr] + rk)[ok], dst=ndst[ok],
                   nbytes=np.full(n, self.pkt_bytes, np.int64),
                   prio=nseq[ok], seq=nseq[ok], ctrl=np.zeros(n, bool),
                   tsend=np.zeros(n, np.int64),
                   clamp=np.full(n, NO_CLAMP, np.int64),
                   sock=np.zeros(n, np.int64))
        self.spawn = self.spawn + np.bincount(hr, minlength=N)
        self.eg = _cat(left, new)

    def run(self, n_windows: int, start_round: int = 0):
        for r in range(start_round, start_round + n_windows):
            self.step(0 if r == 0 else self.W, r)
        return self

    # -- the state as dense rings, live lanes front-packed ---------------
    def dense(self) -> dict:
        N, CE, CI = self.N, self.CE, self.CI

        def put(d, cap, fields):
            out = {}
            for k, fill in fields.items():
                a = np.full((N, cap), fill, np.int64)
                a[d["host"], d["pos"]] = d[k]
                out[k] = a
            v = np.zeros((N, cap), bool)
            v[d["host"], d["pos"]] = True
            return out, v

        eg, egv = put(self.eg, CE, dict(dst=-1, nbytes=0, prio=I32_MAX,
                                        seq=0, ctrl=0, tsend=0,
                                        clamp=NO_CLAMP, sock=0))
        ing, inv = put(self.ing, CI, dict(src=-1, nbytes=0, seq=0, sock=0,
                                          deliver=I32_MAX))
        return dict(
            eg=eg, eg_valid=egv, ing=ing, in_valid=inv,
            hosts=dict(tb_balance=self.bal, tb_rem_ns=self.rem,
                       rng_counter=self.rng, n_sent=self.n_sent,
                       n_loss_dropped=self.n_loss,
                       n_overflow_dropped=self.n_over,
                       n_delivered=self.n_deliv, spawn_seq=self.spawn),
            delivered_total=self.delivered_total)


def compare(got: dict, want: dict) -> dict:
    """The numbers compared: hosts whose rings or counters differ from
    the reference in any live lane or field, and the gap in deliveries
    counted over the run. Both are exact (limit 0)."""
    N = want["eg_valid"].shape[0]
    bad = np.zeros(N, bool)
    bad |= (got["eg_valid"] != want["eg_valid"]).any(axis=1)
    bad |= (got["in_valid"] != want["in_valid"]).any(axis=1)
    for part, valid in (("eg", "eg_valid"), ("ing", "in_valid")):
        v = want[valid]
        for k, w in want[part].items():
            g = np.asarray(got[part][k], np.int64)
            bad |= ((g != w) & v).any(axis=1)
    for k, w in want["hosts"].items():
        bad |= np.asarray(got["hosts"][k], np.int64) != w
    return {
        "hosts_differing": int(bad.sum()),
        "deliveries_gap": abs(int(got["delivered_total"])
                              - int(want["delivered_total"])),
    }
