"""shadowlint pass 5 (shadowbatch): world-axis independence proofs.

ROADMAP item 4's ensemble contract — "world b of a batched run is
bitwise-equal to its solo run" — was only checkable by running every
world twice. This module retires that 2x-run trap the same way
shadowprove retired presence-invisibility sampling: the batched jaxpr
of every registered plane entry (``jaxpr_audit.traced`` gains
``@vmapW{w}`` variants) is abstract-interpreted once, statically, and
three rule families gate on the result:

- **SL701 world-isolation** — axis-provenance tracking over the
  batched jaxpr. Every input leaf of the vmapped entry carries the
  world axis at dim 0; the walk transfers "where does world w's data
  live" through every primitive (broadcast moves it by
  ``broadcast_dimensions``, transpose permutes it, reshape must keep
  it a standalone dim, gather/scatter must carry it in the explicit
  ``operand_batching_dims``/``*_indices_batching_dims``) and emits a
  finding for any primitive that reduces, gathers, scatters, sorts,
  concatenates, or pads ACROSS it — op + ``file:line`` + the
  offending axis. Zero findings is the world-isolation theorem: no
  dataflow path mixes two worlds, so world b's outputs are a function
  of world b's inputs alone.

- **SL702 RNG stream disjointness** — the per-world key derivation
  (``tpu/elastic.world_key``: ``fold_in(root, seed)``) is walked
  symbolically, proving the derived key is INJECTIVE in the seed:
  mod-2^32 bijections (add/sub/xor const, mul odd const) preserve
  injectivity outright, non-bijective affine steps fall back to a
  wrap-free interval argument over the declared seed domain (the
  SL506 machinery on fold-in arithmetic), and a threefry invocation
  under a FIXED key is a block-cipher bijection of its counter block.
  Distinct seeds => distinct derived keys => the per-world cipher
  invocation sets ``{(key_b, counter)}`` are pairwise disjoint — the
  counter-stream disjointness every per-world draw inherits.

- **SL703 vmap-traceability census** — every registered entry either
  vmaps cleanly at TWO world counts with a stable primitive census
  (same graph, wider arrays — the shape-polymorphism witness), or
  carries a written refusal rationale in ``VMAP_REFUSALS`` (none
  does today). An entry built on an opaque kernel (a ``pallas_call``
  body is invisible to the provenance walk) would have to refuse —
  registered, not silent; a refusal naming a no-longer-registered
  entry is itself a finding.

Soundness caveat (mirrors ``ranges.py``): SL701 proves DATAFLOW
isolation over the jaxpr. Two constructs sit outside pure dataflow and
are handled by jax's own vmap contract, with the worlds-parity test
(tests/test_ensemble.py) as the runtime witness: a batched while-loop
predicate (the batching rule freezes finished worlds per-world in the
lowering) and the trip-count sharing it implies. Everything the jaxpr
CAN express is proven, not sampled.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from jax.extend import core as _core

from . import jaxpr_audit
from .rules import Finding

__all__ = [
    "BATCH_ALLOWED",
    "BATCH_WORLD_COUNTS",
    "BatchEntry",
    "RngObligation",
    "VMAP_REFUSALS",
    "batch_entries",
    "check_all_batch",
    "check_rng_disjoint",
    "check_vmap_census",
    "check_world_axis",
    "prove_fold_chain",
    "rng_obligations",
    "world_axis_findings",
]

#: the two audited world counts: tracing the same entry at both and
#: comparing the primitive census is the cheap witness that the
#: batched graph is world-count-polymorphic (wider arrays, same ops)
BATCH_WORLD_COUNTS = (2, 3)

#: entries that REFUSE the vmap surface, with the written rationale
#: SL703 requires (a refusal is a registered engineering decision, not
#: a silent skip; one naming a de-registered entry is a finding)
VMAP_REFUSALS: dict[str, str] = {}

#: (entry key, rule) -> justification for a deliberately-accepted
#: finding — the pass-5 analogue of the jaxpr-audit allow-lists
#: (batched findings have no source comment to anchor a suppression)
BATCH_ALLOWED: dict[tuple[str, str], str] = {}


# --------------------------------------------------------------------------
# SL701: the axis-provenance interpreter
# --------------------------------------------------------------------------

#: shape-preserving lane-wise primitives: the world axis passes through
#: untouched as long as every world-batched operand agrees on where it
#: is (two different positions would lane-wise combine world i with
#: world j — a cross-world mix, flagged)
_ELEMENTWISE = frozenset({
    "abs", "acos", "add", "and", "asin", "atan", "atan2", "cbrt",
    "ceil", "clamp", "clz", "complex", "conj", "convert_element_type",
    "copy", "cos", "cosh", "device_put", "div", "eq", "erf", "erfc",
    "erf_inv", "exp", "exp2", "expm1", "floor", "ge", "ge_to", "gt",
    "gt_to", "imag",
    "integer_pow", "is_finite", "le", "le_to", "lt_to", "log",
    "log1p", "log2",
    "logistic", "lt", "max", "min", "mul", "ne", "neg", "nextafter",
    "not", "or", "population_count", "pow", "random_fold_in",
    "random_seed", "real", "reduce_precision", "rem", "round",
    "rsqrt", "select_n", "shift_left", "shift_right_arithmetic",
    "shift_right_logical", "sign", "sin", "sinh", "sqrt", "square",
    "stop_gradient", "sub", "tan", "tanh", "threefry2x32", "xor",
})

#: primitives whose outputs keep the operand's LEADING dims (the key
#: dims) and append/expand trailing implementation dims
_PASS_LEADING = frozenset({"random_bits", "random_split",
                           "random_unwrap"})

_REDUCES = frozenset({
    "argmax", "argmin", "reduce", "reduce_and", "reduce_max",
    "reduce_min", "reduce_or", "reduce_prod", "reduce_sum",
    "reduce_xor",
})

_CUMULATIVE = frozenset({"cumlogsumexp", "cummax", "cummin", "cumprod",
                         "cumsum"})

_CALL_LIKE = ("jit", "closed_call", "core_call", "remat", "checkpoint",
              "custom_jvp_call", "custom_vjp_call",
              "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr")

_SUB_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _shape(atom) -> tuple:
    return tuple(getattr(getattr(atom, "aval", None), "shape", ()) or ())


def _source_of(eqn) -> str:
    from .ranges import _source_line

    return _source_line(eqn)


class _WorldWalk:
    """One axis-provenance pass over a batched jaxpr.

    Per-var lattice value: ``None`` (world-free — identical across
    worlds, whatever its shape) or ``int d`` (world w's data lives at
    index w of axis d). Loop carries run a quiet fixpoint first
    (``None -> d`` is the only upward move, so ``len(carry)+1`` rounds
    suffice) and findings are emitted on one final loud pass."""

    def __init__(self, where: str, w: int):
        self.where = where
        self.w = w
        self.findings: list[Finding] = []
        self.batched_census: dict[str, int] = {}
        self.quiet = 0

    # -- findings ----------------------------------------------------------

    def _find(self, eqn, msg: str):
        if self.quiet:
            return
        src = _source_of(eqn)
        loc = f" at {src}" if src else ""
        text = f"{msg}{loc}"
        if any(f.message == text for f in self.findings):
            return  # one finding per distinct (op, reason, line)
        self.findings.append(Finding("SL701", self.where, 0, 0, text))

    def _agree(self, eqn, wds, what: str):
        """The single world-axis position among `wds`, flagging a mix."""
        ds = sorted({d for d in wds if d is not None})
        if len(ds) > 1:
            self._find(
                eqn, f"cross-world `{eqn.primitive.name}`: {what} "
                f"operands carry the world axis at different dims "
                f"{ds} (lane-wise combine of two worlds)")
        return ds[0] if ds else None

    # -- jaxpr walk --------------------------------------------------------

    def run(self, jaxpr_like, in_wds) -> list:
        raw = getattr(jaxpr_like, "jaxpr", jaxpr_like)
        env: dict = {}

        def read(v):
            if isinstance(v, _core.Literal):
                return None
            return env.get(v)

        if len(raw.invars) != len(in_wds):
            raise ValueError(
                f"jaxpr arity mismatch in {self.where}: "
                f"{len(raw.invars)} invars, {len(in_wds)} world dims")
        for var, d in zip(raw.invars, in_wds):
            if d is not None:
                env[var] = d

        for eqn in raw.eqns:
            wds = [read(v) for v in eqn.invars]
            if not self.quiet and any(d is not None for d in wds):
                name = eqn.primitive.name
                self.batched_census[name] = \
                    self.batched_census.get(name, 0) + 1
            outs = self.eval_eqn(eqn, wds)
            for var, d in zip(eqn.outvars, outs):
                if d is not None:
                    env[var] = d

        return [read(v) for v in raw.outvars]

    # -- transfer functions ------------------------------------------------

    def eval_eqn(self, eqn, wds) -> list:
        name = eqn.primitive.name
        params = eqn.params
        n_out = len(eqn.outvars)

        if all(d is None for d in wds):
            # world-free inputs => world-free outputs, for ANY
            # primitive (deterministic ops replicate identically
            # across worlds); control flow still needs no descent
            return [None] * n_out

        if name in _ELEMENTWISE:
            d = self._agree(eqn, wds, "elementwise")
            return [d] * n_out

        if name in _PASS_LEADING:
            return [wds[0]] * n_out

        if name == "random_wrap":
            d = wds[0]
            if d is not None and d >= len(_shape(eqn.invars[0])) - 1:
                self._find(
                    eqn, "cross-world `random_wrap`: the world axis "
                    f"(dim {d}) is packed into the key impl words")
                return [None] * n_out
            return [d] * n_out

        if name == "broadcast_in_dim":
            d = wds[0]
            bcd = tuple(params["broadcast_dimensions"])
            return [bcd[d] if d is not None else None] * n_out

        if name == "transpose":
            d = wds[0]
            perm = tuple(params["permutation"])
            return [perm.index(d) if d is not None else None] * n_out

        if name == "reshape":
            return self._reshape(eqn, wds)

        if name == "squeeze":
            d = wds[0]
            dims = tuple(params.get("dimensions") or ())
            if d is None:
                return [None] * n_out
            return [d - sum(1 for r in dims if r < d)] * n_out

        if name == "rev":
            d = wds[0]
            dims = tuple(params.get("dimensions") or ())
            if d is not None and d in dims:
                self._find(
                    eqn, "cross-world `rev`: reverses along the world "
                    f"axis (dim {d}) — world b reads world W-1-b")
            return [d] * n_out

        if name in _REDUCES:
            axes = tuple(params.get("axes", params.get("dimensions"))
                         or ())
            d = self._agree(eqn, wds, "reduction")
            if d is None:
                return [None] * n_out
            if d in axes:
                self._find(
                    eqn, f"cross-world `{name}`: reduces over the "
                    f"world axis (dim {d}) — one output mixes every "
                    "world")
                return [None] * n_out
            return [d - sum(1 for a in axes if a < d)] * n_out

        if name in _CUMULATIVE:
            d = wds[0]
            if d is not None and params.get("axis") == d:
                self._find(
                    eqn, f"cross-world `{name}`: accumulates along "
                    f"the world axis (dim {d})")
            return [d] * n_out

        if name == "sort":
            d = self._agree(eqn, wds, "sort")
            if d is not None and params.get("dimension") == d:
                self._find(
                    eqn, "cross-world `sort`: sorts along the world "
                    f"axis (dim {d}) — worlds exchange lanes")
            # ONE key-derived permutation applies to every operand, so
            # any batched key makes every output world-dependent
            return [d] * n_out

        if name == "concatenate":
            d = self._agree(eqn, wds, "concatenate")
            if d is not None and params.get("dimension") == d:
                self._find(
                    eqn, "cross-world `concatenate`: concatenates "
                    f"along the world axis (dim {d})")
            return [d] * n_out

        if name == "pad":
            d = wds[0]
            cfg = tuple(params.get("padding_config") or ())
            if d is not None and d < len(cfg) and \
                    tuple(cfg[d]) != (0, 0, 0):
                self._find(
                    eqn, "cross-world `pad`: pads the world axis "
                    f"(dim {d}, config {tuple(cfg[d])}) — the world "
                    "count changes mid-graph")
            return [d] * n_out

        if name == "slice":
            return self._slice(eqn, wds)

        if name == "dynamic_slice":
            return self._dynamic_slice(eqn, wds)

        if name == "dynamic_update_slice":
            return self._dynamic_update_slice(eqn, wds)

        if name == "split":
            d = self._agree(eqn, wds, "split")
            if d is not None and params.get("axis") == d:
                self._find(
                    eqn, "cross-world `split`: splits the world axis "
                    f"(dim {d})")
            return [d] * n_out

        if name == "top_k":
            d = wds[0]
            rank = len(_shape(eqn.invars[0]))
            if d is not None and d == rank - 1:
                self._find(
                    eqn, "cross-world `top_k`: selects along the "
                    f"world axis (dim {d})")
            return [d] * n_out

        if name == "gather":
            return self._gather(eqn, wds)

        if name.startswith("scatter"):
            return self._scatter(eqn, wds)

        if name == "dot_general":
            return self._dot_general(eqn, wds)

        if name == "iota":
            return [None] * n_out

        if name in _CALL_LIKE:
            return self._call_like(eqn, wds)

        if name == "cond":
            return self._cond(eqn, wds)

        if name == "while":
            return self._while(eqn, wds)

        if name == "scan":
            return self._scan(eqn, wds)

        if name == "pallas_call":
            self._find(
                eqn, "cross-world hazard: opaque `pallas_call` with a "
                "world-batched operand — the kernel body is invisible "
                "to the provenance walk (register a VMAP_REFUSALS "
                "rationale for such an entry instead)")
            return [None] * n_out

        self._find(
            eqn, f"unmodeled primitive `{name}` with a world-batched "
            "operand: the axis-provenance walk has no transfer rule "
            "for it, so world isolation is unproven here")
        d = self._agree(eqn, wds, "unmodeled")
        return [d] * n_out

    # -- structural handlers -----------------------------------------------

    def _reshape(self, eqn, wds):
        d = wds[0]
        n_out = len(eqn.outvars)
        if d is None:
            return [None] * n_out
        if eqn.params.get("dimensions") is not None:
            self._find(
                eqn, "cross-world `reshape`: a transposing reshape "
                f"(dimensions=...) moves the world axis (dim {d}) "
                "unanalyzably")
            return [None] * n_out
        in_shape = _shape(eqn.invars[0])
        out_shape = tuple(eqn.params["new_sizes"])
        before = int(np.prod(in_shape[:d], dtype=np.int64))
        prefix = 1
        for dp, size in enumerate(out_shape):
            if prefix == before and size == in_shape[d]:
                return [dp] * n_out
            prefix *= size
        self._find(
            eqn, "cross-world `reshape`: the world axis (dim "
            f"{d} of {list(in_shape)}) does not survive as a "
            f"standalone dim of {list(out_shape)} — worlds are "
            "folded together")
        return [None] * n_out

    def _slice(self, eqn, wds):
        d = wds[0]
        n_out = len(eqn.outvars)
        if d is None:
            return [None] * n_out
        p = eqn.params
        shape = _shape(eqn.invars[0])
        start = tuple(p["start_indices"])[d]
        limit = tuple(p["limit_indices"])[d]
        stride = tuple(p["strides"] or [1] * len(shape))[d]
        if (start, limit, stride) != (0, shape[d], 1):
            self._find(
                eqn, "cross-world `slice`: slices the world axis "
                f"(dim {d}: [{start}:{limit}:{stride}] of "
                f"{shape[d]}) — worlds are dropped or renumbered")
        return [d] * n_out

    def _dynamic_slice(self, eqn, wds):
        d = wds[0]
        n_out = len(eqn.outvars)
        if any(x is not None for x in wds[1:]):
            self._find(
                eqn, "cross-world `dynamic_slice`: a world-batched "
                "start index survived batching (expected a gather)")
        if d is None:
            return [None] * n_out
        sizes = tuple(eqn.params["slice_sizes"])
        shape = _shape(eqn.invars[0])
        if sizes[d] != shape[d]:
            self._find(
                eqn, "cross-world `dynamic_slice`: takes a strict "
                f"subset of the world axis (dim {d}: {sizes[d]} of "
                f"{shape[d]} worlds)")
        return [d] * n_out

    def _dynamic_update_slice(self, eqn, wds):
        d, du = wds[0], wds[1]
        n_out = len(eqn.outvars)
        if any(x is not None for x in wds[2:]):
            self._find(
                eqn, "cross-world `dynamic_update_slice`: a "
                "world-batched start index survived batching")
        if d is None and du is None:
            return [None] * n_out
        shape = _shape(eqn.invars[0])
        ushape = _shape(eqn.invars[1])
        start_d = eqn.invars[2 + (d if d is not None else du)]
        full = (d is not None and du == d
                and ushape[d] == shape[d]
                and isinstance(start_d, _core.Literal)
                and int(start_d.val) == 0)
        if not full:
            self._find(
                eqn, "cross-world `dynamic_update_slice`: the update "
                "does not cover the whole world axis aligned at 0 "
                f"(operand dim {d}, update dim {du})")
        return [d if d is not None else du] * n_out

    def _gather(self, eqn, wds):
        wo, wi = wds[0], wds[1]
        n_out = len(eqn.outvars)
        p = eqn.params
        dn = p["dimension_numbers"]
        obd = tuple(int(d) for d in
                    (getattr(dn, "operand_batching_dims", ()) or ()))
        sibd = tuple(int(d) for d in
                     (getattr(dn, "start_indices_batching_dims", ())
                      or ()))
        op_shape = _shape(eqn.invars[0])
        idx_rank = len(_shape(eqn.invars[1]))
        out_rank = len(_shape(eqn.outvars[0]))
        offset = tuple(dn.offset_dims)
        collapsed = set(dn.collapsed_slice_dims)
        start_map = set(dn.start_index_map)
        sizes = tuple(p["slice_sizes"])
        batch_out = [dp for dp in range(out_rank) if dp not in offset]
        idx_batch = [i for i in range(idx_rank) if i != idx_rank - 1]

        def out_from_idx_dim(sib):
            # indices dims (minus the trailing coordinate-vector dim)
            # map IN ORDER onto the non-offset output dims
            return [batch_out[idx_batch.index(sib)]] * n_out

        if wi is not None and wi == idx_rank - 1:
            self._find(
                eqn, "cross-world `gather`: the world axis (indices "
                f"dim {wi}) feeds the coordinate vector — one lookup "
                "mixes coordinates from every world")
            wi = None
        if wo is not None and wo in obd:
            # the structural proof: a declared operand batching dim is
            # blocked per-world by gather semantics — output block w
            # reads ONLY operand block w, whatever the index values
            sib = sibd[obd.index(wo)]
            if wi is not None and wi != sib:
                self._find(
                    eqn, "cross-world `gather`: world-batched indices "
                    f"(dim {wi}) not aligned with the operand's "
                    f"batching dim pairing (expected indices dim "
                    f"{sib})")
            return out_from_idx_dim(sib)
        if wo is not None:
            # no batching-dim declaration for the world axis: it may
            # still ride through wholesale as an un-indexed full slice
            ops_kept = [d for d in range(len(op_shape))
                        if d not in collapsed and d not in obd]
            if wo not in start_map and wo in ops_kept and \
                    sizes[wo] == op_shape[wo]:
                return [offset[ops_kept.index(wo)]] * n_out
            self._find(
                eqn, "cross-world `gather`: indexes across the world "
                f"axis (operand dim {wo}: not in "
                f"operand_batching_dims={list(obd)}, and not a full "
                f"un-indexed slice — slice_sizes[{wo}]={sizes[wo]} "
                f"of {op_shape[wo]}) — world b can read world c's "
                "lanes")
            return [None] * n_out
        if wi is not None:
            if wi in sibd:
                return out_from_idx_dim(wi)
            # shared-table lookup with per-world indices: safe
            return out_from_idx_dim(wi)
        return [None] * n_out

    def _scatter(self, eqn, wds):
        name = eqn.primitive.name
        wo, wi, wu = wds[0], wds[1], wds[2]
        n_out = len(eqn.outvars)
        dn = eqn.params["dimension_numbers"]
        obd = tuple(int(d) for d in
                    (getattr(dn, "operand_batching_dims", ()) or ()))
        sibd = tuple(int(d) for d in
                     (getattr(dn, "scatter_indices_batching_dims", ())
                      or ()))
        op_rank = len(_shape(eqn.invars[0]))
        idx_rank = len(_shape(eqn.invars[1]))
        upd_rank = len(_shape(eqn.invars[2]))
        uwd = tuple(int(d) for d in dn.update_window_dims)
        inserted = set(int(d) for d in dn.inserted_window_dims)
        upd_batch = [dp for dp in range(upd_rank) if dp not in uwd]
        ops_window = [d for d in range(op_rank)
                      if d not in inserted and d not in obd]

        if wi is not None and wi == idx_rank - 1:
            self._find(
                eqn, f"cross-world `{name}`: the world axis (indices "
                f"dim {wi}) feeds the coordinate vector — one write "
                "mixes coordinates from every world")
            wi = None
        # the structural proof: a declared batching-dim pairing blocks
        # the scatter per-world — update/index block w writes ONLY
        # operand block w, whatever the index VALUES are (replicated
        # world-free indices included)
        if wo is not None and wo in obd:
            sib = sibd[obd.index(wo)]
            if wi is not None and wi != sib:
                self._find(
                    eqn, f"cross-world `{name}`: world-batched "
                    f"indices (dim {wi}) not aligned with the "
                    f"operand's batching dim pairing (expected "
                    f"indices dim {sib})")
            return [wo] * n_out
        if wo is None and wi is not None and wi in sibd:
            return [obd[sibd.index(wi)]] * n_out
        if wi is None:
            # world-free indices (static-slice updates like
            # `x.at[:, 0].set(v)`): the batching rule carries the
            # world axis as a WINDOW dim — one scatter, and within
            # its window the updates' world dim maps elementwise onto
            # the operand's, so world w's row lands in world w's lane
            if wu is not None and wu in uwd:
                owd = ops_window[uwd.index(wu)]
                if wo is None or wo == owd:
                    return [owd] * n_out
                self._find(
                    eqn, f"cross-world `{name}`: the updates' world "
                    f"window dim maps to operand dim {owd} but the "
                    f"operand's world axis is dim {wo} — worlds are "
                    "transposed by the write")
                return [wo] * n_out
            if wu is None and wo is not None:
                if wo in ops_window:
                    # replicated world-free update written into every
                    # world's window slice: per-world isolated
                    return [wo] * n_out
                self._find(
                    eqn, f"cross-world `{name}`: a world-free index "
                    "selects a single lane ALONG the world axis "
                    f"(operand dim {wo} is scattered, not a window "
                    "dim) — one world's lane receives the write")
                return [wo] * n_out
        if wo is None and (wi is not None or wu is not None):
            self._find(
                eqn, f"cross-world `{name}`: per-world indices/"
                "updates scattered into a world-SHARED operand (no "
                "batching-dim pairing) — one array receives every "
                "world's writes")
            return [None] * n_out
        if wo is not None:
            self._find(
                eqn, f"cross-world `{name}`: writes across the world "
                f"axis (operand dim {wo} not carried in "
                f"operand_batching_dims={list(obd)}) — world b can "
                "write world c's lanes")
            return [wo] * n_out
        return [None] * n_out

    def _dot_general(self, eqn, wds):
        wl, wr = wds[0], wds[1]
        n_out = len(eqn.outvars)
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs_rank = len(_shape(eqn.invars[0]))
        rhs_rank = len(_shape(eqn.invars[1]))
        if (wl is not None and wl in lc) or \
                (wr is not None and wr in rc):
            self._find(
                eqn, "cross-world `dot_general`: contracts over the "
                f"world axis (lhs dim {wl}, rhs dim {wr})")
            return [None] * n_out
        if wl is not None and wl in lb:
            if wr is not None and wr in rb and \
                    list(lb).index(wl) == list(rb).index(wr):
                return [list(lb).index(wl)] * n_out
            self._find(
                eqn, "cross-world `dot_general`: lhs world batch dim "
                f"{wl} has no matching rhs batch dim (rhs {wr})")
            return [None] * n_out
        if wr is not None and wr in rb:
            self._find(
                eqn, "cross-world `dot_general`: rhs world batch dim "
                f"{wr} has no matching lhs batch dim (lhs {wl})")
            return [None] * n_out
        if wl is not None and wr is not None:
            self._find(
                eqn, "cross-world `dot_general`: both operands carry "
                f"free world axes (lhs {wl}, rhs {wr}) — the product "
                "pairs every world with every other")
            return [None] * n_out
        lhs_free = [dp for dp in range(lhs_rank)
                    if dp not in lc and dp not in lb]
        rhs_free = [dp for dp in range(rhs_rank)
                    if dp not in rc and dp not in rb]
        if wl is not None:
            return [len(lb) + lhs_free.index(wl)] * n_out
        return [len(lb) + len(lhs_free) + rhs_free.index(wr)] * n_out

    # -- control flow ------------------------------------------------------

    def _sub(self, params):
        for key in _SUB_JAXPR_KEYS:
            sub = params.get(key)
            if sub is not None:
                return sub
        return None

    def _call_like(self, eqn, wds):
        n_out = len(eqn.outvars)
        sub = self._sub(eqn.params)
        raw = getattr(sub, "jaxpr", sub) if sub is not None else None
        if raw is None or len(raw.invars) != len(wds):
            self._find(
                eqn, f"unmodeled call-like `{eqn.primitive.name}` "
                "with a world-batched operand (no aligned sub-jaxpr)")
            return [None] * n_out
        outs = self.run(sub, wds)
        return outs[:n_out] + [None] * (n_out - len(outs))

    def _join_carry(self, eqn, old, new, what: str):
        joined, changed = [], False
        for a, b in zip(old, new):
            if a is None and b is not None:
                joined.append(b)
                changed = True
            elif a is not None and b is not None and a != b:
                self._find(
                    eqn, f"cross-world `{eqn.primitive.name}`: "
                    f"{what} carry slot moves the world axis per "
                    f"iteration (dim {a} -> {b})")
                joined.append(a)
            else:
                joined.append(a)
        return joined, changed

    def _while(self, eqn, wds):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_c, body_c = wds[:cn], wds[cn:cn + bn]
        carry = list(wds[cn + bn:])
        self.quiet += 1
        try:
            for _ in range(len(carry) + 1):
                new = self.run(p["body_jaxpr"], list(body_c) + carry)
                carry, changed = self._join_carry(
                    eqn, carry, new, "while")
                if not changed:
                    break
        finally:
            self.quiet -= 1
        # loud final passes: body findings surface once, and the cond
        # is analyzed too (its output may legitimately stay batched —
        # the vmap batching rule owns per-world termination)
        final = self.run(p["body_jaxpr"], list(body_c) + carry)
        carry, _ = self._join_carry(eqn, carry, final, "while")
        self.run(p["cond_jaxpr"], list(cond_c) + carry)
        return carry

    def _scan(self, eqn, wds):
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        consts = wds[:nc]
        carry = list(wds[nc:nc + ncar])
        xs_body = []
        for i, d in enumerate(wds[nc + ncar:]):
            if d == 0:
                self._find(
                    eqn, "cross-world `scan`: iterates OVER the world "
                    "axis (xs leading dim is the world dim) — worlds "
                    "execute sequentially through one carry")
                xs_body.append(None)
            else:
                xs_body.append(None if d is None else d - 1)
        self.quiet += 1
        try:
            for _ in range(len(carry) + 1):
                outs = self.run(p["jaxpr"],
                                list(consts) + carry + xs_body)
                carry, changed = self._join_carry(
                    eqn, carry, outs[:ncar], "scan")
                if not changed:
                    break
        finally:
            self.quiet -= 1
        outs = self.run(p["jaxpr"], list(consts) + carry + xs_body)
        carry, _ = self._join_carry(eqn, carry, outs[:ncar], "scan")
        ys = [None if d is None else d + 1 for d in outs[ncar:]]
        return carry + ys

    def _cond(self, eqn, wds):
        n_out = len(eqn.outvars)
        pred, ops = wds[0], wds[1:]
        if pred is not None:
            self._find(
                eqn, "cross-world `cond`: the branch index is "
                "world-batched (escaped the select_n batching rule) — "
                "one branch choice would serve every world")
        outs = [None] * n_out
        for branch in eqn.params["branches"]:
            raw = getattr(branch, "jaxpr", branch)
            if len(raw.invars) != len(ops):
                self._find(eqn, "unmodeled `cond`: branch arity "
                                "mismatch with world-batched operands")
                return [None] * n_out
            b_outs = self.run(branch, list(ops))
            joined = []
            for a, b in zip(outs, b_outs):
                if a is not None and b is not None and a != b:
                    self._find(
                        eqn, "cross-world `cond`: branches return "
                        f"the world axis at different dims ({a} vs "
                        f"{b})")
                joined.append(a if a is not None else b)
            outs = joined
        return outs


def world_axis_findings(closed_jaxpr, where: str, w: int
                        ) -> tuple[list[Finding], dict]:
    """SL701 over one batched jaxpr whose every invar carries the
    world axis at dim 0 (constvars — closed-over params/roots — are
    world-free by construction). Returns (findings, entry_row)."""
    walk = _WorldWalk(where, w)
    raw = closed_jaxpr.jaxpr
    out_wds = walk.run(closed_jaxpr, [0] * len(raw.invars))
    row = {
        "entry": where,
        "world_count": w,
        "proved": not walk.findings,
        "batched_ops": dict(sorted(walk.batched_census.items())),
        "out_world_dims": [d for d in out_wds],
        "findings": len(walk.findings),
    }
    return walk.findings, row


# --------------------------------------------------------------------------
# SL702: the fold-chain injectivity prover
# --------------------------------------------------------------------------

_CONST, _INJ, _DEP = "const", "inj", "dep"


@dataclass
class RngObligation:
    """One registered per-world key-derivation chain.

    ``build`` returns ``(fn, args, seed_argnum, (lo, hi))`` — the
    traced chain, its example args, which argument is the per-world
    seed, and the declared seed domain the interval fallbacks assume
    (recorded in the report like the SL506 domain registry)."""

    name: str
    build: Callable[[], tuple]


def rng_obligations() -> list[RngObligation]:
    """The registered derivation surface: every function that turns a
    per-world seed into that world's RNG key. One entry today —
    ``tpu/elastic.world_key``, the chain ``drive_ensemble`` consumers
    and the ensemble audit entry both use."""
    def _world_key():
        import jax
        import jax.numpy as jnp

        from ..tpu import elastic

        root = jax.random.key(0)

        def fn(seed):
            return elastic.world_key(root, seed)

        return fn, (jnp.int32(0),), 0, (0, 2**31 - 1)

    return [RngObligation("shadow_tpu.tpu.elastic:world_key",
                          _world_key)]


def _bits(dtype) -> int:
    try:
        return int(np.dtype(dtype).itemsize) * 8
    except TypeError:
        return 32  # extended dtypes (PRNG keys): word-sized payload


def _lit_int(atom):
    if isinstance(atom, _core.Literal):
        val = np.asarray(atom.val)
        if val.size == 1 and np.issubdtype(val.dtype, np.integer):
            return int(val)
    return None


def _fits(iv, dtype) -> bool:
    if iv is None:
        return False
    lo, hi = iv
    try:
        info = np.iinfo(np.dtype(dtype))
    except (TypeError, ValueError):
        return False
    return info.min <= lo and hi <= info.max


def prove_fold_chain(ob: RngObligation) -> tuple[list[Finding], dict]:
    """Walk one derivation chain's jaxpr, proving the output key is
    injective in the seed argument. Statuses: ``const`` (seed-free),
    ``inj`` (provably injective in the seed over its domain), ``dep``
    (seed-dependent, injectivity lost), and pair tags for raw
    threefry outputs that are injective only JOINTLY."""
    fn, args, seed_ix, domain = ob.build()
    closed, _shape_, _args = jaxpr_audit.traced(f"{ob.name}@rng",
                                                lambda: (fn, args))
    raw = closed.jaxpr
    status: dict = {}
    ivs: dict = {}
    chain: list[dict] = []
    demoted: list[str] = []
    pair_n = 0

    for i, v in enumerate(raw.invars):
        status[v] = _INJ if i == seed_ix else _CONST
        if i == seed_ix:
            ivs[v] = tuple(domain)
    for v in raw.constvars:
        status[v] = _CONST

    def read(atom):
        if isinstance(atom, _core.Literal):
            return _CONST
        return status.get(atom, _CONST)

    def note(eqn, outs, why):
        chain.append({"prim": eqn.primitive.name,
                      "status": outs[0] if outs else _CONST,
                      "why": why})
        if outs and outs[0] == _DEP and not demoted:
            src = _source_of(eqn)
            demoted.append(f"`{eqn.primitive.name}` ({why})"
                           + (f" at {src}" if src else ""))

    def descend(eqn, sts):
        """Inline a call-like eqn (jit wrappers around jnp ops —
        `seed % 4` arrives as a jit-wrapped `remainder`): bind statuses
        and intervals through the sub-jaxpr and walk it in place."""
        sub = next((eqn.params[k] for k in _SUB_JAXPR_KEYS
                    if eqn.params.get(k) is not None), None)
        sraw = getattr(sub, "jaxpr", sub) if sub is not None else None
        if sraw is None or len(sraw.invars) != len(eqn.invars):
            return False
        for sv, at in zip(sraw.invars, eqn.invars):
            status[sv] = read(at)
            if not isinstance(at, _core.Literal) and at in ivs:
                ivs[sv] = ivs[at]
        for sv in sraw.constvars:
            status[sv] = _CONST
        walk(sraw.eqns)
        for ov, sv in zip(eqn.outvars, sraw.outvars):
            status[ov] = read(sv)
            if not isinstance(sv, _core.Literal) and sv in ivs:
                ivs[ov] = ivs[sv]
        return True

    def eval_one(eqn):
        nonlocal pair_n
        name = eqn.primitive.name
        sts = [read(v) for v in eqn.invars]
        out_dtype = getattr(getattr(eqn.outvars[0], "aval", None),
                            "dtype", None)
        outs = None
        why = ""
        iv0 = (ivs.get(eqn.invars[0])
               if not isinstance(eqn.invars[0], _core.Literal)
               else None)

        if name in _CALL_LIKE and any(s != _CONST for s in sts):
            if descend(eqn, sts):
                return
            outs = [_DEP] * len(eqn.outvars)
            why = (f"call-like `{name}` with no aligned sub-jaxpr on "
                   "a seed-dependent value")
        elif all(s == _CONST for s in sts):
            outs, why = [_CONST] * len(eqn.outvars), "seed-free"
        elif name == "convert_element_type":
            in_dt = getattr(getattr(eqn.invars[0], "aval", None),
                            "dtype", None)
            if sts[0] == _INJ and (_bits(out_dtype) >= _bits(in_dt)
                                   or _fits(iv0, out_dtype)):
                outs = [_INJ]
                why = (f"width-preserving convert "
                       f"({in_dt}->{out_dtype}): bijective mod 2^n")
                if iv0 is not None:
                    ivs[eqn.outvars[0]] = iv0
            elif sts[0] == _INJ:
                outs = [_DEP]
                why = (f"narrowing convert {in_dt}->{out_dtype} "
                       "without a domain-fit proof")
            else:
                outs, why = [sts[0]], "pass-through"
        elif name in ("add", "sub", "xor") and \
                sorted(sts) == [_CONST, _INJ]:
            outs = [_INJ]
            why = f"`{name}` with a constant: bijective mod 2^n"
            c = _lit_int(eqn.invars[1 if sts[0] == _INJ else 0])
            iv = ivs.get(eqn.invars[0 if sts[0] == _INJ else 1])
            if name == "add" and c is not None and iv is not None:
                ivs[eqn.outvars[0]] = (iv[0] + c, iv[1] + c)
        elif name == "neg" and sts[0] == _INJ:
            outs, why = [_INJ], "negation: bijective mod 2^n"
        elif name == "mul" and sorted(sts) == [_CONST, _INJ]:
            inj_ix = sts.index(_INJ)
            c = _lit_int(eqn.invars[1 - inj_ix])
            iv = ivs.get(eqn.invars[inj_ix])
            if c is not None and c % 2 == 1:
                outs = [_INJ]
                why = f"`mul` by odd constant {c}: bijective mod 2^n"
            elif c not in (None, 0) and iv is not None and _fits(
                    (iv[0] * c, iv[1] * c) if c > 0
                    else (iv[1] * c, iv[0] * c), out_dtype):
                outs = [_INJ]
                why = (f"`mul` by {c}: wrap-free on the declared "
                       f"seed domain {list(iv)} (interval argument)")
                ivs[eqn.outvars[0]] = (min(iv[0] * c, iv[1] * c),
                                       max(iv[0] * c, iv[1] * c))
            else:
                outs = [_DEP]
                why = (f"`mul` by {'unknown' if c is None else c}: "
                       "not a mod-2^n bijection and no wrap-free "
                       "interval proof")
        elif name == "shift_left" and sts[0] == _INJ:
            k = _lit_int(eqn.invars[1])
            if k is not None and iv0 is not None and _fits(
                    (iv0[0] << k, iv0[1] << k), out_dtype):
                outs = [_INJ]
                why = (f"`shift_left` by {k}: wrap-free on the "
                       f"declared seed domain {list(iv0)}")
                ivs[eqn.outvars[0]] = (iv0[0] << k, iv0[1] << k)
            else:
                outs = [_DEP]
                why = "`shift_left` drops high bits (no domain proof)"
        elif name == "random_fold_in":
            if sts[0] == _CONST and sts[1] == _INJ:
                outs = [_INJ]
                why = ("fold_in under a FIXED root key: threefry with "
                       "a constant key is a bijection of its counter "
                       "block, so distinct data -> distinct keys")
            else:
                outs = [_DEP]
                why = ("fold_in with a seed-dependent root key: a "
                       "cipher is not injective in its KEY input")
        elif name in ("random_wrap", "random_unwrap"):
            outs, why = [sts[0]], "key<->u32 repack: bijective"
        elif name == "threefry2x32":
            k_const = sts[0] == _CONST and sts[1] == _CONST
            if k_const and _INJ in (sts[2], sts[3]):
                pair_n += 1
                outs = [("pair", pair_n), ("pair", pair_n)]
                why = ("threefry under a fixed key: counter-block "
                       "bijection — outputs injective JOINTLY "
                       f"(pair #{pair_n})")
            else:
                outs = [_DEP] * len(eqn.outvars)
                why = ("threefry with a seed-dependent key operand: "
                       "not injective in the key")
        elif name == "concatenate":
            if _INJ in sts and all(s in (_CONST, _INJ) for s in sts):
                outs = [_INJ]
                why = ("concatenation containing an injective "
                       "coordinate: injective as a vector")
            else:
                outs = [_DEP]
                why = "concatenation without an injective coordinate"
        elif name in ("reshape", "broadcast_in_dim", "squeeze", "pad",
                      "copy"):
            outs, why = [sts[0]], "entry-preserving restructure"
        else:
            outs = [_DEP] * len(eqn.outvars)
            why = (f"no injectivity transfer rule for `{name}` on a "
                   "seed-dependent value")

        for v, s in zip(eqn.outvars, outs):
            status[v] = s
        note(eqn, outs, why)

    def walk(eqns):
        for eqn in eqns:
            eval_one(eqn)

    walk(raw.eqns)
    out_sts = [read(v) for v in raw.outvars]
    pairs_seen: dict = {}
    for s in out_sts:
        if isinstance(s, tuple):
            pairs_seen[s[1]] = pairs_seen.get(s[1], 0) + 1
    ok = (_INJ in out_sts) or any(n >= 2 for n in pairs_seen.values())

    findings: list[Finding] = []
    if not ok:
        reason = demoted[0] if demoted else \
            "no injective path from the seed to the key"
        findings.append(Finding(
            "SL702", ob.name, 0, 0,
            "per-world RNG key derivation is NOT provably injective "
            f"in the world seed: {reason}. Two worlds could derive "
            "the same key and replay each other's threefry counter "
            "stream; use a mod-2^n-bijective fold chain "
            "(tpu/elastic.world_key)"))
    row = {
        "obligation": ob.name,
        "ok": ok,
        "seed_domain": list(domain),
        "chain": chain,
        "claim": ("distinct seeds -> distinct derived keys -> the "
                  "per-world cipher invocation sets {(key_b, "
                  "counter)} are pairwise disjoint"),
    }
    return findings, row


def check_rng_disjoint(obligations=None
                       ) -> tuple[list[Finding], list[dict]]:
    """SL702 over every registered derivation chain."""
    findings, rows = [], []
    for ob in (obligations if obligations is not None
               else rng_obligations()):
        f, row = prove_fold_chain(ob)
        findings.extend(f)
        rows.append(row)
    return findings, rows


# --------------------------------------------------------------------------
# SL703: the vmap-traceability census + the batch-entry registry
# --------------------------------------------------------------------------


@dataclass
class BatchEntry:
    """One entry of the batch surface: ``build_w(w)`` returns the
    zero-arg (fn, args) thunk of the entry ALREADY batched over ``w``
    worlds (registry entries wrap their audit builder via
    ``jaxpr_audit.vmap_build``; prebatched obligations like the
    ensemble step supply their own world-parametrized builder)."""

    key: str
    build_w: Callable[[int], Callable]


def batch_entries() -> list[BatchEntry]:
    """The batch surface: every registered jaxpr-audit entry plus the
    ensemble consumer itself (per-world keys/shifts batched, params
    shared) — so the proofs cover both 'any entry CAN be ensembled'
    and the step ``drive_ensemble`` actually dispatches."""
    out = [
        BatchEntry(f"{e.module}:{e.name}",
                   lambda w, _b=e.build: jaxpr_audit.vmap_build(_b, w))
        for e in jaxpr_audit.default_entries()
    ]
    out.append(BatchEntry("shadow_tpu.tpu.elastic:ensemble_step[lean]",
                          jaxpr_audit.ensemble_step_build))
    return out


def _traced_w(entry: BatchEntry, w: int):
    closed, _shape_, _args = jaxpr_audit.traced(
        f"{entry.key}@vmapW{w}", entry.build_w(w))
    return closed


def _full_census(closed) -> dict[str, int]:
    from .dataflow import _iter_all_eqns

    census: dict[str, int] = {}
    for eqn in _iter_all_eqns(closed):
        census[eqn.primitive.name] = \
            census.get(eqn.primitive.name, 0) + 1
    return census


def check_vmap_census(entries=None, refusals=None
                      ) -> tuple[list[Finding], list[dict], list[dict]]:
    """SL703: every entry vmaps at both world counts with a stable
    census, or carries a written refusal (``refusals`` defaults to
    the checked-in ``VMAP_REFUSALS``; fixtures inject their own).
    Returns (findings, entry_rows, refusal_rows)."""
    entries = batch_entries() if entries is None else entries
    refused = VMAP_REFUSALS if refusals is None else refusals
    findings, rows, refusal_rows = [], [], []
    keys = {e.key for e in entries}

    for key, rationale in sorted(refused.items()):
        if key not in keys:
            findings.append(Finding(
                "SL703", key, 0, 0,
                "stale vmap refusal: no audited entry by this key — "
                "delete the refusal or fix the entry name"))
            continue
        if not rationale.strip():
            findings.append(Finding(
                "SL703", key, 0, 0,
                "vmap refusal without a written rationale: refusals "
                "are registered engineering decisions, not skips"))
        refusal_rows.append({"entry": key, "rationale": rationale})

    for entry in entries:
        if entry.key in refused:
            continue
        censuses = []
        failed = False
        for w in BATCH_WORLD_COUNTS:
            try:
                censuses.append(_full_census(_traced_w(entry, w)))
            except Exception as exc:  # noqa: BLE001 — the finding IS the report
                findings.append(Finding(
                    "SL703", entry.key, 0, 0,
                    f"entry does not vmap at W={w}: "
                    f"{type(exc).__name__}: {str(exc)[:160]} — fix "
                    "the kernel or register a VMAP_REFUSALS "
                    "rationale"))
                failed = True
                break
        if failed:
            continue
        stable = censuses[0] == censuses[1]
        if not stable:
            drift = sorted(
                k for k in set(censuses[0]) | set(censuses[1])
                if censuses[0].get(k) != censuses[1].get(k))
            findings.append(Finding(
                "SL703", entry.key, 0, 0,
                "vmapped jaxpr is not world-count-stable: primitive "
                f"census differs between W={BATCH_WORLD_COUNTS[0]} "
                f"and W={BATCH_WORLD_COUNTS[1]} on {drift} — the "
                "graph depends on the world count, so per-world "
                "behavior is not count-invariant"))
        rows.append({
            "entry": entry.key,
            "ok": stable,
            "world_counts": list(BATCH_WORLD_COUNTS),
            "ops": sum(censuses[0].values()),
        })
    return findings, rows, refusal_rows


def check_world_axis(entries=None, w: int = BATCH_WORLD_COUNTS[0]
                     ) -> tuple[list[Finding], list[dict]]:
    """SL701 over every non-refused entry's W-world batched jaxpr
    (reuses the trace cache the census pass already filled)."""
    entries = batch_entries() if entries is None else entries
    findings, rows = [], []
    for entry in entries:
        if entry.key in VMAP_REFUSALS:
            continue
        try:
            closed = _traced_w(entry, w)
        except Exception:  # noqa: BLE001  # shadowlint: disable=SL401 -- check_vmap_census reports this same trace failure as an SL703 finding; duplicating it here would double-count every broken entry
            continue
        f, row = world_axis_findings(closed, entry.key, w)
        findings.extend(f)
        rows.append(row)
    return findings, rows


# --------------------------------------------------------------------------
# the pass-5 driver
# --------------------------------------------------------------------------


def check_all_batch(selected=frozenset({"SL701", "SL702", "SL703"})
                    ) -> tuple[list[Finding], dict]:
    """Run the selected batch families over the registered surface.
    Returns (findings, batch_report) — the report is the
    ``--batch-report`` artifact and the json-v2 ``batch`` section."""
    findings: list[Finding] = []
    census_rows: list[dict] = []
    refusal_rows: list[dict] = []
    axis_rows: list[dict] = []
    rng_rows: list[dict] = []
    entries = batch_entries()

    if "SL703" in selected:
        f, census_rows, refusal_rows = check_vmap_census(entries)
        findings.extend(f)
    if "SL701" in selected:
        f, axis_rows = check_world_axis(entries)
        findings.extend(f)
    if "SL702" in selected:
        f, rng_rows = check_rng_disjoint()
        findings.extend(f)

    for f in findings:
        just = BATCH_ALLOWED.get((f.path, f.rule))
        if just:
            f.suppressed = True
            f.justification = just

    active = [f for f in findings if not f.suppressed]
    report = {
        "version": 1,
        "rules": sorted(selected & {"SL701", "SL702", "SL703"}),
        "world_counts": list(BATCH_WORLD_COUNTS),
        "caveat": (
            "SL701 proves dataflow isolation over the batched jaxpr; "
            "batched while-loop predicates (trip-count sharing with "
            "per-world select-freeze) are the vmap batching rule's "
            "contract, witnessed at runtime by the worlds-parity "
            "test. SL702's disjointness claim is on cipher "
            "invocation sets: distinct derived keys mean no two "
            "worlds ever issue the same (key, counter) threefry "
            "call."),
        "entries": axis_rows,
        "census": census_rows,
        "refusals": refusal_rows,
        "rng": rng_rows,
        "summary": {
            "entries": len(axis_rows),
            "proved": sum(1 for r in axis_rows if r["proved"]),
            "refused": len(refusal_rows),
            "rng_obligations": len(rng_rows),
            "active_findings": len(active),
            "suppressed_findings": len(findings) - len(active),
        },
    }
    return findings, report
