"""SL506: integer range / bit-budget abstract interpretation.

Every "no overflow because both < I32_MAX//2" comment in
`tpu/plane.py` / `tpu/flows.py` is a hand-reasoned interval argument.
This pass mechanizes it: a forward interval analysis over the SAME
traced jaxprs the SL2xx/SL501/SL502 passes audit, seeded from a
checked-in **input-domain registry** (`range_specs()` — window_ns <=
I32_MAX//4, wire bytes <= the 2^24 budget, deliver offsets within the
int32-ns wire budget, ...), with:

- exact transfer functions for the integer arithmetic the plane uses
  (add/sub/mul/neg/abs/div/rem/min/max/clamp/select/cumsum/
  reduce_sum/scatter-add/shifts), value-preserving joins for the
  selection ops (sort/gather/slice/concatenate/...), and descent into
  every control-flow sub-jaxpr;
- ``while``-loop carry fixpoints with **predicate refinement**: the
  loop condition's conjunctive comparisons narrow the carry intervals
  inside the body (and one step backward through add/sub producers),
  which is exactly how the `chain_windows` hand-proof works — `off`
  and `next_ev` both stay `< I32_MAX//2` BECAUSE the loop only
  continues while `next_ev < hs - off` — so that comment becomes a
  machine-checked theorem instead of prose;
- **declared-modular** leaves (`rng_counter`, metrics/histogram
  counters, the flow plane's segment indices and ms clock, RR
  virtual-finish counters): int32 counters that wrap BY CONTRACT (the
  harvester delta-unwraps them); arithmetic fed by a modular value is
  wrap-exempt and stays modular;
- an explicit per-entry ``allow`` list (substring match, justification
  mandatory) for wraps that are real but harmless by the masking
  discipline — every consumer masks the affected lanes by validity —
  mirroring the SL2xx audit allow-lists.

The build FAILS (SL506 finding) on any non-exempt signed-integer op
whose computed interval admits wraparound, naming the op, its nesting
path, and the computed interval. Everything else lands in the
``--range-report`` artifact: per-entry output-leaf interval tables,
the assumption inventory (domains, modular leaves, allows), and the
primitives the analysis did not model (conservative full-range).

Caveat recorded in the report: intervals are computed on the audit
registry's representative shapes — prefix-sum and reduction factors
scale with ring capacity, so the shape-dependent budgets (e.g.
egress_cap * max_bytes < 2^31 for the token-gate cumsum) are enforced
separately at config/compile time (workloads/spec.py, plane.make_params).
"""

from __future__ import annotations

import fnmatch
from dataclasses import dataclass, field

import numpy as np

from .rules import Finding

from jax.extend import core as _core

__all__ = [
    "IVal",
    "RangeSpec",
    "analyze_entry",
    "check_all_ranges",
    "range_specs",
]

I32_MAX = 2**31 - 1
I32_MIN = -(2**31)

#: fixpoint budget before widening a while/scan carry slot to its
#: dtype range (taint-free analogue of dataflow._fixpoint; intervals
#: can climb forever, so widening is load-bearing here)
_WIDEN_AT = 6
_MAX_ITERS = 10


# --------------------------------------------------------------------------
# the interval value
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class IVal:
    """[lo, hi] over mathematical integers, plus the wrap-exemption
    flag (modular counters wrap by contract)."""

    lo: int
    hi: int
    modular: bool = False

    def join(self, other: "IVal") -> "IVal":
        return IVal(min(self.lo, other.lo), max(self.hi, other.hi),
                    self.modular or other.modular)


_SIGNED_RANGES = {
    "int8": (-(2**7), 2**7 - 1),
    "int16": (-(2**15), 2**15 - 1),
    "int32": (I32_MIN, I32_MAX),
    "int64": (-(2**63), 2**63 - 1),
}
_UNSIGNED_RANGES = {
    "uint8": (0, 2**8 - 1),
    "uint16": (0, 2**16 - 1),
    "uint32": (0, 2**32 - 1),
    "uint64": (0, 2**64 - 1),
}


def _dtype_str(aval) -> str:
    return str(getattr(aval, "dtype", ""))


def _int_range(dt: str):
    if dt == "bool":
        return (0, 1)
    return _SIGNED_RANGES.get(dt) or _UNSIGNED_RANGES.get(dt)


def _default_ival(aval) -> IVal | None:
    """Conservative value for an unseeded/unmodeled output: the dtype
    range for integers/bools, untracked (None) for floats/keys."""
    rng = _int_range(_dtype_str(aval))
    return IVal(*rng) if rng is not None else None


def _const_ival(value) -> IVal | None:
    try:
        arr = np.asarray(value)
    except TypeError:  # extended dtypes (PRNG keys) refuse conversion
        return None
    if arr.dtype == np.bool_:
        return IVal(0, 1)
    if not np.issubdtype(arr.dtype, np.integer):
        return None
    if arr.size == 0:
        return IVal(0, 0)
    return IVal(int(arr.min()), int(arr.max()))


def _div_trunc(a: int, b: int) -> int:
    """C-style truncating division (lax.div semantics)."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


# --------------------------------------------------------------------------
# the per-entry spec (the checked-in domain registry)
# --------------------------------------------------------------------------


@dataclass
class RangeSpec:
    """One analyzed entry: the audit-registry key it traces, a name
    prefix per positional argument, and the input-domain registry.

    ``domains`` maps fnmatch patterns over input leaf paths to
    (lo, hi, justification); ``modular`` marks wrap-exempt counter
    leaves; ``allow`` suppresses named residual findings (substring
    match against the finding message) with a mandatory justification.
    Unlisted integer leaves default to their FULL dtype range — the
    conservative choice that forces every assumption to be written
    down here."""

    key: str  # "module:name" in the jaxpr-audit registry
    arg_names: list[str]
    domains: dict[str, tuple[int, int, str]] = field(default_factory=dict)
    modular: dict[str, str] = field(default_factory=dict)
    allow: dict[str, str] = field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.key.split(":", 1)[1]

    @property
    def module(self) -> str:
        return self.key.split(":", 1)[0]


# --------------------------------------------------------------------------
# transfer functions
# --------------------------------------------------------------------------

#: value-preserving ops: outputs are copies/permutations of the first
#: (data) operand's elements
_PASS_FIRST = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "slice", "squeeze",
    "rev", "expand_dims", "copy", "dynamic_slice", "reduce_max",
    "reduce_min", "cummax", "cummin", "stop_gradient", "device_put",
    "reduce_precision", "real", "copy_p",
})

#: ops whose outputs join ALL integer operands' values
_JOIN_ALL = frozenset({
    "concatenate", "pad", "dynamic_update_slice", "scatter",
    "scatter-max", "scatter-min", "clamp_deprecated",
})

#: silently-opaque primitives: known to produce untracked/full-range
#: outputs by design (no "unmodeled" note)
_KNOWN_OPAQUE = frozenset({
    "threefry2x32", "random_bits", "random_seed", "random_wrap",
    "random_fold_in",
})

_CALL_LIKE = ("jit", "closed_call", "core_call", "remat", "checkpoint",
              "custom_jvp_call", "custom_vjp_call",
              "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr")

_SUB_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _first_sub_jaxpr(params):
    for key in _SUB_JAXPR_KEYS:
        sub = params.get(key)
        if sub is not None:
            return sub
    return None


def _mul_bounds(a: IVal, b: IVal):
    cands = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
    return min(cands), max(cands)


def _axis_factor(eqn, axes) -> int:
    shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
    f = 1
    for a in axes:
        f *= int(shape[a]) if a < len(shape) else 1
    return max(f, 1)


class _Analysis:
    """One entry's walk: env of IVals, findings, report notes."""

    def __init__(self, spec: RangeSpec):
        self.spec = spec
        self.findings: list[Finding] = []
        self.unmodeled: dict[str, int] = {}
        self.opaque: list[str] = []
        self.gather_fills = 0
        self._seen: set[str] = set()
        self.quiet = 0  # >0 while iterating a fixpoint

    # -- finding emission --------------------------------------------------

    def emit(self, eqn, path: str, lo: int, hi: int, ins):
        if self.quiet:
            return
        name = eqn.primitive.name
        opnds = ", ".join(
            f"[{v.lo}, {v.hi}]" if v is not None else "?" for v in ins)
        dt = _dtype_str(eqn.outvars[0].aval)
        src = _source_line(eqn)
        msg = (f"int32 `{name}` admits wraparound at {path}"
               f"{f' ({src})' if src else ''}: computed "
               f"interval [{lo}, {hi}] exceeds {dt} (operands {opnds})"
               " — widen the guard, clamp the domain, or declare the "
               "feeding counter modular (analysis/ranges.py registry)")
        if msg in self._seen:
            return
        self._seen.add(msg)
        where = f"{self.spec.module}:{self.spec.name}"
        f = Finding("SL506", where, 0, 0, msg)
        for pat, why in self.spec.allow.items():
            if pat in msg:
                f.suppressed = True
                f.justification = why
                break
        self.findings.append(f)

    def note_unmodeled(self, name: str):
        if not self.quiet:
            self.unmodeled[name] = self.unmodeled.get(name, 0) + 1

    # -- one equation ------------------------------------------------------

    def eval_eqn(self, eqn, ins: list[IVal | None], path: str
                 ) -> list[IVal | None]:
        name = eqn.primitive.name
        params = eqn.params
        out_avals = [v.aval for v in eqn.outvars]
        n_out = len(out_avals)

        def default_outs():
            return [_default_ival(a) for a in out_avals]

        def mod_any():
            return any(v is not None and v.modular for v in ins)

        def checked(lo: int, hi: int, *, aval=None) -> IVal:
            """Signed-int arithmetic result: finding on wrap unless a
            modular operand exempts it."""
            aval = aval if aval is not None else out_avals[0]
            dt = _dtype_str(aval)
            rng = _SIGNED_RANGES.get(dt)
            if rng is None:  # unsigned/float result: untracked wrap-ok
                full = _int_range(dt)
                return IVal(*full, modular=mod_any()) if full else None
            if mod_any():
                return IVal(*rng, modular=True)
            if lo < rng[0] or hi > rng[1]:
                self.emit(eqn, path, lo, hi, ins)
                return IVal(*rng)
            return IVal(lo, hi)

        def v(i) -> IVal:
            val = ins[i]
            return val if val is not None else (
                _default_ival(eqn.invars[i].aval) or IVal(I32_MIN,
                                                          I32_MAX))

        # control flow -----------------------------------------------------
        if name in _CALL_LIKE:
            tag0 = params.get("name")
            if tag0 == "searchsorted":
                # modeled library call: insertion indices lie in
                # [0, len(sorted)] — descending into its binary-search
                # scan (uint32 midpoint tricks) would only add noise
                length = int(tuple(getattr(
                    eqn.invars[0].aval, "shape", (0,)))[-1] or 0)
                return [IVal(0, length)] * n_out
            if tag0 == "floor_divide" and len(ins) == 2:
                # modeled library call: jnp's floor-divide wraps lax.div
                # in a sign-correction select whose untaken q-1 arm
                # would otherwise join into the interval
                a, b = v(0), v(1)
                if (b.lo >= 1 or b.hi <= -1) and _int_range(
                        _dtype_str(out_avals[0])):
                    cands = [x // y for x in (a.lo, a.hi)
                             for y in (b.lo, b.hi)]
                    return [checked(min(cands), max(cands))]
            if tag0 == "clip" and len(ins) == 3 and _int_range(
                    _dtype_str(out_avals[0])):
                # modeled library call: jnp.clip traces as
                # jit(max-then-min), and like the clamp primitive it
                # pins its output into the bound operands' range for
                # ANY input — including a wrapped modular counter —
                # so the clipped value re-enters ordinary checked
                # arithmetic (this is what makes the flow plane's
                # `clip(deadline - clock, 0, budget)` wake/RTO paths
                # genuinely proven instead of modular-exempt)
                x, lo_op, hi_op = v(0), v(1), v(2)
                return [IVal(min(max(x.lo, lo_op.lo), hi_op.lo),
                             min(max(x.hi, lo_op.hi), hi_op.hi),
                             modular=lo_op.modular or hi_op.modular)]
            if tag0 in ("remainder", "mod") and len(ins) == 2:
                # floor-mod: the result's sign follows the divisor
                b = v(1)
                if b.lo >= 1 and _int_range(_dtype_str(out_avals[0])):
                    return [IVal(0, b.hi - 1, modular=mod_any())]
                if b.hi <= -1 and _int_range(_dtype_str(out_avals[0])):
                    return [IVal(b.lo + 1, 0, modular=mod_any())]
            sub = _first_sub_jaxpr(params)
            if sub is not None and len(_raw(sub).invars) == len(ins):
                tag = params.get("name") or name
                outs = self.run(sub, ins, path + f"/{tag}")
                return (outs + default_outs())[:n_out]
            self.note_unmodeled(name)
            return default_outs()
        if name == "cond":
            outs = None
            for i, branch in enumerate(params["branches"]):
                b_outs = self.run(branch, ins[1:],
                                  path + f"/cond.b{i}")
                outs = b_outs if outs is None else [
                    (a.join(b) if a is not None and b is not None
                     else None)
                    for a, b in zip(outs, b_outs)]
            return outs if outs is not None else default_outs()
        if name == "while":
            return self._while(eqn, ins, path)
        if name == "scan":
            return self._scan(eqn, ins, path)
        if name == "pallas_call":
            if not self.quiet:
                self.opaque.append(path + "/pallas_call")
            return default_outs()

        # arithmetic (checked) --------------------------------------------
        if name == "add":
            a, b = v(0), v(1)
            return [checked(a.lo + b.lo, a.hi + b.hi)]
        if name == "sub":
            a, b = v(0), v(1)
            return [checked(a.lo - b.hi, a.hi - b.lo)]
        if name == "mul":
            return [checked(*_mul_bounds(v(0), v(1)))]
        if name == "neg":
            a = v(0)
            return [checked(-a.hi, -a.lo)]
        if name == "abs":
            a = v(0)
            return [checked(max(0, a.lo, -a.hi)
                            if a.lo > 0 or a.hi < 0 else 0,
                            max(abs(a.lo), abs(a.hi)))]
        if name == "integer_pow":
            a, y = v(0), int(params.get("y", 1))
            cands = [a.lo**y, a.hi**y] + ([0] if a.lo < 0 < a.hi else [])
            return [checked(min(cands), max(cands))]
        if name == "div":
            a, b = v(0), v(1)
            if b.lo >= 1 or b.hi <= -1:
                cands = [_div_trunc(x, y) for x in (a.lo, a.hi)
                         for y in (b.lo, b.hi)]
                # only the INT_MIN / -1 corner can wrap
                return [checked(min(cands), max(cands))]
            return default_outs()
        if name == "rem":
            a, b = v(0), v(1)
            if b.lo >= 1 or b.hi <= -1:
                m = max(abs(b.lo), abs(b.hi)) - 1
                return [IVal(-m if a.lo < 0 else 0,
                             m if a.hi > 0 else 0, modular=mod_any())]
            return default_outs()
        if name == "shift_left":
            a, k = v(0), v(1)
            kh = min(max(k.hi, 0), 63)
            cands = [a.lo << kh, a.hi << kh, a.lo, a.hi]
            return [checked(min(cands), max(cands))]
        if name in ("shift_right_arithmetic", "shift_right_logical"):
            a = v(0)
            if a.lo >= 0:
                return [IVal(0, a.hi, modular=mod_any())]
            if name == "shift_right_arithmetic":
                return [IVal(min(a.lo, 0), max(a.hi, 0),
                             modular=mod_any())]
            return default_outs()
        if name == "cumsum":
            a = v(0)
            f = _axis_factor(eqn, (params.get("axis", 0),))
            return [checked(min(a.lo, f * a.lo), max(a.hi, f * a.hi))]
        if name == "cumprod":
            a = v(0)
            if 0 <= a.lo and a.hi <= 1:
                # the rcv_bits leading-run trick: products of 0/1 stay
                # 0/1 for any prefix length
                return [IVal(0, 1, modular=a.modular)]
            self.note_unmodeled(name)
            return default_outs()
        if name == "reduce_sum":
            a = v(0)
            f = _axis_factor(eqn, tuple(params.get("axes", ())))
            return [checked(f * a.lo, f * a.hi)]
        if name == "reduce_prod":
            self.note_unmodeled(name)
            return default_outs()
        if name.startswith("scatter-add") or name == "scatter_add":
            a, upd = v(0), v(2) if len(ins) > 2 else v(-1)
            n_upd = int(np.prod(
                tuple(getattr(eqn.invars[-1].aval, "shape", ())) or (1,),
                dtype=np.int64))
            return [checked(a.lo + n_upd * min(0, upd.lo),
                            a.hi + n_upd * max(0, upd.hi))]
        if name.startswith("scatter-mul"):
            self.note_unmodeled(name)
            return default_outs()

        # exact non-wrapping integer ops ----------------------------------
        if name in ("max", "min"):
            a, b = v(0), v(1)
            pick = max if name == "max" else min
            return [IVal(pick(a.lo, b.lo), pick(a.hi, b.hi),
                         modular=mod_any())]
        if name == "clamp":
            # clamp = min(max(x, lo), hi), monotone in EACH argument:
            # the result bounds use each operand's matching bound
            lo_op, x, hi_op = v(0), v(1), v(2)
            lo = min(max(x.lo, lo_op.lo), hi_op.lo)
            hi = min(max(x.hi, lo_op.hi), hi_op.hi)
            # a clamp PINS its output into [lo_op.lo, hi_op.hi] for ANY
            # input value — including a wrapped modular counter — so the
            # clamped VALUE is no longer wrap-exempt: downstream
            # arithmetic on it is ordinary bounded arithmetic and must
            # be checked (this is what makes the flow plane's
            # `clip(deadline - clock, 0, budget)` launder its modular
            # clock into a genuinely proven wake computation)
            return [IVal(lo, hi,
                         modular=lo_op.modular or hi_op.modular)]
        if name == "select_n":
            cases = [x for x in ins[1:] if x is not None]
            if len(cases) != len(ins) - 1:
                return default_outs()
            out = cases[0]
            for c in cases[1:]:
                out = out.join(c)
            return [out]
        if name == "sort":
            # per-operand permutation: output k carries operand k's
            # values
            return [ins[i] if ins[i] is not None
                    else _default_ival(out_avals[i])
                    for i in range(n_out)]
        if name == "gather":
            # OOB fills are assumed unreachable (recorded in the
            # report): the plane's gather indices are ranks/clipped
            # ids bounded by construction, and joining every
            # take_along_axis fill sentinel (-2^31) would reduce the
            # whole analysis to noise
            if params.get("fill_value") is not None and not self.quiet:
                self.gather_fills += 1
            a = v(0)
            return [IVal(a.lo, a.hi, modular=a.modular)]
        if name in _JOIN_ALL:
            vals = [x for i, x in enumerate(ins)
                    if x is not None
                    and _int_range(_dtype_str(eqn.invars[i].aval))]
            if not vals:
                return default_outs()
            out = vals[0]
            for x in vals[1:]:
                out = out.join(x)
            return [out]
        if name in _PASS_FIRST:
            if ins and ins[0] is not None:
                return [ins[0]] * n_out
            return default_outs()
        if name == "iota":
            dim = params.get("dimension", 0)
            shape = tuple(params.get("shape", ()))
            hi = int(shape[dim]) - 1 if shape else 0
            return [IVal(0, max(hi, 0))]
        if name in ("argmax", "argmin"):
            shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            return [IVal(0, max((max(shape) - 1) if shape else 0, 0))]
        if name in ("reduce_or", "reduce_and", "eq", "ne", "lt", "le",
                    "gt", "ge", "is_finite", "not", "xor_bool",
                    "le_to", "lt_to"):
            if _dtype_str(out_avals[0]) == "bool":
                return [IVal(0, 1)]
            return default_outs()
        if name in ("and", "or", "xor"):
            if _dtype_str(out_avals[0]) == "bool":
                return [IVal(0, 1)]
            a, b = v(0), v(1)
            if a.lo >= 0 and b.lo >= 0:
                if name == "and":
                    return [IVal(0, min(a.hi, b.hi),
                                 modular=mod_any())]
                hi = (1 << max(a.hi, b.hi).bit_length()) - 1
                return [IVal(0, hi, modular=mod_any())]
            return default_outs()
        if name == "sign":
            return [IVal(-1, 1)]
        if name in ("population_count", "clz"):
            return [IVal(0, 64)]
        if name == "convert_element_type":
            src_dt = _dtype_str(eqn.invars[0].aval)
            dst_dt = _dtype_str(out_avals[0])
            dst = _int_range(dst_dt)
            if dst is None:
                return [None]
            a = ins[0]
            if a is None or _int_range(src_dt) is None:
                return [IVal(*dst)]
            if dst[0] <= a.lo and a.hi <= dst[1]:
                return [IVal(a.lo, a.hi, modular=a.modular)]
            # narrowing reinterpretation: wraps by design (the packed
            # uint32 sort-key discipline) — full range, never a finding
            return [IVal(*dst, modular=a.modular)]
        if name in _KNOWN_OPAQUE:
            return default_outs()

        self.note_unmodeled(name)
        return default_outs()

    # -- jaxpr walk --------------------------------------------------------

    def run(self, jaxpr_like, in_vals, path: str):
        raw = _raw(jaxpr_like)
        consts = list(getattr(jaxpr_like, "consts", []))
        env: dict = {}

        def read(var):
            if isinstance(var, _core.Literal):
                return _const_ival(var.val)
            return env.get(var)

        for var, const in zip(raw.constvars, consts):
            env[var] = _const_ival(const)
        for var, val in zip(raw.invars, in_vals):
            env[var] = val
        for eqn in raw.eqns:
            ins = [read(v) for v in eqn.invars]
            outs = self.eval_eqn(eqn, ins, path)
            for var, out in zip(eqn.outvars, outs):
                env[var] = out
        return [read(v) for v in raw.outvars]

    # -- while / scan ------------------------------------------------------

    def _refine_by_cond(self, cond_jaxpr, cond_ins):
        """Evaluate the loop condition and narrow the carried intervals
        by its conjunctive comparisons (the predicate-refinement that
        turns `while next_ev < hs - off` into interval facts). Returns
        the refined copies of `cond_ins`."""
        raw = _raw(cond_jaxpr)
        consts = list(getattr(cond_jaxpr, "consts", []))
        env: dict = {}
        producers: dict = {}

        def read(var):
            if isinstance(var, _core.Literal):
                return _const_ival(var.val)
            return env.get(var)

        for var, const in zip(raw.constvars, consts):
            env[var] = _const_ival(const)
        for var, val in zip(raw.invars, cond_ins):
            env[var] = val
        self.quiet += 1
        try:
            for eqn in raw.eqns:
                ins = [read(v) for v in eqn.invars]
                outs = self.eval_eqn(eqn, ins, "cond")
                for var, out in zip(eqn.outvars, outs):
                    env[var] = out
                    producers[var] = eqn
        finally:
            self.quiet -= 1

        def narrow(var, lo=None, hi=None, depth=0):
            if isinstance(var, _core.Literal) or depth > 3:
                return
            cur = env.get(var)
            if cur is None:
                return
            new_lo = max(cur.lo, lo) if lo is not None else cur.lo
            new_hi = min(cur.hi, hi) if hi is not None else cur.hi
            if new_lo > new_hi or (new_lo == cur.lo
                                   and new_hi == cur.hi):
                return
            env[var] = IVal(new_lo, new_hi, cur.modular)
            eqn = producers.get(var)
            if eqn is None:
                return
            name = eqn.primitive.name
            if name == "convert_element_type":
                narrow(eqn.invars[0], lo, hi, depth + 1)
            elif name == "add" and len(eqn.invars) == 2:
                p, q = eqn.invars
                pv, qv = read(p), read(q)
                if pv is None or qv is None:
                    return
                if hi is not None:
                    narrow(p, hi=hi - qv.lo, depth=depth + 1)
                    narrow(q, hi=hi - pv.lo, depth=depth + 1)
                if lo is not None:
                    narrow(p, lo=lo - qv.hi, depth=depth + 1)
                    narrow(q, lo=lo - pv.hi, depth=depth + 1)
            elif name == "sub" and len(eqn.invars) == 2:
                p, q = eqn.invars
                pv, qv = read(p), read(q)
                if pv is None or qv is None:
                    return
                if hi is not None:
                    narrow(p, hi=hi + qv.hi, depth=depth + 1)
                    narrow(q, lo=pv.lo - hi, depth=depth + 1)
                if lo is not None:
                    narrow(p, lo=lo + qv.lo, depth=depth + 1)
                    narrow(q, hi=pv.hi - lo, depth=depth + 1)
            elif name == "min":
                if lo is not None:
                    for op in eqn.invars:
                        narrow(op, lo=lo, depth=depth + 1)
            elif name == "max":
                if hi is not None:
                    for op in eqn.invars:
                        narrow(op, hi=hi, depth=depth + 1)

        # conjuncts: walk back from the output through and/reduce_and
        stack = [raw.outvars[0]]
        seen: set = set()
        while stack:
            var = stack.pop()
            if isinstance(var, _core.Literal) or id(var) in seen:
                continue
            seen.add(id(var))
            eqn = producers.get(var)
            if eqn is None:
                continue
            name = eqn.primitive.name
            if name in ("and", "reduce_and", "convert_element_type"):
                stack.extend(eqn.invars)
            elif name in ("lt", "le", "gt", "ge"):
                x, y = eqn.invars
                if name in ("gt", "ge"):  # x > y == y < x
                    x, y = y, x
                xv, yv = read(x), read(y)
                off = 1 if name in ("lt", "gt") else 0
                if yv is not None:
                    narrow(x, hi=yv.hi - off)
                if xv is not None:
                    narrow(y, lo=xv.lo + off)
        return [read(v) for v in raw.invars]

    def _while(self, eqn, ins, path: str):
        params = eqn.params
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_c, body_c = ins[:cn], ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        body = params["body_jaxpr"]
        cond = params["cond_jaxpr"]
        # a value the predicate constrains may enter the BODY as a
        # body-const under a different position: map refined cond
        # consts back to the body consts sharing the same parent var
        cond_vars = eqn.invars[:cn]
        body_vars = eqn.invars[cn:cn + bn]

        def body_ins(refined_all, carry_ref):
            by_parent = {id(v): r for v, r in
                         zip(cond_vars, refined_all[:cn])}
            consts = [by_parent.get(id(v), orig)
                      for v, orig in zip(body_vars, body_c)]
            return consts + carry_ref

        self.quiet += 1
        try:
            for it in range(_MAX_ITERS):
                refined_all = self._refine_by_cond(
                    cond, list(cond_c) + carry)
                outs = self.run(body,
                                body_ins(refined_all,
                                         refined_all[cn:]),
                                path + "/while")
                new = []
                changed = False
                for c, o, var in zip(carry, outs,
                                     _raw(body).outvars):
                    if c is None or o is None:
                        new.append(None)
                        continue
                    j = c.join(o)
                    if j != c:
                        changed = True
                        if it >= _WIDEN_AT:
                            rng = _int_range(_dtype_str(var.aval)) \
                                or (I32_MIN, I32_MAX)
                            j = IVal(*rng, modular=j.modular)
                    new.append(j)
                carry = new
                if not changed:
                    break
        finally:
            self.quiet -= 1
        # final reporting passes with the converged carry: the body
        # refined by the predicate (its arithmetic runs only while it
        # holds) AND the condition jaxpr itself, unrefined — the
        # predicate's own arithmetic executes on every entry
        refined_all = self._refine_by_cond(cond, list(cond_c) + carry)
        self.run(body, body_ins(refined_all, refined_all[cn:]),
                 path + "/while")
        self.run(cond, list(cond_c) + carry, path + "/while.cond")
        return carry

    def _scan(self, eqn, ins, path: str):
        params = eqn.params
        nc, ncar = params["num_consts"], params["num_carry"]
        consts, carry = ins[:nc], list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        body = params["jaxpr"]

        length = params.get("length")
        if length is not None and 0 < length <= 64:
            # exact unroll: scans are bounded (searchsorted bit steps,
            # the codel micro-step trace) — iterating the body
            # `length` times keeps loop-local counters precise where a
            # widened fixpoint would flood the report
            ys = None
            for _ in range(int(length)):
                outs = self.run(body, list(consts) + carry + list(xs),
                                path + "/scan")
                carry = outs[:ncar]
                tail = outs[ncar:]
                ys = tail if ys is None else [
                    (a.join(b) if a is not None and b is not None
                     else None) for a, b in zip(ys, tail)]
            return carry + (ys if ys is not None else [])

        self.quiet += 1
        try:
            for it in range(_MAX_ITERS):
                outs = self.run(body, list(consts) + carry + list(xs),
                                path + "/scan")[:ncar]
                new = []
                changed = False
                for c, o, var in zip(carry, outs,
                                     _raw(body).outvars[:ncar]):
                    if c is None or o is None:
                        new.append(None)
                        continue
                    j = c.join(o)
                    if j != c:
                        changed = True
                        if it >= _WIDEN_AT:
                            rng = _int_range(_dtype_str(var.aval)) \
                                or (I32_MIN, I32_MAX)
                            j = IVal(*rng, modular=j.modular)
                    new.append(j)
                carry = new
                if not changed:
                    break
        finally:
            self.quiet -= 1
        outs = self.run(body, list(consts) + carry + list(xs),
                        path + "/scan")
        return carry + outs[ncar:]


def _raw(jaxpr_like):
    return getattr(jaxpr_like, "jaxpr", jaxpr_like)


def _source_line(eqn) -> str:
    """Best-effort shadow_tpu/ file:line of the offending op (jax
    records a user traceback per equation; fall back silently when the
    private helper moves)."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return ""
        fname = frame.file_name.replace("\\", "/")
        if "shadow_tpu/" in fname:
            fname = "shadow_tpu/" + fname.split("shadow_tpu/", 1)[1]
        func = getattr(frame, "function_name", "")
        # the function name is the STABLE anchor for allow patterns
        # (line numbers drift with unrelated edits)
        return f"{fname}:{frame.start_line}" + (f" in {func}()"
                                                if func else "")
    except Exception:
        return ""


# --------------------------------------------------------------------------
# entry analysis
# --------------------------------------------------------------------------


def _seed_inputs(spec: RangeSpec, args) -> tuple[list, list[str]]:
    """Per-leaf IVals for the entry arguments, from the domain
    registry. Returns (ivals, notes) — notes record which pattern
    seeded which leaf for the report."""
    from jax import tree_util

    from .dataflow import leaf_paths

    if len(spec.arg_names) != len(args):
        raise ValueError(
            f"{spec.key}: arg_names has {len(spec.arg_names)} entries "
            f"but the audit builder produced {len(args)} args")
    ivals: list = []
    notes: list[str] = []
    for name, arg in zip(spec.arg_names, args):
        paths = leaf_paths(arg, prefix=name)
        leaves = tree_util.tree_leaves(arg)
        for path, leaf in zip(paths, leaves):
            aval_dt = str(np.asarray(leaf).dtype) \
                if not hasattr(leaf, "dtype") else str(leaf.dtype)
            if _int_range(aval_dt) is None:
                ivals.append(None)
                continue
            # exact match first: dict-key paths like
            # `delivered['sock']` contain fnmatch character classes.
            # modular wins over a domain pattern covering the same
            # leaf (a bounded modular counter is still wrap-exempt)
            matched = None
            for pat, _why in spec.modular.items():
                if path == pat or fnmatch.fnmatch(path, pat):
                    matched = IVal(*_int_range(aval_dt), modular=True)
                    notes.append(f"{path}: modular ({pat})")
                    break
            if matched is None:
                for pat, (lo, hi, _why) in spec.domains.items():
                    if path == pat or fnmatch.fnmatch(path, pat):
                        matched = IVal(lo, hi)
                        notes.append(
                            f"{path}: [{lo}, {hi}] (domain {pat})")
                        break
            if matched is None:
                matched = IVal(*_int_range(aval_dt))
                notes.append(f"{path}: full {aval_dt} (unseeded)")
            ivals.append(matched)
    return ivals, notes


def analyze_entry(spec: RangeSpec, *, trace=None, args=None,
                  out_shape=None) -> tuple[list[Finding], dict]:
    """Run one entry's interval analysis. Returns (findings, report
    section). `trace`/`args` short-circuit the build (the shared
    proof-pass trace cache)."""
    if trace is None or args is None:
        from .jaxpr_audit import default_entries, traced

        entry = next(e for e in default_entries()
                     if f"{e.module}:{e.name}" == spec.key)
        trace, out_shape, args = traced(spec.key, entry.build)

    in_vals, notes = _seed_inputs(spec, args)
    raw = _raw(trace)
    if len(in_vals) != len(raw.invars):
        raise AssertionError(
            f"{spec.key}: {len(in_vals)} seeded leaves vs "
            f"{len(raw.invars)} jaxpr inputs")
    ana = _Analysis(spec)
    outs = ana.run(trace, in_vals, spec.name)

    out_paths = None
    if out_shape is not None:
        from .dataflow import leaf_paths

        out_paths = leaf_paths(out_shape)
    table = {}
    for i, val in enumerate(outs):
        key = out_paths[i] if out_paths and i < len(out_paths) \
            else f"out[{i}]"
        table[key] = (None if val is None else
                      [val.lo, val.hi] + (["modular"] if val.modular
                                          else []))
    report = {
        "entry": spec.key,
        "outputs": table,
        "seeds": notes,
        "assumptions": {pat: why for pat, (_l, _h, why)
                        in spec.domains.items()},
        "modular": dict(spec.modular),
        "allow": dict(spec.allow),
        "unmodeled": dict(sorted(ana.unmodeled.items())),
        "gather_fills_assumed_unreachable": ana.gather_fills,
        "opaque": ana.opaque,
        "findings": [f.message for f in ana.findings
                     if not f.suppressed],
        "suppressed": [f.message for f in ana.findings if f.suppressed],
    }
    return ana.findings, report


def check_all_ranges(specs=None) -> tuple[list[Finding], dict]:
    findings: list[Finding] = []
    sections = []
    for spec in (specs if specs is not None else range_specs()):
        f, report = analyze_entry(spec)
        findings.extend(f)
        sections.append(report)
    report = {
        "version": 1,
        "rule": "SL506",
        "caveat": ("intervals computed on the audit registry's "
                   "representative shapes; shape-scaled budgets "
                   "(capacity x max-bytes prefix sums) are enforced "
                   "at config/compile time"),
        "entries": sections,
        "summary": {
            "entries": len(sections),
            "active_findings": sum(1 for f in findings
                                   if not f.suppressed),
            "suppressed_findings": sum(1 for f in findings
                                       if f.suppressed),
        },
    }
    return findings, report


# --------------------------------------------------------------------------
# the checked-in domain registry
# --------------------------------------------------------------------------

_B = I32_MAX  # shorthand

#: the wire-size budget: spec.py caps message/pattern byte knobs so
#: capacity-scaled prefix sums (token gate cumsum, byte counters) stay
#: inside int32 at every supported ring size
BYTES_BUDGET = 1 << 24

_WHY_TSEND = ("rebased send times: a queued packet's tsend drops by "
              "one window per round; the half-budget floor holds while "
              "a packet waits < ~1 s virtual for bandwidth (token rate "
              ">= 1 B/ms; recorded as an open assumption — the rebase "
              "is not inductively closed by intervals alone)")
_WHY_DELIVER = ("deliver offsets: max(tsend + latency, clamp) with "
                "tsend <= window <= I32_MAX//4 and latency <= "
                "I32_MAX//2 (make_params budget); I32_MAX is the idle "
                "sentinel; the lower edge is one window of rebase")
_WHY_WINDOW = ("window_ns <= I32_MAX//4: enforced at scenario parse "
               "(workloads/spec.py) and the config runahead budget")
_WHY_SHIFT = ("shift_ns < I32_MAX//2: the chain driver opens windows "
              "at next_event, which its own loop bounds below the "
              "horizon clamp (the chain_windows while-theorem)")
_WHY_COUNTER = ("modular device counter: wraps by contract, the "
                "harvester delta-unwraps (docs/observability.md)")
_WHY_FLOWSEQ = ("flow segment indices / ms clock are declared modular "
                "(ISSUE scope): cumulative stream offsets wrap like "
                "every device counter; comparisons are range-relative")
_WHY_RR = ("RR virtual-finish counters are floor-rebased each window "
           "(within ~CE of zero) but join the I32_MAX idle sentinel "
           "through masked lanes; rank arithmetic on them is "
           "order-relative, the packed key masks invalid lanes")

#: NetPlaneState domains shared by every window_step-family entry
_STATE_DOMAINS = {
    "state.eg_dst": (-1, 1 << 20, "host ids; spec caps hosts <= 2^20"),
    "state.in_src": (-1, 1 << 20, "host ids; spec caps hosts <= 2^20"),
    "state.eg_bytes": (0, BYTES_BUDGET,
                       "wire bytes <= 2^24 (spec byte budget)"),
    "state.in_bytes": (0, BYTES_BUDGET,
                       "wire bytes <= 2^24 (spec byte budget)"),
    "state.eg_prio": (0, _B, "priorities: monotone counters from 0, "
                             "I32_MAX idle sentinel"),
    "state.eg_tsend": (-(_B // 2), _B // 4, _WHY_TSEND),
    "state.eg_clamp": (-(2**30), _B // 2,
                       "NO_CLAMP sentinel (-2^30) or a window-relative "
                       "barrier within the shift budget"),
    "state.in_deliver_rel": (-(_B // 4), _B, _WHY_DELIVER),
    "state.in_sock": (0, _B, "payload tags are non-negative"),
    "state.eg_sock": (0, _B, "payload tags are non-negative"),
    "state.tb_balance": (0, 2**30, "token balance <= cap <= 2^30 "
                                   "(make_params rate clamp)"),
    "state.tb_rem_ns": (0, 999_999, "sub-millisecond remainder"),
    # the destination-side router scalars (codel.RouterDownState)
    "state.router.mode": (0, 1, "store/drop enum"),
    "state.router.interval_end": (
        -(_B // 2), _B // 2,
        "CoDel timers sit within one control interval of the window "
        "horizon; rebased down every window"),
    "state.router.drop_next": (
        -(_B // 2), _B // 2,
        "CoDel timers sit within one control interval of the window "
        "horizon; rebased down every window"),
    "state.router.resume": (
        -(_B // 2), _B - 1_000_000,
        "relay resume time; wait_until saturates at I32_MAX - "
        "interval_ms and the conformance re-check recomputes "
        "(codel.py)"),
    "state.router.dn_balance": (0, 2**30,
                                "down-bucket balance <= cap <= 2^30 "
                                "(make_params rate clamp)"),
    "state.router.dn_last_refill": (
        -1_000_000, _B // 4,
        "re-anchored into (-1ms, 0] at every rebase "
        "(codel.rebase_router_state), advanced at most to the window "
        "horizon by in-window refills"),
    "state.router.cached_src": (-1, 1 << 20, "host ids"),
    "state.router.cached_sock": (0, _B, "payload tags"),
    "state.router.cached_bytes": (0, BYTES_BUDGET,
                                  "wire bytes budget"),
}
_STATE_MODULAR = {
    "state.n_*": _WHY_COUNTER,
    "state.rng_counter": _WHY_COUNTER,
    "state.rr_sent": _WHY_RR,
    "state.eg_seq": "per-source packet ids grow without bound: "
                    "modular like every counter",
    "state.in_seq": "per-source packet ids grow without bound: "
                    "modular like every counter",
    "state.router.cur_count": "CoDel drop counts: monotone counters, "
                              "modular like every device counter",
    "state.router.prev_count": "CoDel drop counts: monotone counters, "
                               "modular like every device counter",
    "state.router.dropped": _WHY_COUNTER,
    "state.router.cached_seq": "per-source packet ids: modular",
}

_DELIVERED_DOMAINS = {
    "delivered['bytes']": (0, BYTES_BUDGET, "wire bytes budget"),
    "delivered['deliver_rel']": (-(_B // 4), _B, _WHY_DELIVER),
    "delivered['src']": (-1, 1 << 20, "host ids"),
    "delivered['sock']": (0, _B, "payload tags"),
}
_DELIVERED_MODULAR = {"delivered['seq']": _WHY_FLOWSEQ}

#: the flow plane's wrap-exempt leaves are ENUMERATED, not fs.*: only
#: the segment-index stream offsets, the ms clock, and the cumulative
#: counters wrap by contract — cwnd/Reno/RTT-estimator/timer
#: arithmetic below gets real checked domains (a blanket fs.* made
#: the flow half of the proof vacuous)
_FS_MODULAR = {
    "fs.snd_una": _WHY_FLOWSEQ, "fs.snd_nxt": _WHY_FLOWSEQ,
    "fs.snd_max": _WHY_FLOWSEQ, "fs.stream_len": _WHY_FLOWSEQ,
    "fs.rcv_nxt": _WHY_FLOWSEQ, "fs.rtt_seq": _WHY_FLOWSEQ,
    "fs.clock_ms": _WHY_FLOWSEQ,
    "fs.retransmit_count": _WHY_COUNTER,
    "fs.retransmitted_bytes": _WHY_COUNTER,
    "fs.rto_fired": _WHY_COUNTER,
    "fs.rto_gen": _WHY_COUNTER,
    "fs.backoff_count": "monotone backoff tally: consumed only by "
                        "==0 / >0 compares (Karn gating); the RTO "
                        "value itself saturates at the _set_rto clamp",
}
_FS_DOMAINS = {
    "fs.cwnd": (0, 1 << 24,
                "congestion window in segments: additive growth "
                "clamped by ssthresh/recv_wnd; 2^24 segments is far "
                "past any modeled bandwidth-delay product"),
    "fs.ssthresh": (0, 1 << 30,
                    "slow-start threshold: halved cwnd or the "
                    "SSTHRESH_INF sentinel (tcp/cong.py)"),
    "fs.dup_acks": (0, 1 << 16, "dup-ack run length"),
    "fs.avoid_acked": (0, 1 << 24, "congestion-avoidance ack tally, "
                                   "reset each cwnd advance"),
    "fs.srtt_ms": (0, 1 << 22,
                   "RFC 6298 estimator in ms: samples are window-"
                   "quantized RTTs bounded by the RTO_MAX clamp"),
    "fs.rttvar_ms": (0, 1 << 22, "estimator variance, same budget"),
    "fs.rto_ms": (0, 1 << 22,
                  "_set_rto clips into [RTO_MIN_MS, RTO_MAX_MS] "
                  "(tpu/tcp.py); 2^22 ms leaves backoff headroom"),
    "fs.rto_deadline_ms": (-(_B // 2), _B,
                           "absolute virtual ms against the modular "
                           "clock; consumed only via clamped "
                           "differences (next_deadline_rel_ns)"),
    "fs.rtt_sent_ms": (-(_B // 2), _B,
                       "probe timestamp against the modular clock"),
    "fs.clock_rem_ns": (0, 999_999, "sub-millisecond remainder"),
}
_PLANES_MODULAR = {
    "metrics.*": _WHY_COUNTER,
    "guards.*": "guard tallies/bitmasks: saturating accumulators, "
                "modular by the same harvest contract",
    "hist.*": _WHY_COUNTER,
    "flightrec.*": "trace-ring cursor/buckets: modular, overwrites "
                   "counted at drain",
}

_SCALARS = {
    "shift_ns": (0, _B // 2, _WHY_SHIFT),
    "window_ns": (0, _B // 4, _WHY_WINDOW),
    "horizon_rel": (0, _B // 2,
                    "pre-clamped to <= I32_MAX//2 by the caller "
                    "(chain_windows docstring contract)"),
}


#: the Reno congestion-avoidance tick (tcp._avoid_tick:
#: `while acked >= cwnd: acked -= cwnd; cwnd += 1`) bounds cwnd
#: RELATIONALLY — it grows one segment per cwnd-worth of acks, so it
#: stays within one segment of the ack tally's 2^24 budget — which an
#: interval fixpoint cannot represent; the loop is the flow plane's
#: one justified residual
_AVOID_TICK_ALLOW = {
    "/while (shadow_tpu/tpu/tcp.py": (
        "Reno avoid-tick: the loop guard (acked >= cwnd) keeps cwnd "
        "within one segment of the ack tally (<= the fs.cwnd 2^24 "
        "budget); the bound is relational, beyond the interval "
        "fixpoint (tcp._avoid_tick)"),
}

#: codel's resume-time machinery is deliberately wrap-TOLERANT: a
#: saturating wait_until detects its own overflow (`r < now`) and the
#: conformance re-check recomputes a too-early firing; the refill span
#: against a saturated anchor is clamped by `max(. , 0)` + the cap min,
#: so the wrapped intermediate never commits (codel.py docstrings)
_CODEL_SATURATION_ALLOW = {
    "in wait_until()": (
        "deliberate saturation: wait_until detects its own int32 "
        "overflow (r < now) and clamps to I32_MAX - interval_ms; the "
        "resume conformance re-check recomputes early firings"),
    "in refill()": (
        "span against a saturated resume anchor: max(., 0) clamps the "
        "span and the cap min bounds the refill, so a wrapped "
        "intermediate never commits (codel.py refill/wait_until)"),
}


def _window_spec(key: str, extra_args=(), extra_domains=None,
                 extra_modular=None, allow=None) -> RangeSpec:
    domains = dict(_STATE_DOMAINS)
    domains.update({"shift_ns": _SCALARS["shift_ns"],
                    "window_ns": _SCALARS["window_ns"]})
    domains.update(extra_domains or {})
    modular = dict(_STATE_MODULAR)
    modular.update(extra_modular or {})
    return RangeSpec(
        key=key,
        arg_names=["state", *extra_args, "shift_ns", "window_ns"],
        domains=domains, modular=modular, allow=dict(allow or {}))


def range_specs() -> list[RangeSpec]:
    """The SL506 surface: the plane.py / flows.py kernel family whose
    overflow comments this pass turns into theorems (the audit
    registry's representative traces, via the shared cache)."""
    return [
        _window_spec("shadow_tpu.tpu.plane:window_step[lean]"),
        _window_spec("shadow_tpu.tpu.plane:window_step[rr,aqm,loss]",
                     allow=_CODEL_SATURATION_ALLOW),
        _window_spec("shadow_tpu.tpu.plane:window_step[flows]",
                     extra_args=["fs"], extra_domains=_FS_DOMAINS,
                     extra_modular=_FS_MODULAR,
                     allow=_AVOID_TICK_ALLOW),
        RangeSpec(
            key="shadow_tpu.tpu.plane:ingest_rows[planes]",
            arg_names=["state", "metrics", "guards", "hist",
                       "flightrec", "dst", "nbytes", "prio", "seq",
                       "valid"],
            domains={
                **_STATE_DOMAINS,
                "dst": (-1, 1 << 20, "host ids"),
                "nbytes": (0, BYTES_BUDGET, "wire bytes budget"),
                "prio": (0, _B, "priorities"),
            },
            modular={**_STATE_MODULAR, **_PLANES_MODULAR,
                     "seq": _WHY_FLOWSEQ}),
        RangeSpec(
            key="shadow_tpu.tpu.flows:flow_step",
            arg_names=["ft", "fs", "state", "delivered"],
            domains={
                **_STATE_DOMAINS, **_DELIVERED_DOMAINS, **_FS_DOMAINS,
                "ft.src": (-1, 1 << 20, "host ids"),
                "ft.dst": (-1, 1 << 20, "host ids"),
                "ft.pkt_bytes": (0, BYTES_BUDGET, "wire bytes budget"),
            },
            modular={**_STATE_MODULAR, **_DELIVERED_MODULAR,
                     **_FS_MODULAR},
            allow=dict(_AVOID_TICK_ALLOW)),
        RangeSpec(
            key="shadow_tpu.tpu.plane:chain_windows",
            arg_names=["state", "shift0", "horizon_rel"],
            domains={
                **_STATE_DOMAINS,
                "shift0": _SCALARS["shift_ns"],
                "horizon_rel": _SCALARS["horizon_rel"],
            },
            modular=dict(_STATE_MODULAR)),
        # the flows-threaded chain: the RTO-wake re-arm (plane.py
        # `wake = window_ns + min(rto_rel, I32_MAX//2)`) rides the
        # while carry — the arithmetic the plane.py:616 comment used
        # to hand-argue
        RangeSpec(
            key="shadow_tpu.tpu.plane:chain_windows[flows]",
            arg_names=["state", "fs", "shift0", "horizon_rel"],
            domains={
                **_STATE_DOMAINS, **_FS_DOMAINS,
                "shift0": _SCALARS["shift_ns"],
                "horizon_rel": _SCALARS["horizon_rel"],
            },
            modular={**_STATE_MODULAR, **_FS_MODULAR},
            allow=dict(_AVOID_TICK_ALLOW)),
    ]
