"""Interval abstract interpretation over jaxprs (kernel overflow prover).

The quantized-domain Pallas GEMM relies on one numerical invariant (paper
Sec. V-B, ``kernels/mls_matmul.py`` module doc): every *integer-valued*
accumulation — decoded code fractions, their products, and the intra-group
MAC — must stay below ``2^24`` in magnitude so fp32 arithmetic on it is
bit-exact integer arithmetic.  ``analysis/lint.py`` proves this with a
closed-form bound for the one shipped tiling; this module proves it for
**arbitrary kernel code** by abstract interpretation of the traced kernel
jaxpr in a reduced product of two domains:

* **Intervals** — every array is abstracted to one :class:`Interval`, a
  ``[lo, hi]`` range valid for all its elements plus an ``integer`` flag
  (every concretization is integer-valued: the property fp32-exactness
  cares about).  Positions are ignored, so any elementwise/shuffle op is
  sound.
* **Seed images** — an array produced by an elementwise chain from a single
  small-range integer source (e.g. the uint8 code operand of the decode)
  additionally carries the exact *image* of that source's values through
  the chain, evaluated concretely with numpy.  This keeps the correlation
  between a code's exponent and mantissa fields that plain intervals lose
  (a ``where(is_denorm, ...)`` join would over-bound the decoded fraction
  by 2x), so the decoded-fraction bound — and hence the accumulator-width
  proof — is exact and agrees bit-for-bit with the closed form of
  :func:`repro.core.formats.accumulation_bits`.

Transfer functions cover the primitive vocabulary of the shipped kernels
(bit ops, shifts, select/where, dot_general, reductions, state
``get``/``swap``/``addupdate``, ``cond`` with concrete or unknown
predicate, ``jit`` recursion); unknown primitives degrade soundly to
``Interval.top()``.  ``dot_general`` and integer add/accumulate ops record
:class:`Accumulation` events; the prover in
:mod:`repro.analysis.kernel_verify` checks each against the ``2^24``
budget using the same ``ceil(log2(hi + 1))`` bit convention as
``accumulation_bits`` so the two provers flag identical configs.

Everything here is pure Python/numpy over jaxpr metadata — nothing is
executed or compiled, so it is safe in CI on any host.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any

import numpy as np
from jax.extend import core as jcore

__all__ = [
    "AbsVal",
    "Accumulation",
    "Interval",
    "InterpResult",
    "abstract_eval_jaxpr",
    "integer_bits",
]

_INF = float("inf")
_MAX_SEED_VALUES = 4096  # largest integer source range tracked exactly


def integer_bits(hi: float) -> int:
    """Unsigned integer bits needed for magnitudes up to ``hi`` —
    ``ceil(log2(hi + 1))``, the ``product_bits + ceil(log2(k_block))``
    convention of :func:`repro.core.formats.accumulation_bits`, so both
    provers flag exactly the same configurations."""
    if hi == _INF:
        return 1 << 30
    return max(int(math.ceil(hi)), 0).bit_length()


@dataclasses.dataclass(frozen=True)
class Interval:
    """Value range of every element of an array, with integerness."""

    lo: float
    hi: float
    integer: bool = False

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    # ---- constructors ----------------------------------------------------
    @staticmethod
    def top() -> Interval:
        return Interval(-_INF, _INF, False)

    @staticmethod
    def const(v: float) -> Interval:
        v = float(v)
        return Interval(v, v, v.is_integer())

    @staticmethod
    def of_dtype(dtype) -> Interval:
        """Widest sound seed for an input of the given dtype."""
        dt = np.dtype(dtype)
        if dt.kind in "ui":
            info = np.iinfo(dt)
            return Interval(float(info.min), float(info.max), True)
        if dt.kind == "b":
            return Interval(0.0, 1.0, True)
        return Interval.top()

    # ---- lattice ---------------------------------------------------------
    def join(self, other: Interval) -> Interval:
        return Interval(
            min(self.lo, other.lo), max(self.hi, other.hi),
            self.integer and other.integer,
        )

    @property
    def max_abs(self) -> float:
        return max(abs(self.lo), abs(self.hi))

    @property
    def bounded(self) -> bool:
        return self.lo > -_INF and self.hi < _INF

    @property
    def concrete(self) -> float | None:
        """The single value when the interval is a point, else None."""
        return self.lo if self.lo == self.hi else None

    # ---- arithmetic ------------------------------------------------------
    def __add__(self, o: Interval) -> Interval:
        return Interval(self.lo + o.lo, self.hi + o.hi,
                        self.integer and o.integer)

    def __sub__(self, o: Interval) -> Interval:
        return Interval(self.lo - o.hi, self.hi - o.lo,
                        self.integer and o.integer)

    def __neg__(self) -> Interval:
        return Interval(-self.hi, -self.lo, self.integer)

    def __mul__(self, o: Interval) -> Interval:
        cands = [_mul(a, b) for a in (self.lo, self.hi)
                 for b in (o.lo, o.hi)]
        return Interval(min(cands), max(cands), self.integer and o.integer)

    def scale(self, k: float) -> Interval:
        """Multiply by a non-negative scalar (contraction-depth sums)."""
        assert k >= 0
        return Interval(_mul(self.lo, k), _mul(self.hi, k),
                        self.integer and float(k).is_integer())

    def abs(self) -> Interval:
        if self.lo >= 0:
            return self
        if self.hi <= 0:
            return -self
        return Interval(0.0, self.max_abs, self.integer)

    def truediv(self, o: Interval) -> Interval:
        if o.lo > 0 or o.hi < 0:
            cands = [a / b for a in (self.lo, self.hi)
                     for b in (o.lo, o.hi)]
            return Interval(min(cands), max(cands), False)
        return Interval.top()  # divisor range spans 0

    def min_(self, o: Interval) -> Interval:
        return Interval(min(self.lo, o.lo), min(self.hi, o.hi),
                        self.integer and o.integer)

    def max_(self, o: Interval) -> Interval:
        return Interval(max(self.lo, o.lo), max(self.hi, o.hi),
                        self.integer and o.integer)

    def floor(self) -> Interval:
        return Interval(math.floor(self.lo) if self.lo > -_INF else -_INF,
                        math.floor(self.hi) if self.hi < _INF else _INF,
                        True)

    def ceil(self) -> Interval:
        return Interval(math.ceil(self.lo) if self.lo > -_INF else -_INF,
                        math.ceil(self.hi) if self.hi < _INF else _INF,
                        True)

    def round(self) -> Interval:
        return Interval(round(self.lo) if self.lo > -_INF else -_INF,
                        round(self.hi) if self.hi < _INF else _INF,
                        True)

    def exp2(self) -> Interval:
        lo = 2.0 ** self.lo if self.lo > -_INF else 0.0
        hi = 2.0 ** self.hi if self.hi < _INF else _INF
        return Interval(lo, hi, False)

    def to_int(self) -> Interval:
        """convert_element_type to an integer dtype (truncation lies in the
        floor/ceil envelope of the source range)."""
        if not self.bounded:
            return Interval(-_INF, _INF, True)
        return Interval(float(math.floor(self.lo)),
                        float(math.ceil(self.hi)), True)

    # ---- bit ops ---------------------------------------------------------
    def bit_and(self, o: Interval) -> Interval:
        # x & m with m >= 0 lands in [0, m] regardless of x's sign (two's
        # complement); used by the decode field masks.
        for mask, _other in ((o, self), (self, o)):
            if mask.lo >= 0 and mask.bounded:
                return Interval(0.0, mask.hi, True)
        return Interval(-_INF, _INF, True)

    def bit_or(self, o: Interval) -> Interval:
        if self.lo >= 0 and o.lo >= 0 and self.bounded and o.bounded:
            bits = max(integer_bits(self.hi), integer_bits(o.hi))
            # OR only sets bits: result >= each operand, < 2^bits
            return Interval(max(self.lo, o.lo), float(2**bits - 1), True)
        return Interval(-_INF, _INF, True)

    def bit_xor(self, o: Interval) -> Interval:
        if self.lo >= 0 and o.lo >= 0 and self.bounded and o.bounded:
            # XOR can clear any bit (x ^ x = 0), so unlike OR the lower
            # bound is 0, never max(lo_a, lo_b).
            bits = max(integer_bits(self.hi), integer_bits(o.hi))
            return Interval(0.0, float(2**bits - 1), True)
        return Interval(-_INF, _INF, True)

    def shift_left(self, o: Interval) -> Interval:
        if o.lo >= 0 and o.bounded and self.bounded:
            f = 2.0 ** int(o.hi)
            lo = min(self.lo, self.lo * f)
            hi = max(self.hi, self.hi * f)
            return Interval(lo, hi, self.integer)
        return Interval(-_INF, _INF, self.integer)

    def shift_right(self, o: Interval) -> Interval:
        if o.lo >= 0 and o.bounded and self.bounded and self.lo >= 0:
            return Interval(math.floor(self.lo / 2.0 ** int(o.hi)),
                            self.hi, True)
        return Interval(-_INF, _INF, True)

    def to_json(self) -> dict:
        def num(v):
            return v if abs(v) != _INF else ("inf" if v > 0 else "-inf")

        return {"lo": num(self.lo), "hi": num(self.hi),
                "integer": self.integer}

    def __str__(self) -> str:
        tag = "int" if self.integer else "f32"
        return f"[{self.lo:g}, {self.hi:g}]{tag}"


def _mul(a: float, b: float) -> float:
    """IEEE-safe product for interval endpoints (0 * inf -> 0)."""
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


_BOOL = Interval(0.0, 1.0, True)
_seed_counter = itertools.count()


@dataclasses.dataclass(frozen=True)
class AbsVal:
    """Abstract array value: interval hull + optional exact seed image.

    ``vals`` (when present) is the concrete image of one small-range
    integer source through the elementwise chain that produced this array;
    ``src`` identifies the source so two images are only combined when they
    describe the same seed.  The interval is always the hull of ``vals``
    when ``vals`` exists.
    """

    iv: Interval
    src: int | None = None
    vals: np.ndarray | None = None

    @staticmethod
    def of(iv: Interval) -> AbsVal:
        return AbsVal(iv)

    @staticmethod
    def const(v: float) -> AbsVal:
        return AbsVal(Interval.const(v))

    @staticmethod
    def seeded(iv: Interval) -> AbsVal:
        """Seed a new exact image when the interval is a small integer
        range (e.g. a uint8 code operand)."""
        if (iv.integer and iv.bounded
                and iv.hi - iv.lo + 1 <= _MAX_SEED_VALUES):
            vals = np.arange(int(iv.lo), int(iv.hi) + 1, dtype=np.float64)
            return AbsVal(iv, next(_seed_counter), vals)
        return AbsVal(iv)

    def join(self, o: AbsVal) -> AbsVal:
        if (self.src is not None and self.src == o.src
                and self.vals is not None and o.vals is not None):
            # per-seed-value join: either image may occur for that value
            lo = np.minimum(self.vals, o.vals)
            hi = np.maximum(self.vals, o.vals)
            if np.array_equal(lo, hi):
                return AbsVal(self.iv.join(o.iv), self.src, lo)
        return AbsVal(self.iv.join(o.iv))


def _hull(vals: np.ndarray) -> Interval:
    lo, hi = float(np.min(vals)), float(np.max(vals))
    integer = bool(np.all(vals == np.floor(vals)))
    return Interval(lo, hi, integer)


# numpy realizations of elementwise primitives for the seed-image domain
def _np_shift_left(a, b):
    # Scale in float64: multiplying by a power of two is exact until it
    # overflows to inf, where the isfinite bail-out reverts to intervals.
    # An int64 `<<` would instead wrap silently once integer_bits(a) + b
    # reaches 64, corrupting the "exact" image with finite garbage.
    with np.errstate(over="ignore", invalid="ignore"):
        res = a.astype(np.float64) * np.exp2(b.astype(np.float64))
        return np.where(a == 0, 0.0, res)


def _np_shift_right(a, b):
    return (a.astype(np.int64) >> b.astype(np.int64)).astype(np.float64)


_NP_UNARY = {
    "neg": np.negative,
    "abs": np.abs,
    "sign": np.sign,
    "floor": np.floor,
    "ceil": np.ceil,
    "round": np.round,
    "nearbyint": np.round,
    "exp2": np.exp2,
    "not": lambda a: 1.0 - a,
}
_NP_BINARY = {
    "add": np.add,
    "sub": np.subtract,
    "mul": np.multiply,
    "max": np.maximum,
    "min": np.minimum,
    "and": lambda a, b: (a.astype(np.int64) & b.astype(np.int64)).astype(
        np.float64),
    "or": lambda a, b: (a.astype(np.int64) | b.astype(np.int64)).astype(
        np.float64),
    "xor": lambda a, b: (a.astype(np.int64) ^ b.astype(np.int64)).astype(
        np.float64),
    "shift_left": _np_shift_left,
    "shift_right_arithmetic": _np_shift_right,
    "shift_right_logical": _np_shift_right,
    "eq": lambda a, b: (a == b).astype(np.float64),
    "ne": lambda a, b: (a != b).astype(np.float64),
    "lt": lambda a, b: (a < b).astype(np.float64),
    "le": lambda a, b: (a <= b).astype(np.float64),
    "gt": lambda a, b: (a > b).astype(np.float64),
    "ge": lambda a, b: (a >= b).astype(np.float64),
}
# Value- and order-preserving layout ops: flat element order is unchanged,
# so the image passes through with its seed identity intact and stays
# pointwise-aligned with other images of the same seed.
_EXACT_LAYOUT_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "copy", "stop_gradient",
    "reduce_precision",
})
# Value-preserving but element-rearranging/selecting/duplicating ops: the
# output's values are still a subset of the input's, so the image remains a
# sound per-element over-approximation — but positional correspondence with
# the seed is broken (x[0:4] and x[4:8] carry the same image yet pair
# *different* seed elements), so the image survives only under a FRESH seed
# identity; binary ops between two rearrangements of one source then fall
# back to sound interval rules instead of pointwise alignment.
_REARRANGE_PRIMS = frozenset({
    "broadcast_in_dim", "transpose", "slice", "dynamic_slice", "rev",
    "gather", "reduce_max", "reduce_min",
})
_LAYOUT_PRIMS = _EXACT_LAYOUT_PRIMS | _REARRANGE_PRIMS


@dataclasses.dataclass
class Accumulation:
    """One accumulation event the overflow prover must budget.

    ``kind``: ``"dot"`` (an MXU contraction summing ``depth`` products per
    output element) or ``"acc"`` (a running add / reduce).  ``bound`` is
    the statically proven max |result|.  Only *integer* accumulations carry
    the fp32-exactness obligation; float ones are recorded with
    ``integer=False`` for visibility but not gated.
    """

    kind: str
    bound: float
    integer: bool
    depth: int
    operand_bound: float

    @property
    def bits(self) -> int:
        return integer_bits(self.bound)

    def to_json(self) -> dict:
        def num(v):
            return v if v != _INF else "inf"

        return {"kind": self.kind, "bound": num(self.bound),
                "bits": min(self.bits, 9999), "integer": self.integer,
                "depth": self.depth, "operand_bound": num(self.operand_bound)}


@dataclasses.dataclass
class InterpResult:
    """Outcome of one abstract pass over a jaxpr."""

    accumulations: list[Accumulation]
    warnings: list[str]

    def max_integer_accumulation(self) -> Accumulation | None:
        ints = [a for a in self.accumulations if a.integer]
        return max(ints, key=lambda a: a.bound) if ints else None


class _Env:
    """Var -> AbsVal environment with literal handling."""

    def __init__(self):
        self._m: dict[Any, AbsVal] = {}

    def read(self, atom) -> AbsVal:
        if isinstance(atom, jcore.Literal):
            try:
                return AbsVal.const(float(atom.val))
            except (TypeError, ValueError):
                return AbsVal.of(Interval.top())
        return self._m.get(atom, AbsVal.of(Interval.top()))

    def write(self, var, v: AbsVal) -> None:
        self._m[var] = v


def _dot_depth(eqn) -> int:
    (lhs_c, _), _batch = eqn.params["dimension_numbers"]
    shape = tuple(eqn.invars[0].aval.shape)
    return math.prod(int(shape[d]) for d in lhs_c) or 1


def _reduce_depth(eqn) -> int:
    axes = eqn.params.get("axes", ())
    shape = tuple(eqn.invars[0].aval.shape)
    return math.prod(int(shape[a]) for a in axes) or 1


def _aligned_images(ins: list[AbsVal]) -> tuple[int, list[np.ndarray]] | None:
    """Images of all operands over one shared seed, lifting constants."""
    src, length = None, None
    for v in ins:
        if v.vals is not None:
            if src is None:
                src, length = v.src, len(v.vals)
            elif v.src != src:
                return None
    if src is None:
        return None
    out = []
    for v in ins:
        if v.vals is not None:
            out.append(v.vals)
        elif v.iv.concrete is not None:
            out.append(np.full(length, v.iv.concrete, dtype=np.float64))
        else:
            return None
    return src, out


class _Interp:
    """One abstract execution of a (kernel) jaxpr.

    ``program_ids`` maps grid axis -> Interval (a point when the caller is
    enumerating grid steps).  Refs are ordinary vars whose AbsVal is the
    *current content bound*; get/swap/addupdate read and update it, and ref
    vars passed into cond/jit sub-jaxprs alias their operand so writes
    propagate back out.
    """

    def __init__(self, program_ids: dict[int, Interval]):
        self.program_ids = program_ids
        self.result = InterpResult([], [])
        self._warned: set[str] = set()

    def warn(self, msg: str) -> None:
        if msg not in self._warned:
            self._warned.add(msg)
            self.result.warnings.append(msg)

    def _acc(self, kind: str, bound: float, integer: bool, depth: int,
             operand_bound: float) -> None:
        self.result.accumulations.append(
            Accumulation(kind, bound, integer, depth, operand_bound))

    # ------------------------------------------------------------------
    def run(self, jaxpr: jcore.Jaxpr, env: _Env) -> list[AbsVal]:
        for eqn in jaxpr.eqns:
            self.eqn(eqn, env)
        return [env.read(v) for v in jaxpr.outvars]

    # ------------------------------------------------------------------
    def eqn(self, eqn, env: _Env) -> None:
        prim = eqn.primitive.name
        # structural / stateful primitives first
        if prim == "cond":
            self._cond(eqn, env)
            return
        if prim in ("jit", "closed_call", "call", "custom_jvp_call",
                    "custom_vjp_call", "remat2"):
            self._call(eqn, env)
            return

        ins = [env.read(a) for a in eqn.invars]

        def out(v: AbsVal, idx: int = 0) -> None:
            env.write(eqn.outvars[idx], v)

        if prim == "program_id":
            out(AbsVal.of(self.program_ids.get(int(eqn.params["axis"]),
                                               Interval.top())))
            return
        if prim == "get":
            # reading a small-int ref (the packed codes) seeds a fresh
            # exact image for the decode chain downstream
            content = ins[0]
            if content.vals is None:
                seeded = AbsVal.seeded(content.iv)
                out(dataclasses.replace(seeded, iv=content.iv))
            else:
                out(content)
            return
        if prim == "swap":
            # swap(ref, val) -> old; ref := val.  Strong update only when
            # the write covers the whole ref; partial writes join.
            out(ins[0])
            ref_shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
            val_shape = tuple(getattr(eqn.invars[1].aval, "shape", ()))
            if val_shape == ref_shape:
                env.write(eqn.invars[0], ins[1])
            else:
                env.write(eqn.invars[0], ins[0].join(ins[1]))
            return
        if prim == "addupdate":
            new_iv = ins[0].iv + ins[1].iv
            if new_iv.integer:
                self._acc("acc", new_iv.max_abs, True, 1,
                          max(ins[0].iv.max_abs, ins[1].iv.max_abs))
            env.write(eqn.invars[0], AbsVal.of(new_iv))
            return
        if prim == "dot_general":
            depth = _dot_depth(eqn)
            per = ins[0].iv * ins[1].iv
            res = Interval(_mul(min(per.lo, 0.0), depth),
                           _mul(max(per.hi, 0.0), depth), per.integer)
            self._acc("dot", res.max_abs, per.integer, depth, per.max_abs)
            out(AbsVal.of(res))
            return
        if prim in ("reduce_sum", "cumsum"):
            if prim == "cumsum":
                ax = eqn.params.get("axis")
                depth = (int(eqn.invars[0].aval.shape[ax])
                         if ax is not None else _reduce_depth(eqn))
            else:
                depth = _reduce_depth(eqn)
            src = ins[0].iv
            res = Interval(_mul(min(src.lo, 0.0), depth),
                           _mul(max(src.hi, 0.0), depth), src.integer)
            self._acc("acc", res.max_abs, src.integer, depth, src.max_abs)
            out(AbsVal.of(res))
            return
        if prim in ("reduce_and", "reduce_or"):
            out(AbsVal.of(_BOOL))
            return
        if prim == "iota":
            size = math.prod(int(s) for s in eqn.outvars[0].aval.shape)
            out(AbsVal.of(Interval(0.0, float(max(size - 1, 0)), True)))
            return
        if prim == "select_n":
            out(self._select_n(ins))
            return
        if prim == "convert_element_type":
            dt = np.dtype(eqn.params["new_dtype"])
            src = ins[0]
            if src.vals is not None:
                vals = (np.trunc(src.vals) if dt.kind in "ui"
                        else src.vals.astype(np.float64))
                out(AbsVal(_hull(vals), src.src, vals))
            elif dt.kind in "ui":
                riv = src.iv.to_int()
                rng = Interval.of_dtype(dt)
                if not (riv.lo >= rng.lo and riv.hi <= rng.hi):
                    riv = rng  # int conversion wraps into the dtype range
                out(AbsVal.of(riv))
            else:
                out(AbsVal.of(Interval(src.iv.lo, src.iv.hi, src.iv.integer)))
            return
        if prim == "bitcast_convert_type":
            out(self._bitcast(eqn, ins[0]))
            return
        if prim == "clamp":
            lo, x, hi = ins[0].iv, ins[1].iv, ins[2].iv
            out(AbsVal.of(Interval(
                min(max(x.lo, lo.lo), hi.hi), min(max(x.hi, lo.lo), hi.hi),
                x.integer and lo.integer and hi.integer)))
            return
        if prim in _LAYOUT_PRIMS:
            v = ins[0]
            if v.vals is not None and prim in _REARRANGE_PRIMS:
                v = dataclasses.replace(v, src=next(_seed_counter))
            out(v)
            return
        if prim in ("concatenate", "pad", "dynamic_update_slice"):
            joined = ins[0]
            for o in ins[1:]:
                joined = joined.join(o)
            out(AbsVal.of(joined.iv))
            return

        # elementwise: try the exact seed-image domain first
        if prim in _NP_UNARY or prim in _NP_BINARY:
            img = _aligned_images(ins)
            if img is not None:
                src, arrs = img
                fn = _NP_UNARY.get(prim) or _NP_BINARY[prim]
                with np.errstate(all="ignore"):
                    vals = fn(*arrs)
                if np.all(np.isfinite(vals)):
                    out(AbsVal(_hull(vals), src, vals))
                    return
        out(self._interval_rule(prim, eqn, ins))

    # ------------------------------------------------------------------
    def _interval_rule(self, prim: str, eqn, ins: list[AbsVal]) -> AbsVal:
        iv = [v.iv for v in ins]
        if prim in ("add", "add_any"):
            res = iv[0] + iv[1]
            if res.integer:
                self._acc("acc", res.max_abs, True, 1,
                          max(iv[0].max_abs, iv[1].max_abs))
            return AbsVal.of(res)
        table = {
            "sub": lambda: iv[0] - iv[1],
            "mul": lambda: iv[0] * iv[1],
            "neg": lambda: -iv[0],
            "abs": lambda: iv[0].abs(),
            "sign": lambda: Interval(-1.0, 1.0, True),
            "div": lambda: iv[0].truediv(iv[1]),
            "max": lambda: iv[0].max_(iv[1]),
            "min": lambda: iv[0].min_(iv[1]),
            "floor": lambda: iv[0].floor(),
            "ceil": lambda: iv[0].ceil(),
            "round": lambda: iv[0].round(),
            "nearbyint": lambda: iv[0].round(),
            "exp2": lambda: iv[0].exp2(),
            "and": lambda: iv[0].bit_and(iv[1]),
            "or": lambda: iv[0].bit_or(iv[1]),
            "xor": lambda: iv[0].bit_xor(iv[1]),
            "not": lambda: _BOOL,
            "shift_left": lambda: iv[0].shift_left(iv[1]),
            "shift_right_arithmetic": lambda: iv[0].shift_right(iv[1]),
            "shift_right_logical": lambda: iv[0].shift_right(iv[1]),
            "integer_pow": lambda: abs_pow(iv[0], eqn.params.get("y", 2)),
            "square": lambda: iv[0] * iv[0],
            "rsqrt": lambda: Interval(0.0, _INF, False),
            "sqrt": lambda: Interval(0.0, _INF, False),
        }
        if prim == "rem":
            a, b = iv[0], iv[1]
            ca, cb = a.concrete, b.concrete
            if ca is not None and cb is not None and cb != 0:
                return AbsVal.const(float(math.fmod(ca, cb)))
            if a.integer and b.integer and a.lo >= 0 and b.lo >= 1 \
                    and b.bounded:
                # truncated remainder of nonneg by positive: [0, b.hi - 1],
                # and never larger than the dividend itself
                return AbsVal.of(Interval(
                    0.0, min(a.hi, b.hi - 1.0) if a.bounded else b.hi - 1.0,
                    True))
            return AbsVal.of(Interval.top())
        if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
            c0, c1 = iv[0].concrete, iv[1].concrete
            if c0 is not None and c1 is not None:
                val = {"eq": c0 == c1, "ne": c0 != c1, "lt": c0 < c1,
                       "le": c0 <= c1, "gt": c0 > c1, "ge": c0 >= c1}[prim]
                return AbsVal.const(float(val))
            return AbsVal.of(_BOOL)
        if prim in table:
            return AbsVal.of(table[prim]())
        self.warn(f"no interval rule for primitive '{prim}'; widening to top")
        return AbsVal.of(Interval.top())

    # ------------------------------------------------------------------
    def _select_n(self, ins: list[AbsVal]) -> AbsVal:
        pred, cases = ins[0], ins[1:]
        img = _aligned_images(ins)
        if img is not None:
            src, arrs = img
            p = np.clip(np.trunc(arrs[0]), 0, len(cases) - 1).astype(np.int64)
            vals = np.choose(p, arrs[1:])
            return AbsVal(_hull(vals), src, vals)
        c = pred.iv.concrete
        if c is not None and 0 <= int(c) < len(cases):
            return cases[int(c)]
        v = cases[0]
        for o in cases[1:]:
            v = v.join(o)
        return v

    # ------------------------------------------------------------------
    def _bitcast(self, eqn, src: AbsVal) -> AbsVal:
        dt = np.dtype(eqn.params["new_dtype"])
        src_dt = np.dtype(eqn.invars[0].aval.dtype)
        iv = src.iv
        if dt == src_dt:
            return src  # identity cast (e.g. int32 -> int32)
        if (dt.kind in "ui" and src_dt.kind in "ui"
                and dt.itemsize == src_dt.itemsize and iv.integer
                and iv.bounded and iv.lo >= 0
                and iv.hi < 2.0 ** (8 * dt.itemsize - 1)):
            return src  # same bits, both interpretations non-negative
        if dt == np.float32 and iv.integer and iv.bounded and iv.lo >= 0 \
                and iv.hi < float(0x7F800000):
            # non-negative fp32 bit patterns order like their float values,
            # so the pattern interval maps monotonically to a float interval
            # (this is what keeps Exponent/Fraction's frac in [1, 2))
            lo = float(np.array(int(iv.lo), np.int32).view(np.float32))
            hi = float(np.array(int(iv.hi), np.int32).view(np.float32))
            return AbsVal.of(Interval(lo, hi, False))
        return AbsVal.of(Interval.of_dtype(dt))

    # ------------------------------------------------------------------
    def _cond(self, eqn, env: _Env) -> None:
        branches = eqn.params["branches"]
        operands = eqn.invars[1:]
        pred = env.read(eqn.invars[0]).iv.concrete

        def run_branch(br) -> tuple[list[AbsVal], dict]:
            sub = br.jaxpr if isinstance(br, jcore.ClosedJaxpr) else br
            benv = _Env()
            for cv in sub.constvars:
                benv.write(cv, AbsVal.of(Interval.top()))
            for v, a in zip(sub.invars, operands):
                benv.write(v, env.read(a))
            for beqn in sub.eqns:
                self.eqn(beqn, benv)
            outs = [benv.read(v) for v in sub.outvars]
            writes = {}
            for v, a in zip(sub.invars, operands):
                if not isinstance(a, jcore.Literal):
                    writes[a] = benv.read(v)
            return outs, writes

        if pred is not None and 0 <= int(pred) < len(branches):
            outs, writes = run_branch(branches[int(pred)])
            for a, val in writes.items():
                env.write(a, val)
        else:
            results = [run_branch(br) for br in branches]
            outs = []
            for i in range(len(eqn.outvars)):
                v = results[0][0][i]
                for o, _ in results[1:]:
                    v = v.join(o[i])
                outs.append(v)
            touched = {a for _, w in results for a in w}
            for a in touched:
                v = env.read(a)
                for _, w in results:
                    v = v.join(w.get(a, v))
                env.write(a, v)
        for v, val in zip(eqn.outvars, outs):
            env.write(v, val)

    # ------------------------------------------------------------------
    def _call(self, eqn, env: _Env) -> None:
        sub = None
        for v in eqn.params.values():
            if isinstance(v, jcore.ClosedJaxpr):
                sub = v.jaxpr
                break
            if isinstance(v, jcore.Jaxpr):
                sub = v
                break
        if sub is None:
            for v in eqn.outvars:
                env.write(v, AbsVal.of(Interval.top()))
            return
        senv = _Env()
        for cv in sub.constvars:
            senv.write(cv, AbsVal.of(Interval.top()))
        for v, a in zip(sub.invars, eqn.invars):
            senv.write(v, env.read(a))
        for seqn in sub.eqns:
            self.eqn(seqn, senv)
        # propagate ref-content updates made inside the call back out
        for v, a in zip(sub.invars, eqn.invars):
            if not isinstance(a, jcore.Literal):
                env.write(a, senv.read(v))
        for ov, sv in zip(eqn.outvars, sub.outvars):
            env.write(ov, senv.read(sv))


def abs_pow(iv: Interval, y: int) -> Interval:
    if y < 0:
        return Interval.top()
    res = Interval.const(1.0)
    for _ in range(int(y)):
        res = res * iv
    return res


def abstract_eval_jaxpr(
    jaxpr: jcore.Jaxpr,
    in_intervals: list[Interval],
    *,
    program_ids: dict[int, Interval] | None = None,
    steps: list[dict[int, int]] | None = None,
) -> tuple[list[Interval], InterpResult]:
    """Interval-interpret ``jaxpr`` (a Pallas kernel body or any jaxpr).

    ``in_intervals`` seeds the invars (for refs, the seed is the content
    bound of the backing buffer).  ``steps``, when given, replays the body
    once per entry with those concrete ``program_id`` values while ref
    state persists across steps — the sequential-grid semantics of the
    revisiting-accumulator pattern.  Without ``steps`` a single pass runs
    with symbolic ``program_ids``.

    Returns the final invar intervals (ref end-state bounds) and the
    :class:`InterpResult` with every accumulation event observed.
    """
    env = _Env()
    for v, iv in zip(jaxpr.invars, in_intervals):
        # small-int inputs (packed codes) get an exact seed image up front,
        # exactly as a ref `get` would seed one inside a kernel body — the
        # image over the full range is a sound superset of any element set
        env.write(v, AbsVal.seeded(iv))
    pid_default = {i: Interval.top() for i in range(8)}
    if program_ids:
        pid_default.update(program_ids)
    interp = _Interp(dict(pid_default))
    if steps is None:
        interp.run(jaxpr, env)
    else:
        for step in steps:
            pids = dict(pid_default)
            pids.update({ax: Interval.const(v) for ax, v in step.items()})
            interp.program_ids = pids
            interp.run(jaxpr, env)
    final = [env.read(v).iv for v in jaxpr.invars]
    return final, interp.result
