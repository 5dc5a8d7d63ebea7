"""Static quantized-domain coverage analysis of traced JAX programs.

The paper's energy argument (Sec. VII) requires *every* hot-path MAC to run
on MLS low-bit operands — a single silently-unquantized ``dot_general`` (a
layer that forgot its ``QuantConfig``, a backend that fell back to XLA fp32)
voids it.  This module walks a jaxpr — recursing through ``jit``,
``custom_vjp``/``custom_jvp``, ``scan``, ``while``, ``cond``, ``remat``,
``shard_map`` and ``pallas_call`` — and classifies every FLOP-bearing
primitive (``dot_general``, ``conv_general_dilated``) into:

* ``quantized`` — a contraction executed inside a Pallas kernel on values
  decoded from packed integer MLS codes (both operands reach the dot through
  an int8/uint8 taint chain: the quantized-domain GEMM of
  ``mls_matmul_pallas``).  Pallas grid dimensions multiply the per-program
  MAC count, scan lengths multiply their body.
* ``data_movement`` — a conv whose filter is *constant-derived* (built from
  literals/iota with no dependence on any traced input).  This is the
  im2col patch extraction / col2im scatter of ``kernels.lowbit_conv``: a
  one-hot identity filter, i.e. a gather on real hardware, not MACs.  These
  are reported separately, never silently dropped.
* ``full_precision`` — everything else: XLA dots/convs on float operands
  (fake-quant simulation, attention score GEMMs, unquantized first/last
  layers, a planted fp32 op on the hot path).

MAC counting is static (shape arithmetic on avals); nothing is executed, so
full-scale graphs can be audited on any host via ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs.

``quantized_fraction = quantized / (quantized + full_precision)`` is the
number the CI gate compares against the checked-in baseline.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

__all__ = ["Site", "CoverageReport", "coverage_of_jaxpr", "trace_coverage"]

_INT_CODE_DTYPES = (jnp.uint8, jnp.int8)


@dataclasses.dataclass
class Site:
    """One FLOP-bearing primitive instance (multiplier-weighted)."""

    path: str  # scope chain, e.g. "jit:train_step/scan/pallas:_kernel"
    kind: str  # "dot" | "conv"
    klass: str  # "quantized" | "full_precision" | "data_movement"
    macs: int  # multiply-accumulates, weighted by loop/grid multipliers
    out_shape: tuple

    def to_json(self) -> dict:
        return {
            "path": self.path, "kind": self.kind, "class": self.klass,
            "macs": self.macs, "out_shape": list(self.out_shape),
        }


@dataclasses.dataclass
class CoverageReport:
    sites: list[Site]
    warnings: list[str]

    def _total(self, klass: str) -> int:
        return sum(s.macs for s in self.sites if s.klass == klass)

    @property
    def quantized_macs(self) -> int:
        return self._total("quantized")

    @property
    def full_precision_macs(self) -> int:
        return self._total("full_precision")

    @property
    def data_movement_macs(self) -> int:
        return self._total("data_movement")

    @property
    def quantized_fraction(self) -> float:
        denom = self.quantized_macs + self.full_precision_macs
        return self.quantized_macs / denom if denom else 0.0

    def full_precision_sites(self) -> list[Site]:
        return sorted((s for s in self.sites if s.klass == "full_precision"),
                      key=lambda s: -s.macs)

    def to_json(self, top_sites: int = 24) -> dict:
        ranked = sorted(self.sites, key=lambda s: -s.macs)
        return {
            "quantized_macs": self.quantized_macs,
            "full_precision_macs": self.full_precision_macs,
            "data_movement_macs": self.data_movement_macs,
            "quantized_fraction": round(self.quantized_fraction, 6),
            "n_sites": len(self.sites),
            "sites": [s.to_json() for s in ranked[:top_sites]],
            "full_precision_sites": [
                s.to_json() for s in self.full_precision_sites()[:top_sites]
            ],
            "warnings": self.warnings,
        }


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _aval_shape(atom) -> tuple:
    aval = getattr(atom, "aval", None)
    return tuple(getattr(aval, "shape", ()))


def _aval_is_int_code(atom) -> bool:
    aval = getattr(atom, "aval", None)
    dt = getattr(aval, "dtype", None)
    return dt is not None and any(dt == d for d in _INT_CODE_DTYPES)


def _prod(xs) -> int:
    return math.prod(int(x) for x in xs)


def _dot_macs(eqn) -> int:
    (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
    lhs_shape = _aval_shape(eqn.invars[0])
    k = _prod(lhs_shape[d] for d in lhs_c)
    return _prod(_aval_shape(eqn.outvars[0])) * k


def _conv_macs(eqn) -> int:
    dn = eqn.params["dimension_numbers"]
    rhs_shape = _aval_shape(eqn.invars[1])
    rhs_spec = dn.rhs_spec  # (out_chan, in_chan, *spatial) dim indices
    k = rhs_shape[rhs_spec[1]] * _prod(rhs_shape[d] for d in rhs_spec[2:])
    return _prod(_aval_shape(eqn.outvars[0])) * k


def _sub_jaxprs(params: dict) -> list[tuple[str, Any]]:
    """All (param_name, Jaxpr|ClosedJaxpr) pairs of an eqn's params."""
    out = []
    for k, v in params.items():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for vv in vs:
            if isinstance(vv, jcore.ClosedJaxpr):
                out.append((k, vv.jaxpr))
            elif isinstance(vv, jcore.Jaxpr):
                out.append((k, vv))
    return out


def _scope_name(eqn) -> str | None:
    """Human-readable scope for an eqn that has sub-jaxprs."""
    prim = eqn.primitive.name
    name = eqn.params.get("name")
    if not isinstance(name, str):
        nsi = eqn.params.get("name_and_src_info")
        name = getattr(nsi, "name", None)
    if prim == "pallas_call":
        return f"pallas:{name}" if name else "pallas"
    if prim == "scan":
        return f"scan[{eqn.params.get('length', '?')}]"
    return f"{prim}:{name}" if name else prim


class _Walker:
    def __init__(self):
        self.sites: list[Site] = []
        self.warnings: list[str] = []
        self._warned: set[str] = set()

    def _warn(self, msg: str):
        if msg not in self._warned:
            self._warned.add(msg)
            self.warnings.append(msg)

    def walk(self, jaxpr, const_in, taint_in, mult, path, in_pallas):
        # per-var flags within this jaxpr
        const: dict[Any, bool] = {}
        taint: dict[Any, bool] = {}
        for v, c in zip(jaxpr.invars, const_in):
            const[v] = bool(c)
        for v, t in zip(jaxpr.invars, taint_in):
            taint[v] = bool(t) or _aval_is_int_code(v)
        for v in jaxpr.constvars:
            const[v] = True
            taint[v] = _aval_is_int_code(v)

        def is_const(atom):
            if isinstance(atom, jcore.Literal):
                return True
            return const.get(atom, False)

        def is_tainted(atom):
            if isinstance(atom, jcore.Literal):
                return False
            return taint.get(atom, False) or _aval_is_int_code(atom)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_const = [is_const(a) for a in eqn.invars]
            in_taint = [is_tainted(a) for a in eqn.invars]
            out_const = all(in_const)
            out_taint = any(in_taint)

            if prim == "dot_general":
                both_int = in_taint[0] and in_taint[1]
                klass = "quantized" if (in_pallas and both_int) \
                    else "full_precision"
                self.sites.append(Site(
                    path, "dot", klass, mult * _dot_macs(eqn),
                    _aval_shape(eqn.outvars[0]),
                ))
            elif prim == "conv_general_dilated":
                if is_const(eqn.invars[1]):
                    klass = "data_movement"  # constant (patch/identity) filter
                elif in_pallas and in_taint[0] and in_taint[1]:
                    klass = "quantized"
                else:
                    klass = "full_precision"
                self.sites.append(Site(
                    path, "conv", klass, mult * _conv_macs(eqn),
                    _aval_shape(eqn.outvars[0]),
                ))
            else:
                subs = _sub_jaxprs(eqn.params)
                if subs:
                    self._recurse(eqn, subs, in_const, in_taint, mult, path,
                                  in_pallas)

            for v in eqn.outvars:
                const[v] = out_const
                taint[v] = out_taint or _aval_is_int_code(v)

    def _recurse(self, eqn, subs, in_const, in_taint, mult, path, in_pallas):
        prim = eqn.primitive.name
        scope = _scope_name(eqn)
        sub_path = f"{path}/{scope}" if path else scope
        sub_mult = mult
        sub_pallas = in_pallas

        if prim == "pallas_call":
            grid = tuple(getattr(eqn.params.get("grid_mapping"), "grid", ()) or ())
            sub_mult = mult * (_prod(grid) if grid else 1)
            sub_pallas = True
            # kernel refs don't map 1:1 onto outer operands (outputs/scratch
            # are refs too); taint is re-seeded from the refs' dtypes.
            in_const, in_taint = [], []
        elif prim == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif prim == "while":
            self._warn(
                "while-loop encountered: trip count is not static, its body "
                "FLOPs are counted once"
            )
        elif prim == "cond":
            self._warn(
                "cond encountered: all branches counted (upper bound)"
            )
            # branch jaxprs see the operands minus the predicate
            in_const = in_const[1:]
            in_taint = in_taint[1:]

        for _, sub in subs:
            n = len(sub.invars)
            c = (in_const + [False] * n)[:n]
            t = (in_taint + [False] * n)[:n]
            self.walk(sub, c, t, sub_mult, sub_path, sub_pallas)


def coverage_of_jaxpr(closed: jcore.ClosedJaxpr) -> CoverageReport:
    """Classify every dot/conv MAC of an already-traced ``ClosedJaxpr``."""
    w = _Walker()
    n = len(closed.jaxpr.invars)
    w.walk(closed.jaxpr, [False] * n, [False] * n, 1, "", False)
    return CoverageReport(w.sites, w.warnings)


def trace_coverage(fn, *args, **kwargs) -> CoverageReport:
    """Trace ``fn`` (no execution — ``ShapeDtypeStruct`` args are fine) and
    audit its jaxpr."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return coverage_of_jaxpr(closed)
