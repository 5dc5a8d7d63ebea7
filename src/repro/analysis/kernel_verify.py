"""Static verifier for the shipped Pallas kernels.

Two proofs per ``pallas_call``, both computed from traced jaxpr metadata
without executing or compiling anything:

**Grid / index-map coverage** — the grid is enumerated and every BlockSpec
index map is evaluated at every grid point (``jax.core.eval_jaxpr`` on
concrete indices), proving for each output that

* every output block is written at least once (no *gaps*),
* any block revisited across grid steps is revisited only along grid
  dimensions its index map does not depend on — the legal
  revisiting-accumulator pattern; two writes from points that differ in a
  *dependent* dimension are conflicting (*overlap*),
* all reads/writes land in bounds and array dims divide their block shape.

**Accumulator exactness** — the kernel body jaxpr is abstractly interpreted
in the interval ⊗ seed-image domain of :mod:`repro.analysis.intervals`,
replaying the body once per (used) grid step so VMEM scratch state
persists exactly as the sequential Pallas grid executes it.  Every
*integer* accumulation event (decoded-code dot products, running adds)
must stay below ``2^24`` so fp32 arithmetic on it is bit-exact — the
invariant behind the paper's energy argument (Sec. V-B).  This generalizes
``analysis/lint.py``'s closed-form ``accumulation_bits`` bound to
arbitrary kernel code, and agrees with it bit-for-bit on the shipped GEMM
(:func:`prove_matmul_accumulation_bits`).

Entry points: :func:`verify_entry` (a ``KERNEL_REGISTRY`` entry),
:func:`verify_candidate` (the autotuner's legality oracle for a
``(shape, qcfg, blocks)`` tiling candidate), and
:func:`run_kernel_audit` (the ``--kernels`` section of
``python -m repro.analysis.audit``, including the ``--sabotage`` negative
controls).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from collections.abc import Iterator
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # registry imports kernels; keep runtime import lazy
    from repro.kernels.registry import KernelEntry

import jax
import jax.numpy as jnp
from jax.core import eval_jaxpr
from jax.experimental import pallas as pl
from jax.extend import core as jcore

from repro.core.formats import EMFormat, FMT_IMAGENET, GS_FMT_DEFAULT
from repro.core.lowbit import QuantConfig
from repro.analysis.intervals import Interval, abstract_eval_jaxpr

__all__ = [
    "ACC_BUDGET_BITS",
    "CallReport",
    "KernelReport",
    "Violation",
    "find_pallas_eqns",
    "prove_matmul_accumulation_bits",
    "prove_window_grid",
    "run_kernel_audit",
    "verify_candidate",
    "verify_closed_jaxpr",
    "verify_entry",
    "verify_implicit_conv_candidate",
    "verify_quantize_candidate",
]

ACC_BUDGET_BITS = 24      # fp32 integer-exactness budget (paper Sec. V-B)
_MAX_GRID_POINTS = 1 << 18  # full index-map enumeration cap
_MAX_STEP_REPLAYS = 2048    # abstract body replays over used grid axes
_MAX_UNUSED_REPLAYS = 8     # unused-axis subgrid replays before fixpoint gate

SABOTAGE_MODES = ("overlap_write", "deep_k", "drop_halo")


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------
def _iter_sub_jaxprs(val) -> Iterator[jcore.Jaxpr]:
    if isinstance(val, jcore.Jaxpr):
        yield val
    elif isinstance(val, jcore.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_sub_jaxprs(v)


def find_pallas_eqns(jaxpr: jcore.Jaxpr) -> list:
    """All ``pallas_call`` eqns in ``jaxpr``, recursing into sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in _iter_sub_jaxprs(v):
                out.extend(find_pallas_eqns(sub))
    return out


def _used_program_axes(jaxpr: jcore.Jaxpr) -> set[int]:
    axes: set[int] = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "program_id":
            axes.add(int(eqn.params["axis"]))
        for v in eqn.params.values():
            for sub in _iter_sub_jaxprs(v):
                axes |= _used_program_axes(sub)
    return axes


# ---------------------------------------------------------------------------
# report dataclasses
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Violation:
    """One statically proven defect in a kernel's grid or arithmetic."""

    kind: str    # gap | overlap | oob | divisibility | overflow | unproven
    where: str   # block-mapping origin ("outputs[0]", "args[2]") or "body"
    detail: str

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CallReport:
    """Verification result for one ``pallas_call``."""

    kernel: str
    grid: tuple[int, ...]
    violations: list[Violation]
    coverage: dict
    accumulations: list[dict]
    max_integer_bits: int
    out_bounds: dict
    warnings: list[str]
    exhaustive: bool

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "kernel": self.kernel,
            "grid": list(self.grid),
            "ok": self.ok,
            "exhaustive": self.exhaustive,
            "violations": [v.to_json() for v in self.violations],
            "coverage": self.coverage,
            "max_integer_accumulation_bits": self.max_integer_bits,
            "accumulations": self.accumulations,
            "out_bounds": self.out_bounds,
            "warnings": self.warnings,
        }


@dataclasses.dataclass
class KernelReport:
    """Aggregated verification of one kernel entry point (all its calls)."""

    name: str
    calls: list[CallReport]

    @property
    def ok(self) -> bool:
        return bool(self.calls) and all(c.ok for c in self.calls)

    @property
    def max_integer_bits(self) -> int:
        return max((c.max_integer_bits for c in self.calls), default=0)

    @property
    def violations(self) -> list[Violation]:
        return [v for c in self.calls for v in c.violations]

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "ok": self.ok,
            "num_pallas_calls": len(self.calls),
            "max_integer_accumulation_bits": self.max_integer_bits,
            "calls": [c.to_json() for c in self.calls],
        }


# ---------------------------------------------------------------------------
# coverage proofs
# ---------------------------------------------------------------------------
def _block_sizes(bm) -> tuple[int, ...]:
    """Block extent per dim (``Blocked`` sizes; a squeezed dim is 1)."""
    return tuple(
        1 if isinstance(b, pl.Squeezed) else b.block_size
        for b in bm.block_shape)


def _eval_index_map(bm, point: tuple[int, ...]) -> tuple[int, ...]:
    im = bm.index_map_jaxpr
    res = eval_jaxpr(im.jaxpr, im.consts, *point)
    return tuple(int(r) for r in res)


def _dependent_dims(table: dict, ndim: int) -> set[int]:
    """Grid dims whose value the index map's output actually varies with."""
    deps: set[int] = set()
    for d in range(ndim):
        seen: dict = {}
        for pt, idx in table.items():
            key = pt[:d] + pt[d + 1:]
            if key in seen:
                if seen[key] != idx:
                    deps.add(d)
                    break
            else:
                seen[key] = idx
    return deps


def _check_operand(
    name: str, bm, grid: tuple[int, ...], points: list[tuple[int, ...]],
    is_output: bool,
) -> tuple[list[Violation], dict | None]:
    viols: list[Violation] = []
    shape = tuple(int(s) for s in bm.array_aval.shape)
    bs = _block_sizes(bm)
    for i, (s, b) in enumerate(zip(shape, bs)):
        if b < 1 or s % b:
            viols.append(Violation(
                "divisibility", name,
                f"dim {i}: array extent {s} not divisible by block {b}",
            ))
    table = {pt: _eval_index_map(bm, pt) for pt in points}
    nblocks = tuple(-(-s // b) for s, b in zip(shape, bs))
    oob = [
        (pt, idx) for pt, idx in table.items()
        if any(ix < 0 or ix >= nb for ix, nb in zip(idx, nblocks))
    ]
    if oob:
        pt, idx = oob[0]
        word = "write" if is_output else "read"
        viols.append(Violation(
            "oob", name,
            f"{word} out of bounds: grid point {pt} -> block {idx} outside "
            f"{nblocks} ({len(oob)} of {len(table)} grid points)",
        ))
    if not is_output:
        return viols, None

    deps = sorted(_dependent_dims(table, len(grid)))
    groups: dict[tuple, list[tuple]] = {}
    for pt, idx in table.items():
        groups.setdefault(idx, []).append(pt)
    for idx, pts in groups.items():
        by_proj: dict[tuple, tuple] = {}
        for p in pts:
            by_proj.setdefault(tuple(p[d] for d in deps), p)
        if len(by_proj) > 1:
            pa, pb = list(by_proj.values())[:2]
            viols.append(Violation(
                "overlap", name,
                f"output block {idx} written from grid points {pa} and {pb}, "
                f"which differ in grid dims {deps} that the index map "
                f"depends on — conflicting writes, not a legal revisit",
            ))
            break
    required = set(itertools.product(*[range(n) for n in nblocks]))
    missing = sorted(required - set(groups))
    if missing:
        viols.append(Violation(
            "gap", name,
            f"{len(missing)} of {len(required)} output blocks never "
            f"written, e.g. block {missing[0]}",
        ))
    cov = {
        "output_blocks": len(required),
        "blocks_written": len(set(groups) & required),
        "revisit_depth": max(len(p) for p in groups.values()),
        "index_map_grid_dims": deps,
    }
    return viols, cov


# ---------------------------------------------------------------------------
# overflow proof
# ---------------------------------------------------------------------------
def _prove_body(eqn, grid: tuple[int, ...]):
    gm = eqn.params["grid_mapping"]
    body = eqn.params["jaxpr"]
    if isinstance(body, jcore.ClosedJaxpr):
        body = body.jaxpr
    warnings: list[str] = []
    exhaustive = True

    seeds = [
        Interval.of_dtype(bm.array_aval.dtype) for bm in gm.block_mappings
    ]
    seeds += [Interval.top()] * len(gm.scratch_avals)
    if len(seeds) != len(body.invars):
        warnings.append(
            f"body has {len(body.invars)} invars but {len(seeds)} block "
            f"mappings + scratch; widening the rest to top"
        )
        seeds = (seeds + [Interval.top()] * len(body.invars))[
            : len(body.invars)]

    used = sorted(_used_program_axes(body) & set(range(len(grid))))
    sizes = [grid[a] for a in used]
    steps = None
    if used and math.prod(sizes) <= _MAX_STEP_REPLAYS:
        steps = [
            dict(zip(used, combo))
            for combo in itertools.product(*[range(n) for n in sizes])
        ]
    elif used:
        warnings.append(
            f"grid axes {used} span {math.prod(sizes)} steps > "
            f"{_MAX_STEP_REPLAYS}; falling back to one symbolic pass"
        )
        exhaustive = False

    finals, res = abstract_eval_jaxpr(body, seeds, steps=steps)
    accs = list(res.accumulations)
    warnings += res.warnings
    violations: list[Violation] = []

    # The sequential grid replays the used-axes subgrid once per setting of
    # the unused axes, with scratch state carried across replays.  Re-run
    # the abstraction seeded with the previous pass's end state until it
    # reaches a fixpoint (a well-formed kernel re-initializes its
    # accumulators every replay) or the concrete replay count is exhausted.
    # State still widening once the cap cuts the iteration short means the
    # recorded bounds under-cover the remaining concrete replays, so it
    # gates as unproven rather than merely warning.
    unused_repeat = math.prod(
        g for a, g in enumerate(grid) if a not in used
    ) if grid else 1
    if steps is not None and unused_repeat > 1:
        replays = min(unused_repeat, _MAX_UNUSED_REPLAYS)
        widening = False
        for _ in range(replays - 1):
            finals2, res2 = abstract_eval_jaxpr(body, finals, steps=steps)
            accs += res2.accumulations
            widening = any(
                (f2.lo < f1.lo or f2.hi > f1.hi)
                for f1, f2 in zip(finals, finals2)
            )
            finals = finals2
            if not widening:
                break
        if widening and unused_repeat > replays:
            violations.append(Violation(
                "unproven", "body",
                f"ref state keeps widening after {replays} of "
                f"{unused_repeat} grid replays (accumulator not "
                f"re-initialized per output tile?): accumulation bounds "
                f"for the remaining replays are not covered",
            ))
    return finals, accs, warnings, exhaustive, violations


def verify_pallas_eqn(eqn, name: str) -> CallReport:
    """Run both proofs on one traced ``pallas_call`` eqn."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    violations: list[Violation] = []
    coverage: dict = {}
    warnings: list[str] = []
    exhaustive = True

    npoints = math.prod(grid) if grid else 1
    if int(gm.num_index_operands):
        warnings.append(
            f"{gm.num_index_operands} scalar-prefetch operands not modeled")
    if npoints > _MAX_GRID_POINTS:
        warnings.append(
            f"grid {grid} has {npoints} points > {_MAX_GRID_POINTS}; "
            f"coverage not proven")
        exhaustive = False
        points: list[tuple[int, ...]] = []
    else:
        points = list(itertools.product(*[range(g) for g in grid]))

    n_in, n_out = int(gm.num_inputs), int(gm.num_outputs)
    if points:
        for k, bm in enumerate(gm.block_mappings):
            is_output = k >= n_in
            where = str(getattr(bm, "origin", None) or (
                f"outputs[{k - n_in}]" if is_output else f"args[{k}]"))
            viols, cov = _check_operand(where, bm, grid, points, is_output)
            violations += viols
            if cov is not None:
                coverage[where] = cov

    finals, accs, body_warnings, body_exhaustive, body_viols = _prove_body(
        eqn, grid)
    warnings += body_warnings
    violations += body_viols
    exhaustive = exhaustive and body_exhaustive
    int_accs = [a for a in accs if a.integer]
    max_bits = max((a.bits for a in int_accs), default=0)
    for a in int_accs:
        if a.bits >= ACC_BUDGET_BITS:
            violations.append(Violation(
                "overflow", "body",
                f"integer {a.kind} accumulation spans {min(a.bits, 9999)} "
                f"bits (|bound| {a.bound:g}, depth {a.depth}, operand bound "
                f"{a.operand_bound:g}) >= {ACC_BUDGET_BITS}: fp32 "
                f"accumulation is no longer bit-exact",
            ))
            break
    out_bounds = {}
    for k in range(n_in, n_in + n_out):
        bm = gm.block_mappings[k]
        where = str(getattr(bm, "origin", None) or f"outputs[{k - n_in}]")
        if k < len(finals):
            out_bounds[where] = finals[k].to_json()

    seen = set()
    acc_json = []
    for a in accs:
        key = (a.kind, a.bound, a.depth, a.integer)
        if key not in seen:
            seen.add(key)
            acc_json.append(a.to_json())
    return CallReport(
        kernel=name, grid=grid, violations=violations, coverage=coverage,
        accumulations=acc_json, max_integer_bits=max_bits,
        out_bounds=out_bounds, warnings=sorted(set(warnings)),
        exhaustive=exhaustive,
    )


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def verify_closed_jaxpr(cj: jcore.ClosedJaxpr, name: str) -> KernelReport:
    eqns = find_pallas_eqns(cj.jaxpr)
    calls = [
        verify_pallas_eqn(eqn, f"{name}#{i}") for i, eqn in enumerate(eqns)
    ]
    if not calls:
        calls = [CallReport(
            kernel=name, grid=(), coverage={}, accumulations=[],
            max_integer_bits=0, out_bounds={}, warnings=[], exhaustive=False,
            violations=[Violation(
                "unproven", "body", "no pallas_call found in trace")],
        )]
    return KernelReport(name=name, calls=calls)


def verify_entry(entry: KernelEntry) -> KernelReport:
    """Verify one ``repro.kernels.KERNEL_REGISTRY`` entry."""
    return verify_closed_jaxpr(entry.trace(), entry.name)


def _unpack_qcfg(qcfg) -> tuple[EMFormat, int, EMFormat]:
    if isinstance(qcfg, QuantConfig):
        return qcfg.fmt, qcfg.k_block, qcfg.gs_fmt
    fmt, k_block = qcfg
    return fmt, int(k_block), GS_FMT_DEFAULT


def verify_candidate(
    shape: tuple[int, int, int], qcfg, blocks: tuple[int, int] | None = None,
    grouping: str | None = None,
) -> KernelReport:
    """Autotuner legality oracle: statically verify one tiling candidate.

    ``shape`` is the GEMM ``(M, K, N)``; ``qcfg`` a ``QuantConfig`` or a
    bare ``(fmt, k_block)`` pair (for sweeps over configs that
    ``QuantConfig`` itself would refuse to construct); ``blocks`` the
    ``(block_m, block_n)`` output tiling; ``grouping`` the group-scale
    layout (``None`` takes the QuantConfig's grouping, or ``"nc"``).  The
    full fused pipeline (quantize x, quantize w, quantized-domain GEMM) is
    traced at those shapes and every ``pallas_call`` is proven — nothing is
    compiled, so illegal tilings are pruned before costing a Mosaic
    compile.
    """
    M, K, N = shape
    fmt, k_block, gs_fmt = _unpack_qcfg(qcfg)
    if grouping is None:
        grouping = qcfg.grouping if isinstance(qcfg, QuantConfig) else "nc"
    block_m, block_n = blocks or (128, 128)
    from repro.kernels.ops import lowbit_matmul_fused

    def fn(x, w):
        return lowbit_matmul_fused(
            x, w, None, fmt=fmt, gs_fmt=gs_fmt, k_block=k_block,
            block_m=block_m, block_n=block_n, grouping=grouping,
            interpret=True,
        )

    cj = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32),
    )
    return verify_closed_jaxpr(
        cj,
        f"candidate_{M}x{K}x{N}_{fmt}_kb{k_block}_b{block_m}x{block_n}"
        f"_{grouping}",
    )


def verify_quantize_candidate(
    shape: tuple[int, int], fmt: EMFormat, k_block: int, block_m: int,
    gs_fmt: EMFormat = GS_FMT_DEFAULT, grouping: str = "nc",
) -> KernelReport:
    """Legality oracle for a quantizer tiling candidate: trace
    ``mls_quantize_pallas`` on an ``(M, K)`` operand at one ``block_m`` /
    ``grouping`` and statically prove every ``pallas_call`` (grid coverage
    + accumulator budget), without compiling."""
    M, K = shape
    from repro.kernels.mls_quantize import mls_quantize_pallas

    def fn(x):
        return mls_quantize_pallas(
            x, fmt, k_block, gs_fmt, None, block_m=block_m,
            interpret=True, grouping=grouping,
        )

    cj = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((M, K), jnp.float32))
    return verify_closed_jaxpr(
        cj, f"qcandidate_{M}x{K}_{fmt}_kb{k_block}_bm{block_m}_{grouping}")


def prove_window_grid(
    geom, bh: int, cb: int, block_n: int, *,
    band_h_override: int | None = None,
) -> tuple[list[Violation], dict]:
    """Coverage proof for the implicit-GEMM conv's halo'd window grid.

    The implicit kernel's activation BlockSpec fetches whole images — the
    actual patch addressing is the in-kernel strided tap loads from the
    halo band of each M-tile, which the generic index-map enumeration
    cannot see.
    This replays that address arithmetic over the full grid and proves:

    * every halo band ``[row0, row0 + band_h)`` stays inside the padded
      input and contains every tap row its ``bh`` output rows need,
    * every ``(image, output_row)`` pair is produced by exactly one M-tile
      and every input channel by exactly one K-tile (no gaps, no overlaps),
    * tap column slices stay inside the padded width.

    ``band_h_override`` exists for the ``drop_halo`` negative control —
    shrinking the band must surface an ``oob`` violation here.
    """
    viols: list[Violation] = []
    cov: dict = {}
    oh, ow, kh, kw = geom.oh, geom.ow, geom.kh, geom.kw
    sh, sw, hp, wp = geom.sh, geom.sw, geom.hp, geom.wp
    for cond, msg in (
        (bh >= 1 and oh % bh == 0, f"bh={bh} must divide OH={oh}"),
        (cb >= 1 and geom.c % cb == 0, f"cb={cb} must divide C={geom.c}"),
        (block_n >= 1, f"block_n={block_n} must be positive"),
    ):
        if not cond:
            viols.append(Violation("divisibility", "window_grid", msg))
    if viols:
        return viols, cov
    band_h = sh * (bh - 1) + kh if band_h_override is None \
        else band_h_override
    oh_tiles, n_k = oh // bh, geom.c // cb
    m_tiles = geom.m0 // (bh * ow)
    rows_covered: dict[tuple[int, int], int] = {}
    for i in range(m_tiles):
        img, rt = divmod(i, oh_tiles)
        row0 = rt * bh * sh
        if img >= geom.n:
            viols.append(Violation(
                "oob", "window_grid",
                f"M-tile {i} addresses image {img} >= N={geom.n}"))
            break
        if row0 < 0 or row0 + band_h > hp:
            viols.append(Violation(
                "oob", "window_grid",
                f"M-tile {i}: halo band rows [{row0}, {row0 + band_h}) "
                f"outside padded input height {hp}"))
            break
        bad = next(
            ((r, kh_) for r in range(bh) for kh_ in range(kh)
             if kh_ + sh * r >= band_h), None)
        if bad is not None:
            r, kh_ = bad
            viols.append(Violation(
                "oob", "window_grid",
                f"M-tile {i}: output row {rt * bh + r} tap {kh_} needs "
                f"band row {kh_ + sh * r} >= band_h={band_h} — halo band "
                f"too short"))
            break
        for r in range(bh):
            key = (img, rt * bh + r)
            rows_covered[key] = rows_covered.get(key, 0) + 1
    if kw + sw * (ow - 1) > wp:
        viols.append(Violation(
            "oob", "window_grid",
            f"tap column slice spans {kw + sw * (ow - 1)} > padded width "
            f"{wp}"))
    if not any(v.kind == "oob" for v in viols):
        want = {(n_, r_) for n_ in range(geom.n) for r_ in range(oh)}
        missing = sorted(want - set(rows_covered))
        dup = sorted(k for k, v in rows_covered.items() if v > 1)
        if missing:
            viols.append(Violation(
                "gap", "window_grid",
                f"{len(missing)} of {len(want)} (image, output_row) pairs "
                f"never produced, e.g. {missing[0]}"))
        if dup:
            viols.append(Violation(
                "overlap", "window_grid",
                f"(image, output_row) {dup[0]} produced by multiple "
                f"M-tiles"))
        chans = [c_ for k in range(n_k) for c_ in range(k * cb, k * cb + cb)]
        if sorted(chans) != list(range(geom.c)) or any(
                c_ >= geom.c for c_ in chans):
            viols.append(Violation(
                "gap", "window_grid",
                f"K-tiles cover channels {sorted(set(chans))[:4]}... "
                f"instead of 0..{geom.c - 1} exactly once"))
    cov = {
        "output_blocks": geom.n * oh,
        "blocks_written": len(rows_covered),
        "band_h": band_h,
        "m_tiles": m_tiles,
        "k_tiles": n_k,
    }
    return viols, cov


def verify_implicit_conv_candidate(
    geom, fmt: EMFormat, k_block: int, bh: int, block_n: int,
    grouping: str = "nc", gs_fmt: EMFormat = GS_FMT_DEFAULT,
) -> KernelReport:
    """Legality oracle for an implicit-GEMM conv tiling candidate.

    Combines the generic pallas proofs (trace
    :func:`repro.kernels.implicit_conv.implicit_conv_forward` and prove
    every ``pallas_call``: BlockSpec coverage + the 2^24 accumulator
    budget over the fused quantize+GEMM body) with the window-grid proof
    of :func:`prove_window_grid`, which covers the in-kernel halo
    addressing the BlockSpec enumeration cannot see.
    """
    from repro.kernels.implicit_conv import implicit_compatible, \
        implicit_conv_forward

    name = (f"iconv_{'x'.join(str(d) for d in geom.as_dims())}"
            f"_{fmt}_kb{k_block}_bh{bh}_bn{block_n}_{grouping}")
    ok, reason = implicit_compatible(geom, k_block)
    window_viols: list[Violation] = []
    cov: dict = {}
    if not ok:
        window_viols.append(Violation("divisibility", "window_grid", reason))
    else:
        window_viols, cov = prove_window_grid(
            geom, bh, k_block // geom.kk, block_n)
    calls: list[CallReport] = [CallReport(
        kernel=f"{name}#window", grid=(), violations=window_viols,
        coverage={"window_grid": cov} if cov else {}, accumulations=[],
        max_integer_bits=0, out_bounds={}, warnings=[], exhaustive=True,
    )]
    if not window_viols:
        stride = (geom.sh, geom.sw)
        padding = [(geom.ph_lo, geom.ph_hi), (geom.pw_lo, geom.pw_hi)]

        def fn(x, w):
            return implicit_conv_forward(
                x, w, None, None, stride, padding, fmt=fmt, gs_fmt=gs_fmt,
                k_block=k_block, bh=bh, block_n=block_n, grouping=grouping,
                interpret=True,
            )

        try:
            cj = jax.make_jaxpr(fn)(
                jax.ShapeDtypeStruct(
                    (geom.n, geom.c, geom.h, geom.w), jnp.float32),
                jax.ShapeDtypeStruct(
                    (geom.o, geom.c, geom.kh, geom.kw), jnp.float32),
            )
        except ValueError as e:
            calls.append(CallReport(
                kernel=f"{name}#trace", grid=(), coverage={},
                accumulations=[], max_integer_bits=0, out_bounds={},
                warnings=[], exhaustive=True,
                violations=[Violation(
                    "divisibility", "trace",
                    f"kernel rejected the tiling: {e}")],
            ))
        else:
            calls += verify_closed_jaxpr(cj, name).calls
    return KernelReport(name=name, calls=calls)


def prove_matmul_accumulation_bits(fmt: EMFormat, k_block: int) -> int:
    """Interval-prover bound on the GEMM's integer accumulator width for
    one ``(fmt, k_block)`` — must equal
    :func:`repro.core.formats.accumulation_bits` (the lint's closed form)
    for every legal pair; the agreement is asserted in the test suite."""
    from repro.kernels.mls_matmul import mls_matmul_pallas

    M = N = 8
    K = 2 * k_block

    def fn(xc, xsg, xst, wc, wsg, wst):
        return mls_matmul_pallas(
            xc, xsg, xst, wc, wsg, wst, fmt, k_block=k_block,
            block_m=M, block_n=N, interpret=True,
        )

    cj = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((M, K), jnp.uint8),
        jax.ShapeDtypeStruct((M, K // k_block), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.uint8),
        jax.ShapeDtypeStruct((K // k_block, N), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    report = verify_closed_jaxpr(cj, f"prove_{fmt}_kb{k_block}")
    return report.max_integer_bits


# ---------------------------------------------------------------------------
# sabotage negative controls (CI must prove these fail)
# ---------------------------------------------------------------------------
def _sabotage_overlap_jaxpr() -> jcore.ClosedJaxpr:
    """Matmul-shaped kernel whose output index map folds two j-steps onto
    one block: ``(i, j - j % 2)`` writes block columns {0, 2} twice each and
    never writes {1, 3} — an overlap *and* a gap the verifier must name."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bm = bn = bk = 8
    n_k = 2

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

        @pl.when(k == n_k - 1)
        def _done():
            o_ref[...] = acc_ref[...]

    def fn(x, w):
        return pl.pallas_call(
            kernel,
            grid=(1, 4, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (0, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j - j % 2)),
            out_shape=jax.ShapeDtypeStruct((bm, 4 * bn), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=True,
        )(x, w)

    return jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((bm, n_k * bk), jnp.float32),
        jax.ShapeDtypeStruct((n_k * bk, 4 * bn), jnp.float32),
    )


def _sabotage_deep_k_jaxpr() -> jcore.ClosedJaxpr:
    """The shipped GEMM kernel at a contraction tile the closed form
    rejects: <2,4> x k_block=2048 accumulates 25 integer bits >= 24.
    ``QuantConfig`` refuses to construct this, but the raw kernel accepts
    it — exactly the hole the interval prover closes."""
    from repro.kernels.mls_matmul import mls_matmul_pallas

    fmt, k_block, M, N = FMT_IMAGENET, 2048, 8, 8
    K = k_block

    def fn(xc, xsg, xst, wc, wsg, wst):
        return mls_matmul_pallas(
            xc, xsg, xst, wc, wsg, wst, fmt, k_block=k_block,
            block_m=M, block_n=N, interpret=True,
        )

    return jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((M, K), jnp.uint8),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.uint8),
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
    )


def _sabotage_drop_halo_report() -> KernelReport:
    """The implicit conv's window grid with the last halo row dropped:
    ``band_h - 1`` leaves the deepest tap of every M-tile's last output row
    unreadable — the window proof must name the ``oob``."""
    from repro.kernels.implicit_conv import conv_geometry

    geom = conv_geometry((2, 4, 8, 8), (8, 4, 3, 3), (1, 1), "SAME")
    bh, cb, bn = 2, 2, 8
    band_h = geom.sh * (bh - 1) + geom.kh
    viols, cov = prove_window_grid(
        geom, bh, cb, bn, band_h_override=band_h - 1)
    name = "sabotage:drop_halo"
    return KernelReport(name=name, calls=[CallReport(
        kernel=f"{name}#window", grid=(), violations=viols,
        coverage={"window_grid": cov} if cov else {}, accumulations=[],
        max_integer_bits=0, out_bounds={}, warnings=[], exhaustive=True,
    )])


# builders return either a ClosedJaxpr to verify or a finished KernelReport
_SABOTAGE_BUILDERS = {
    "overlap_write": _sabotage_overlap_jaxpr,
    "deep_k": _sabotage_deep_k_jaxpr,
    "drop_halo": _sabotage_drop_halo_report,
}


def run_kernel_audit(sabotage: str | None = None) -> dict:
    """Verify every ``KERNEL_REGISTRY`` entry (+ an optional planted
    negative control) and return the ``--kernels`` report section."""
    from repro.kernels import KERNEL_REGISTRY

    reports = {
        name: verify_entry(entry) for name, entry in KERNEL_REGISTRY.items()
    }
    if sabotage is not None:
        built = _SABOTAGE_BUILDERS[sabotage]()
        name = f"sabotage:{sabotage}"
        if isinstance(built, KernelReport):
            reports[name] = built
        else:
            reports[name] = verify_closed_jaxpr(built, name)
    return {
        "budget_bits": ACC_BUDGET_BITS,
        "ok": all(r.ok for r in reports.values()),
        "kernels": {name: r.to_json() for name, r in reports.items()},
    }
