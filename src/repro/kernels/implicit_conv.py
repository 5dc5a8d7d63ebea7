"""Implicit-GEMM fused conv kernel: quantize-in-prologue, no im2col.

The im2col lowering in :mod:`repro.kernels.lowbit_conv` materializes the
full fp32 patch matrix in HBM — every input element is duplicated
``kh * kw`` times at 4 bytes — before the quantize kernel even runs.  This
module is the paper's memory story done properly (Sec. VII: the energy win
of low-bit training is realized in *traffic*, not just MAC width): one
Pallas kernel walks the NCHW activation directly and fuses the dynamic
quantization of paper Alg. 2 into the GEMM prologue.

How the implicit GEMM is laid out
---------------------------------

The virtual GEMM is the same one im2col produces — ``(M0, K0) @ (K0, O)``
with ``M0 = N*OH*OW`` patch rows and ``K0 = C*kh*kw`` features in
``(c, kh, kw)`` order — but no patch matrix ever exists:

* Grid ``(M0/bm, Op/bn, K0/kb)`` with the contraction innermost, where the
  M-tile is ``bm = bh * OW`` (``bh`` whole output rows, ``bh | OH``) and the
  K-tile is ``kb = cb * kh * kw`` (``cb`` whole input channels, ``cb | C``).
  Tiles therefore never straddle an image, an output row, or a channel's
  taps, so no M/K padding exists and scaling groups are exactly whole
  channels' taps — the conv analogue of the paper's (n, c) grouping.
* The activation arrives spatially pre-padded in a channel-blocked
  channels-last layout ``(N, C/cb, Hp, Wp, cb)`` as whole-image blocks
  whose index map depends only on the image index ``i // (OH/bh)``:
  consecutive grid steps (all j, k, and same-image row tiles) keep the same
  block index, so Pallas fetches each image from HBM **once** — the
  "activations read once" property the ROADMAP asks for.
* Inside the kernel, a program decodes its ``(i, k)`` grid coordinates into
  an ``(n, channel-block, h-band)`` window and reads each of the ``kh*kw``
  taps as one strided ``(bh, OW, cb)`` load of that band — a ``(bm, cb)``
  GEMM operand with rows in patch order and channels on the lanes.  The
  ``(bm, kb)`` tile of the virtual GEMM is the concatenation of those taps,
  so the tile's dot is the sum of one 2-D dot per tap.
* The quantize prologue then runs paper Alg. 2 **in VMEM** on the taps —
  the ``"nc"`` group maximum of a row is the maximum over its taps, the
  element codes are elementwise — reusing the exact helpers of
  :mod:`repro.kernels.mls_quantize`, so codes and scales are bit-identical
  to the im2col pipeline with ``k_block = kb``.  Neither fp32 patches nor
  intermediate codes ever round-trip through HBM.
* The epilogue is :mod:`repro.kernels.mls_matmul`'s: decode to integer
  fractions, MXU dots (exact fp32 integer MACs, < 2^24; summing the per-tap
  dots of one group stays exact), inter-group scale ``s_g^x * s_g^w``, and
  a final ``s_t^x * s_t^w * unit`` on the output tile.

Legality: the layout requires ``k_block = cb * kh * kw`` with ``cb | C``
(:func:`implicit_compatible`).  Incompatible configs keep the im2col path —
impl selection never changes quantization semantics.  Only the tensor/group
scales (cheap XLA reductions over the padded activation, no patch
materialization) and the optional stochastic-rounding draws are computed
outside the kernel.

Stochastic rounding uses the same u8 source as the im2col path, drawn over
the un-padded virtual GEMM shape ``(M0, K0)``; draws agree bit-for-bit with
the im2col/ref pipeline whenever that pipeline's tiles divide (M0, K0) —
the bit-exactness tests pin blocks accordingly.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import EMFormat, GS_FMT_DEFAULT
from repro.core.quantize import quantize_group_scale
from .mls_matmul import _decode_frac, fraction_dot, scale_operand
from .mls_quantize import (
    GROUPINGS,
    SMEM_SPEC,
    _element_codes,
    rounding_offset,
)
from .runtime import resolve_interpret

__all__ = [
    "CONV_IMPL_ENV_VAR",
    "CONV_IMPLS",
    "ConvGeom",
    "conv_geometry",
    "default_conv_blocks",
    "elementwise_codes",
    "im2col_conv_bytes",
    "implicit_compatible",
    "implicit_conv_bytes",
    "implicit_conv_forward",
    "patches_u8",
    "resolve_conv_blocks",
    "resolve_conv_impl",
]

CONV_IMPL_ENV_VAR = "REPRO_CONV_IMPL"
CONV_IMPLS = ("auto", "im2col", "implicit")

# Soft cap on the M-tile: bh is the largest divisor of OH with bh*OW under
# this (one full output row minimum), mirroring the GEMM default tiles.
_DEFAULT_BM_CAP = 256
_DEFAULT_BLOCK_N = 128


# ---------------------------------------------------------------------------
# Geometry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ConvGeom:
    """Static NCHW conv geometry with normalized explicit padding."""

    n: int
    c: int
    h: int
    w: int
    o: int
    kh: int
    kw: int
    sh: int
    sw: int
    ph_lo: int
    ph_hi: int
    pw_lo: int
    pw_hi: int

    @property
    def hp(self) -> int:
        return self.h + self.ph_lo + self.ph_hi

    @property
    def wp(self) -> int:
        return self.w + self.pw_lo + self.pw_hi

    @property
    def oh(self) -> int:
        return (self.hp - self.kh) // self.sh + 1

    @property
    def ow(self) -> int:
        return (self.wp - self.kw) // self.sw + 1

    @property
    def kk(self) -> int:
        return self.kh * self.kw

    @property
    def m0(self) -> int:
        return self.n * self.oh * self.ow

    @property
    def k0(self) -> int:
        return self.c * self.kk

    def as_dims(self) -> tuple[int, ...]:
        """13-int canonical tuple (the conv TuneSpec shape, sans k_block)."""
        return (
            self.n, self.c, self.h, self.w, self.o, self.kh, self.kw,
            self.sh, self.sw, self.ph_lo, self.ph_hi, self.pw_lo, self.pw_hi,
        )


def conv_geometry(x_shape, w_shape, stride, padding) -> ConvGeom:
    """Normalize ``(x, w, stride, padding)`` into a :class:`ConvGeom`.

    ``padding`` accepts "SAME"/"VALID" or explicit ``[(lo, hi), (lo, hi)]``
    pairs — resolved with the same ``lax.padtype_to_pads`` rule the conv
    lowering uses, so geometry here matches ``conv_general_dilated_patches``
    exactly.
    """
    n, c, h, w = (int(d) for d in x_shape)
    o, c2, kh, kw = (int(d) for d in w_shape)
    assert c == c2, (x_shape, w_shape)
    sh, sw = (int(s) for s in stride)
    if isinstance(padding, str):
        pads = jax.lax.padtype_to_pads((h, w), (kh, kw), (sh, sw), padding)
    else:
        pads = padding
    # padtype_to_pads yields np.int64; pallas treats non-int grid dims as
    # *dynamic* grid bounds, so everything must be a Python int
    (ph_lo, ph_hi), (pw_lo, pw_hi) = [
        (int(lo), int(hi)) for lo, hi in pads]
    return ConvGeom(n, c, h, w, o, kh, kw, sh, sw, ph_lo, ph_hi, pw_lo, pw_hi)


def implicit_compatible(geom: ConvGeom, k_block: int) -> tuple[bool, str]:
    """Can the implicit layout realize ``k_block``-wide scaling groups?

    Groups must be whole channels' taps: ``k_block = cb * kh * kw`` with
    ``cb | C``.  Returns ``(ok, reason)`` — the reason names the nearest
    legal k_block when not.
    """
    kk = geom.kk
    if geom.oh < 1 or geom.ow < 1:
        return False, "empty output window"
    if k_block % kk:
        legal = _nearest_conv_k_block(geom, k_block)
        return False, (
            f"k_block={k_block} is not a multiple of kh*kw={kk} "
            f"(nearest legal: {legal})"
        )
    cb = k_block // kk
    if cb < 1 or geom.c % cb:
        legal = _nearest_conv_k_block(geom, k_block)
        return False, (
            f"k_block={k_block} needs cb={cb} whole channels per group but "
            f"cb does not divide C={geom.c} (nearest legal: {legal})"
        )
    return True, ""


def _nearest_conv_k_block(geom: ConvGeom, k_block: int) -> int:
    """Largest legal conv k_block (= cb*kh*kw, cb | C) not above k_block."""
    best = geom.kk
    for cb in range(1, geom.c + 1):
        if geom.c % cb == 0 and cb * geom.kk <= max(k_block, geom.kk):
            best = cb * geom.kk
    return best


def default_conv_blocks(geom: ConvGeom) -> tuple[int, int]:
    """Proven-legal default ``(bh, block_n)``: the largest divisor of OH
    whose M-tile ``bh*OW`` stays under the default cap, and the GEMM's
    default N-tile."""
    bh = 1
    for cand in range(1, geom.oh + 1):
        if geom.oh % cand == 0 and cand * geom.ow <= _DEFAULT_BM_CAP:
            bh = cand
    return bh, min(_DEFAULT_BLOCK_N, max(geom.o, 1))


# ---------------------------------------------------------------------------
# Impl/block resolution: explicit > env > tuned cache > legality default
# ---------------------------------------------------------------------------
def conv_tune_dims(geom: ConvGeom, k_block: int) -> tuple[int, ...]:
    """Conv TuneSpec shape: geometry + k_block (k_block is numerics-bearing
    for convs — the grouping width — so it keys the cache entry)."""
    return (*geom.as_dims(), k_block)


def _cached_conv_config(geom: ConvGeom, fmt, grouping: str, k_block: int):
    from .autotune import TuneSpec, get_cache  # lazy: avoids an import cycle

    spec = TuneSpec("conv", conv_tune_dims(geom, k_block), fmt,
                    k_block=k_block, grouping=grouping)
    return get_cache().get(spec.key())


def resolve_conv_impl(geom: ConvGeom, cfg) -> str:
    """Pick ``"im2col"`` or ``"implicit"`` for this conv.

    Precedence: ``REPRO_CONV_IMPL`` env (A/B runs) > ``cfg.conv_impl`` >
    tuned-cache winner > implicit-when-legal default.  An explicit
    ``"implicit"`` request on an incompatible ``k_block`` raises — impl
    selection never silently changes the scaling-group semantics.
    """
    env = os.environ.get(CONV_IMPL_ENV_VAR, "").strip().lower()
    if env and env not in CONV_IMPLS:
        raise ValueError(
            f"{CONV_IMPL_ENV_VAR}={env!r}: expected one of {CONV_IMPLS}")
    choice = env or getattr(cfg, "conv_impl", "auto")
    ok, reason = implicit_compatible(geom, cfg.k_block)
    if choice == "im2col":
        return "im2col"
    if choice == "implicit":
        if not ok:
            raise ValueError(
                f"conv_impl='implicit' is not legal for this conv: {reason}")
        return "implicit"
    # "auto"
    if not ok:
        return "im2col"
    cached = _cached_conv_config(geom, cfg.fmt, cfg.grouping, cfg.k_block)
    if cached is not None and getattr(cached, "impl", ""):
        return cached.impl
    return "implicit"


def resolve_conv_blocks(
    geom: ConvGeom, cfg, *, block_m: int | None = None,
    block_n: int | None = None,
) -> tuple[int, int]:
    """Resolve the implicit kernel's ``(bh, block_n)``.

    ``cfg.block_m`` (if set) is the M-tile in GEMM rows and must be a
    ``bh * OW`` multiple of whole output rows; ``cfg.block_n`` is the
    output-channel tile.  Unset fields resolve through the tuned cache
    (``BlockConfig.block_m`` stores ``bh`` for conv entries), then the
    legality default.
    """
    block_m = cfg.block_m if block_m is None else block_m
    block_n = cfg.block_n if block_n is None else block_n
    bh_default, bn_default = default_conv_blocks(geom)
    bh = bn = None
    if block_m is not None:
        if block_m % geom.ow or geom.oh % (block_m // geom.ow):
            raise ValueError(
                f"implicit conv block_m={block_m} must be bh*OW with bh "
                f"dividing OH (OW={geom.ow}, OH={geom.oh})")
        bh = block_m // geom.ow
    if block_n is not None:
        bn = block_n
    if bh is None or bn is None:
        cached = _cached_conv_config(geom, cfg.fmt, cfg.grouping, cfg.k_block)
        if cached is not None and getattr(cached, "impl", "") == "implicit":
            if bh is None and geom.oh % max(cached.block_m, 1) == 0:
                bh = cached.block_m
            if bn is None:
                bn = cached.block_n
    return bh if bh is not None else bh_default, \
        bn if bn is not None else bn_default


# ---------------------------------------------------------------------------
# Scale precompute (exact, window-based — no patch materialization)
# ---------------------------------------------------------------------------
def _covered_abs_max(xp: jax.Array, geom: ConvGeom) -> jax.Array:
    """Per-(n, c, patch) abs-max over each conv window — (N, C, OH, OW).

    Only pixels some patch actually covers contribute (VALID/stride can
    leave a tail uncovered), matching ``max|im2col(x)|`` exactly.
    """
    return jax.lax.reduce_window(
        jnp.abs(xp), -jnp.inf, jax.lax.max,
        (1, 1, geom.kh, geom.kw), (1, 1, geom.sh, geom.sw), "VALID",
    )


def _tap_abs_max(xp: jax.Array, geom: ConvGeom) -> jax.Array:
    """Per-feature abs-max over all patches — (C*kh*kw,) in (c, kh, kw)
    order, i.e. ``max|im2col(x)|`` along the patch axis."""
    a = jnp.abs(xp)
    cols = []
    for kh_ in range(geom.kh):
        for kw_ in range(geom.kw):
            sl = a[
                :, :,
                kh_: kh_ + 1 + geom.sh * (geom.oh - 1): geom.sh,
                kw_: kw_ + 1 + geom.sw * (geom.ow - 1): geom.sw,
            ]
            cols.append(sl.max(axis=(0, 2, 3)))  # (C,)
    return jnp.stack(cols, axis=1).reshape(-1)  # (C, KK) -> (C*KK,)


def _implicit_x_scales(xp, geom: ConvGeom, fmt, gs_fmt, kb, grouping):
    """(s_t, compact s_g | None) for the activation, bit-identical to the
    im2col pipeline's (``quantize_ref`` / ``mls_quantize_pallas``) scales.

    ``"nc"`` group scales are computed inside the kernel (groups live in one
    tile); ``"n"``/``"c"`` cross k-tiles / row-tiles in the implicit layout
    so their compact scales are precomputed here with the exact
    ``quantize_group_scale`` math the reference uses.
    """
    n_kb = geom.k0 // kb
    if grouping in ("c", "none"):
        feat = _tap_abs_max(xp, geom)  # (K0,)
        s_t = jnp.max(feat)
    else:
        win = _covered_abs_max(xp, geom)  # (N, C, OH, OW)
        s_t = jnp.max(win)
    s_t = jnp.where(s_t > 0, s_t, 1.0)
    if grouping == "nc":
        return s_t, None
    if grouping == "n":
        s_r = win.max(axis=1).reshape(geom.m0, 1)  # per-row (patch) max
        s_g, _, _ = quantize_group_scale(s_r / s_t, gs_fmt)
        return s_t, s_g  # (M0, 1)
    if grouping == "c":
        s_r = feat.reshape(n_kb, kb).max(axis=1)[None, :]  # (1, n_kb)
        s_g, _, _ = quantize_group_scale(s_r / s_t, gs_fmt)
        return s_t, s_g
    return s_t, jnp.ones((1, 1), jnp.float32)  # "none"


# ---------------------------------------------------------------------------
# The fused kernel
# ---------------------------------------------------------------------------
def _tap_loads(x_ref, geom: ConvGeom, bh: int):
    """The ``kh*kw`` taps of this program's window as ``(bm, cb)`` operands.

    Decodes the (i, k) grid coordinates into an (image, channel block,
    output-row band) window and reads each tap with one strided load of the
    whole-image block — the "index map" of the implicit GEMM, executed on
    VMEM-resident data.  Tap ``(kh_, kw_)`` of output pixel ``(r, c)`` is
    padded-input pixel ``(row0 + kh_ + sh*r, kw_ + sw*c)``; the halo band is
    rows ``[row0, row0 + sh*(bh-1) + kh)``.
    """
    i = pl.program_id(0)
    k = pl.program_id(2)
    row0 = (i % (geom.oh // bh)) * bh * geom.sh
    cb = x_ref.shape[-1]
    taps = []
    for kh_ in range(geom.kh):
        for kw_ in range(geom.kw):
            a = x_ref[
                0, k,
                pl.ds(row0 + kh_, bh, stride=geom.sh),
                pl.ds(kw_, geom.ow, stride=geom.sw),
                :,
            ]  # (bh, OW, cb): rows in (oh_local, ow) patch order
            taps.append(a.reshape(bh * geom.ow, cb))
    return taps


def _implicit_kernel(
    *refs, geom: ConvGeom, fmt: EMFormat, gs_fmt: EMFormat, grouping: str,
    stochastic: bool, emit: bool, bh: int, n_k: int, x_scale, w_scale,
):
    it = iter(refs)
    x_ref = next(it)
    r_ref = next(it) if stochastic else None
    stx_ref = next(it)
    stp_ref = next(it)
    xsg_ref = None if grouping == "nc" else next(it)
    wc_ref = next(it)
    wsg_ref = next(it)
    out_ref = next(it)
    codes_ref = next(it) if emit else None
    sgo_ref = next(it) if (emit and grouping == "nc") else None
    acc_ref = next(it)

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    taps = _tap_loads(x_ref, geom, bh)
    s_t = stx_ref[0, 0]

    # ---- quantize prologue: paper Alg. 2 on the VMEM taps ----------------
    if grouping == "nc":  # one group per row: the max over all its taps
        s_r = functools.reduce(jnp.maximum, [
            jnp.max(jnp.abs(a), axis=1, keepdims=True) for a in taps])
        xs, _, _ = quantize_group_scale(s_r / s_t, gs_fmt)  # (bm, 1)
    else:  # (bm, 1) for "n", a scalar for "c"/"none"
        xs = x_scale(xsg_ref, k, 0)
    denom = s_t * xs

    # ---- GEMM body: mls_matmul's, one dot per tap -------------------------
    p = None
    for t, a in enumerate(taps):
        r = rounding_offset(r_ref[0, t]) if stochastic else None
        codes = _element_codes(a, r, denom, fmt)
        if emit:
            codes_ref[0, t] = codes
        pt = fraction_dot(_decode_frac(codes, fmt),
                          _decode_frac(wc_ref[0, t], fmt), fmt)
        p = pt if p is None else p + pt
    acc_ref[...] += p * (xs * w_scale(wsg_ref, k, 0))

    @pl.when(k == n_k - 1)
    def _done():
        unit = 2.0 ** (2 * (fmt.e_min - fmt.m))
        out_ref[...] = acc_ref[...] * (stp_ref[0, 0] * unit)

    if sgo_ref is not None:
        sgo_ref[0] = xs


def implicit_conv_forward(
    x: jax.Array,
    w: jax.Array,
    key_x: jax.Array | None,
    key_w: jax.Array | None,
    stride,
    padding,
    *,
    fmt: EMFormat,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    k_block: int,
    bh: int | None = None,
    block_n: int | None = None,
    grouping: str = "nc",
    interpret: bool | None = None,
    emit_codes: bool = False,
):
    """Fused implicit-GEMM forward conv: fp32 NCHW in, fp32 NCHW out.

    ``x`` (N, C, H, W), ``w`` (O, C, kh, kw).  ``k_block`` must satisfy
    :func:`implicit_compatible`.  With ``emit_codes=True`` also returns
    ``(codes (M0, K0), x_sg compact, s_t)`` — the activation's quantized
    form in im2col layout, for bit-exactness tests against the reference
    pipeline (the codes round-trip through HBM only in this debug mode).
    """
    if grouping not in GROUPINGS:
        raise ValueError(
            f"unknown grouping {grouping!r}; expected one of {GROUPINGS}")
    geom = conv_geometry(x.shape, w.shape, stride, padding)
    ok, reason = implicit_compatible(geom, k_block)
    if not ok:
        raise ValueError(f"implicit_conv_forward: {reason}")
    cb = k_block // geom.kk
    kb = k_block
    n_k = geom.k0 // kb
    if bh is None or block_n is None:
        bh_d, bn_d = default_conv_blocks(geom)
        bh = bh_d if bh is None else bh
        block_n = bn_d if block_n is None else block_n
    if geom.oh % bh:
        raise ValueError(
            f"implicit conv bh={bh} must divide OH={geom.oh}")
    bm = bh * geom.ow
    interpret = resolve_interpret(interpret)

    xp = jnp.pad(
        x.astype(jnp.float32),
        ((0, 0), (0, 0), (geom.ph_lo, geom.ph_hi), (geom.pw_lo, geom.pw_hi)),
    )
    s_t, x_sg = _implicit_x_scales(xp, geom, fmt, gs_fmt, kb, grouping)
    stx = s_t.reshape(1, 1)

    # Weight side: byte-for-byte the im2col pipeline's (see qd_gemm) — the
    # OIHW weight flattens to (K0, O), is padded to the N-tile, and is
    # quantized transposed so its groups run along the contraction.
    from .mls_quantize import mls_quantize_pallas  # local: keep import light

    # O pads to the *unclamped* block_n multiple — exactly qd_gemm's
    # _pad_to — so the weight-side stochastic draws are shape-identical to
    # the im2col/ref pipeline; the kernel's N-tile clamps separately below.
    wmat = w.reshape(geom.o, -1).T.astype(jnp.float32)  # (K0, O)
    pn = (-geom.o) % block_n
    wp = jnp.pad(wmat, ((0, 0), (0, pn))) if pn else wmat
    op = geom.o + pn
    bn = min(block_n, op)
    wc, wsgT, wst = mls_quantize_pallas(
        wp.T, fmt, kb, gs_fmt, key_w, block_m=block_n, interpret=interpret,
        grouping=grouping,
    )
    wsg = wsgT.T
    stp = (s_t * wst).astype(jnp.float32).reshape(1, 1)

    stochastic = key_x is not None
    oh_tiles = geom.oh // bh
    kk = geom.kk
    grid = (geom.m0 // bm, op // bn, n_k)

    # Channel-blocked channels-last activation: (N, C/cb, Hp, Wp, cb).
    x5 = xp.reshape(geom.n, n_k, cb, geom.hp, geom.wp).transpose(0, 1, 3, 4, 2)
    in_specs = [
        pl.BlockSpec((1, n_k, geom.hp, geom.wp, cb),
                     lambda i, j, k, t=oh_tiles: (i // t, 0, 0, 0, 0)),
    ]
    operands = [x5]
    # Per-tap operand layout of a (rows, K0) array whose K0 is in
    # (c, kh, kw) order: (n_k, kh*kw, rows, cb).
    def per_tap(a):
        return a.reshape(a.shape[0], n_k, cb, kk).transpose(1, 3, 0, 2)

    if stochastic:
        r_u8 = jax.random.randint(
            key_x, (geom.m0, geom.k0), 0, 256, dtype=jnp.int32
        ).astype(jnp.uint8)
        in_specs.append(
            pl.BlockSpec((1, kk, bm, cb), lambda i, j, k: (k, 0, i, 0)))
        operands.append(per_tap(r_u8))
    in_specs += [SMEM_SPEC, SMEM_SPEC]  # stx, stp
    operands += [stx, stp]
    x_scale = None
    if grouping != "nc":
        x_sg, xsg_spec, x_scale = scale_operand(
            x_sg, "x", grouping, n_k, 1, bm, grid_axis=0)
        in_specs.append(xsg_spec)
        operands.append(x_sg)
    wsg, wsg_spec, w_scale = scale_operand(
        wsg, "w", grouping, n_k, 1, bn, grid_axis=1)
    in_specs += [
        pl.BlockSpec((1, kk, cb, bn), lambda i, j, k: (k, 0, 0, j)),
        wsg_spec,
    ]
    operands += [per_tap(wc).transpose(0, 1, 3, 2), wsg]  # (n_k, kk, cb, Op)

    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((geom.m0, op), jnp.float32)]
    if emit_codes:
        out_specs.append(
            pl.BlockSpec((1, kk, bm, cb), lambda i, j, k: (k, 0, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((n_k, kk, geom.m0, cb), jnp.uint8))
        if grouping == "nc":
            out_specs.append(
                pl.BlockSpec((1, bm, 1), lambda i, j, k: (k, i, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((n_k, geom.m0, 1), jnp.float32))

    kernel = functools.partial(
        _implicit_kernel, geom=geom, fmt=fmt, gs_fmt=gs_fmt,
        grouping=grouping, stochastic=stochastic, emit=emit_codes,
        bh=bh, n_k=n_k, x_scale=x_scale, w_scale=w_scale,
    )
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if emit_codes else out_specs[0],
        out_shape=out_shape if emit_codes else out_shape[0],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)

    y2d = res[0] if emit_codes else res
    y = y2d[:, : geom.o].reshape(geom.n, geom.oh, geom.ow, geom.o)
    y = y.transpose(0, 3, 1, 2)
    if not emit_codes:
        return y
    # back to the im2col layout: (n_k, kk, M0, cb) -> (M0, K0) in
    # (c, kh, kw) feature order
    codes = res[1].transpose(2, 0, 3, 1).reshape(geom.m0, geom.k0)
    if grouping == "nc":
        x_sg = res[2][:, :, 0].T  # (M0, n_k)
    return y, codes, x_sg, s_t


# ---------------------------------------------------------------------------
# Forward-code reuse for the weight-grad GEMM ("none" grouping)
# ---------------------------------------------------------------------------
def elementwise_codes(v: jax.Array, s_t: jax.Array, fmt: EMFormat):
    """Deterministic element codes against a tensor-wide scale (the
    ``grouping="none"`` quantizer, r = 127) — exactly the forward kernel's
    prologue, so gathering these *is* reusing the forward codes."""
    return _element_codes(v.astype(jnp.float32), None, s_t, fmt)


def patches_u8(xq: jax.Array, geom: ConvGeom) -> jax.Array:
    """im2col gather on uint8 codes — (N, C, Hp, Wp) -> (M0, K0) in the
    (c, kh, kw) feature order (``conv_general_dilated_patches`` only takes
    floats; codes stay 1 byte/element through this gather)."""
    taps = []
    for kh_ in range(geom.kh):
        for kw_ in range(geom.kw):
            taps.append(xq[
                :, :,
                kh_: kh_ + 1 + geom.sh * (geom.oh - 1): geom.sh,
                kw_: kw_ + 1 + geom.sw * (geom.ow - 1): geom.sw,
            ])  # (N, C, OH, OW)
    g = jnp.stack(taps, axis=2)  # (N, C, KK, OH, OW)
    return g.transpose(0, 3, 4, 1, 2).reshape(geom.m0, geom.k0)


def covered_tensor_scale(x: jax.Array, geom: ConvGeom) -> jax.Array:
    """The forward tensor scale s_t: abs-max over covered (padded) pixels."""
    xp = jnp.pad(
        x.astype(jnp.float32),
        ((0, 0), (0, 0), (geom.ph_lo, geom.ph_hi), (geom.pw_lo, geom.pw_hi)),
    )
    s_t = jnp.max(_covered_abs_max(xp, geom))
    return jnp.where(s_t > 0, s_t, 1.0), xp


# ---------------------------------------------------------------------------
# Bytes-moved estimators (the interpret-mode stand-in for HBM counters)
# ---------------------------------------------------------------------------
def _ceil_to(v: int, m: int) -> int:
    return v + (-v) % m


def _gemm_code_traffic(M: int, K: int, N: int, bm: int, bn: int) -> int:
    """u8 code bytes the tiled GEMM fetches: each operand block is re-read
    once per sweep of the other operand's independent grid axis (Pallas
    only dedups *consecutive* grid steps with an unchanged block index)."""
    return M * K * (N // bn) + K * N * (M // bm)


def im2col_conv_bytes(
    geom: ConvGeom, k_block: int, *, block_m: int = 128, block_n: int = 128,
    stochastic: bool = False,
) -> dict:
    """HBM bytes-moved model of the im2col forward path.

    Counts: reading x, materializing + re-reading the fp32 patch matrix,
    writing/reading both operands' codes, the stochastic draws, and the
    fp32 output.  Scales are a few hundred bytes and are ignored on both
    paths.
    """
    mp = _ceil_to(geom.m0, min(block_m, geom.m0))
    kp = _ceil_to(geom.k0, k_block)
    np_ = _ceil_to(geom.o, min(block_n, max(geom.o, 1)))
    bm = min(block_m, geom.m0)
    bn = min(block_n, max(geom.o, 1))
    x_bytes = 4 * geom.n * geom.c * geom.h * geom.w
    cols = 4 * mp * kp
    w_io = 4 * kp * np_ + kp * np_  # fp32 read + code write
    quant_x = cols + mp * kp  # fp32 re-read + code write
    r_bytes = (mp * kp + kp * np_) if stochastic else 0
    gemm = _gemm_code_traffic(mp, kp, np_, bm, bn)
    out = 4 * mp * np_
    total = x_bytes + cols + quant_x + w_io + r_bytes + gemm + out
    return {
        "total": total, "x_read": x_bytes, "im2col_materialize": cols,
        "quantize": quant_x + w_io, "stochastic_draws": r_bytes,
        "gemm_codes": gemm, "out": out,
    }


def implicit_conv_bytes(
    geom: ConvGeom, k_block: int, *, bh: int | None = None,
    block_n: int | None = None, grouping: str = "nc",
    stochastic: bool = False,
) -> dict:
    """HBM bytes-moved model of the fused implicit path.

    The activation is written once spatially padded, re-read once by the
    scale precompute, and fetched into VMEM **once per image** by the
    kernel (the full-image block's index map only changes with the image
    index).  No patch matrix, no activation-code round-trip.

    Left out: the relayouts the kernel's operand layouts cost.  The
    compiled v5e program writes the channel-blocked channels-last copy of
    the padded activation beside the padded one, in the same fusion (one
    more activation write), and, stochastic, re-lays the rounding bits out
    per tap (one more read and write of ``M0*K0`` bytes).  Arrays whose
    minor dim is ``cb`` or ``O`` < 128 are stored lane-padded to 128 on the
    chip, which is not modelled either.  The per-tap weight-code transpose
    is a few KiB, ignored like the scales (the im2col model ignores its
    own ``wc.T`` too).
    """
    bh_d, bn_d = default_conv_blocks(geom)
    bh = bh_d if bh is None else bh
    bn = min(bn_d if block_n is None else block_n, max(geom.o, 1))
    bm = bh * geom.ow
    np_ = _ceil_to(geom.o, bn)
    xp_bytes = 4 * geom.n * geom.c * geom.hp * geom.wp
    x_io = 4 * geom.n * geom.c * geom.h * geom.w + xp_bytes  # read + pad write
    scale_pre = xp_bytes  # one fused reduction pass
    kernel_x = xp_bytes  # fetched once per image
    w_io = 4 * geom.k0 * np_ + geom.k0 * np_
    w_codes = geom.k0 * np_ * (geom.m0 // bm)
    r_bytes = (geom.m0 * geom.k0 + geom.k0 * np_) if stochastic else 0
    out = 4 * geom.m0 * np_
    total = x_io + scale_pre + kernel_x + w_io + w_codes + r_bytes + out
    return {
        "total": total, "x_read": x_io, "scale_precompute": scale_pre,
        "kernel_x_fetch": kernel_x, "quantize": w_io,
        "stochastic_draws": r_bytes, "gemm_codes": w_codes, "out": out,
    }
