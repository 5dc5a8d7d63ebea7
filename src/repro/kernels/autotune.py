"""Shape-keyed Pallas kernel autotuner with a persistent tuning cache.

The paper's energy argument (Sec. VII) only materializes if the low-bit
GEMMs run at hardware speed, and no single static tiling does that across
shapes.  This module searches tiling candidates per *tuning key* —
``(kind, shape, <E,M> format, grouping)`` — and persists the winners:

* **Candidates** are :class:`BlockConfig` points — ``(block_m, block_n,
  k_block, grouping)`` for a GEMM, ``block_m`` for the quantizer,
  ``(impl, bh, block_n)`` for a conv — enumerated by
  :func:`gemm_candidates` / :func:`quantize_candidates` /
  :func:`conv_candidates`.
* **Pruning**: every candidate is first proven legal by the static verifier
  (:func:`repro.analysis.kernel_verify.verify_candidate`): grid coverage +
  the 2^24 integer-accumulation budget, from traced jaxpr metadata alone.
  Illegal tilings are never timed (and never cost a Mosaic compile).
* **Timing**: survivors run through the real fused pipeline
  (``lowbit_matmul_fused`` / ``mls_quantize_pallas``), best-of-n.
* **Persistence**: ``--tune`` writes winners to a JSON cache —
  ``$REPRO_TUNE_CACHE``, else ``.cache/kernel_tune.json``.  Resolution
  reads the committed seed cache (``kernels/tuned/kernel_tune.json``, kept
  fresh by CI with ``python -m repro.kernels.autotune --check``) plus an
  overlay only when ``REPRO_TUNE_CACHE`` names one, so an uncommitted local
  file never changes what a run executes.

Hot-path resolution (:func:`resolve_block_config`) never times or traces:
**explicit override > cache hit > proven-legal default**, where the default
is legal by construction (blocks are clamped and operands padded to block
multiples by the kernels; the accumulator budget is enforced at
``QuantConfig`` construction).

CLI::

    python -m repro.kernels.autotune --tune            # tune registry shapes
    python -m repro.kernels.autotune --check           # CI: seed cache fresh?
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
from collections.abc import Callable, Iterable

from repro.core.formats import EMFormat, accumulation_bits

__all__ = [
    "BlockConfig",
    "TuneSpec",
    "TuneCache",
    "CACHE_ENV_VAR",
    "CACHE_SCHEMA_VERSION",
    "DEFAULT_CACHE_PATH",
    "SEED_CACHE_PATH",
    "check_cache",
    "conv_candidates",
    "default_block_config",
    "gemm_candidates",
    "get_cache",
    "invalidate_cache",
    "quantize_candidates",
    "resolve_block_config",
    "time_config",
    "tune_all",
    "tune_spec",
]

CACHE_SCHEMA_VERSION = 1
CACHE_ENV_VAR = "REPRO_TUNE_CACHE"
DEFAULT_CACHE_PATH = pathlib.Path(".cache") / "kernel_tune.json"
SEED_CACHE_PATH = pathlib.Path(__file__).parent / "tuned" / "kernel_tune.json"

_MAX_ACC_BITS = 24  # fp32 integer-exactness budget (paper Sec. V-B)


# ---------------------------------------------------------------------------
# BlockConfig / TuneSpec
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One tiling point of the Pallas kernel layer.

    ``block_m`` / ``block_n`` tile the GEMM output (``block_m`` doubles as
    the quantizer's row block); ``k_block`` is the contraction tile ==
    scaling-group width; ``grouping`` the group-scale layout the kernel
    executes (``kernels.mls_matmul.sg_shapes``).  For ``"conv"`` specs,
    ``impl`` selects the lowering (``"im2col"`` | ``"implicit"``); on the
    implicit kernel ``block_m`` stores ``bh`` (output rows per M-tile, the
    M-tile being ``bh*OW``).  Empty ``impl`` means "not a conv entry".
    """

    block_m: int
    block_n: int
    k_block: int
    grouping: str = "nc"
    impl: str = ""

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        if not self.impl:
            d.pop("impl")  # keep pre-conv cache entries byte-stable
        return d

    @classmethod
    def from_json(cls, d: dict) -> BlockConfig:
        return cls(
            block_m=int(d["block_m"]), block_n=int(d["block_n"]),
            k_block=int(d["k_block"]), grouping=str(d["grouping"]),
            impl=str(d.get("impl", "")),
        )

    def replace(self, **kw) -> BlockConfig:
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class TuneSpec:
    """One tunable workload: a GEMM or a quantizer call at a fixed shape.

    ``kind`` is ``"gemm"`` (shape ``(M, K, N)``), ``"quantize"``
    (shape ``(M, K)``) or ``"conv"`` (shape = the 13 geometry dims of
    ``implicit_conv.ConvGeom.as_dims()`` followed by ``k_block``, so the
    cache key distinguishes group widths).  ``k_block`` is the *caller's*
    group width — the gemm/quantize search may try neighbours, but conv
    candidates keep it fixed because it *is* the numerics (``cb*kh*kw``).
    """

    kind: str
    shape: tuple[int, ...]
    fmt: EMFormat
    k_block: int = 128
    grouping: str = "nc"

    def __post_init__(self):
        if self.kind not in ("gemm", "quantize", "conv"):
            raise ValueError(f"unknown TuneSpec kind {self.kind!r}")
        want = {"gemm": 3, "quantize": 2, "conv": 14}[self.kind]
        if len(self.shape) != want:
            raise ValueError(
                f"{self.kind} TuneSpec needs a rank-{want} shape, "
                f"got {self.shape}")
        if self.kind == "conv" and self.shape[13] != self.k_block:
            raise ValueError(
                "conv TuneSpec shape[13] must equal k_block, got "
                f"{self.shape[13]} != {self.k_block}")

    def key(self) -> str:
        """The cache key: (kind, shape, format, grouping)."""
        dims = "x".join(str(int(d)) for d in self.shape)
        return f"{self.kind}:{dims}:e{self.fmt.e}m{self.fmt.m}:{self.grouping}"

    def to_json(self) -> dict:
        return {
            "kind": self.kind, "shape": list(self.shape),
            "fmt": [self.fmt.e, self.fmt.m], "k_block": self.k_block,
            "grouping": self.grouping,
        }

    @classmethod
    def from_json(cls, d: dict) -> TuneSpec:
        e, m = d["fmt"]
        return cls(
            kind=str(d["kind"]), shape=tuple(int(s) for s in d["shape"]),
            fmt=EMFormat(int(e), int(m)), k_block=int(d.get("k_block", 128)),
            grouping=str(d.get("grouping", "nc")),
        )


def tune_spec(
    kind: str, shape: Iterable[int], fmt: EMFormat,
    k_block: int = 128, grouping: str = "nc",
) -> TuneSpec:
    return TuneSpec(kind, tuple(int(s) for s in shape), fmt, k_block, grouping)


def cache_key(
    kind: str, shape: Iterable[int], fmt: EMFormat, grouping: str
) -> str:
    dims = "x".join(str(int(d)) for d in shape)
    return f"{kind}:{dims}:e{fmt.e}m{fmt.m}:{grouping}"


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------
class TuneCache:
    """JSON-backed map ``key -> (BlockConfig winner, timing metadata)``.

    Corrupted files and unknown schema versions degrade to an empty cache
    (recorded in ``load_warnings``) — resolution then falls back to the
    proven-legal defaults instead of crashing.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = pathlib.Path(path) if path is not None else None
        self.entries: dict[str, dict] = {}
        self.load_warnings: list[str] = []

    # -- I/O ---------------------------------------------------------------
    @classmethod
    def load(cls, path: str | os.PathLike) -> TuneCache:
        cache = cls(path)
        p = pathlib.Path(path)
        if not p.exists():
            return cache
        try:
            payload = json.loads(p.read_text())
        except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
            cache.load_warnings.append(f"{p}: unreadable tuning cache ({e})")
            return cache
        if not isinstance(payload, dict) or (
            payload.get("version") != CACHE_SCHEMA_VERSION
        ):
            cache.load_warnings.append(
                f"{p}: tuning-cache schema "
                f"{payload.get('version') if isinstance(payload, dict) else '?'}"
                f" != {CACHE_SCHEMA_VERSION}; ignoring stale cache"
            )
            return cache
        entries = payload.get("entries", {})
        if not isinstance(entries, dict):
            cache.load_warnings.append(f"{p}: malformed 'entries'; ignoring")
            return cache
        for key, ent in entries.items():
            try:
                BlockConfig.from_json(ent["config"])  # validate eagerly
                cache.entries[str(key)] = ent
            except (KeyError, TypeError, ValueError) as e:
                cache.load_warnings.append(
                    f"{p}: dropping malformed entry {key!r} ({e})")
        return cache

    def save(self, path: str | os.PathLike | None = None) -> pathlib.Path:
        p = pathlib.Path(path) if path is not None else self.path
        if p is None:
            raise ValueError("TuneCache.save: no path")
        p.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "version": CACHE_SCHEMA_VERSION,
            "generated_unix": round(time.time(), 1),
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        p.write_text(json.dumps(payload, indent=2) + "\n")
        return p

    # -- access ------------------------------------------------------------
    def get(self, key: str) -> BlockConfig | None:
        ent = self.entries.get(key)
        return BlockConfig.from_json(ent["config"]) if ent else None

    def put(
        self, spec: TuneSpec, config: BlockConfig, us: float,
        timed: int = 0, source: str = "autotune",
    ) -> None:
        self.entries[spec.key()] = {
            **spec.to_json(),
            "config": config.to_json(),
            "us": round(float(us), 2),
            "candidates_timed": int(timed),
            "source": source,
        }

    def merged_over(self, base: TuneCache) -> TuneCache:
        """This cache's entries overlaid on ``base`` (self wins)."""
        out = TuneCache(self.path)
        out.entries = {**base.entries, **self.entries}
        out.load_warnings = base.load_warnings + self.load_warnings
        return out

    def __len__(self) -> int:
        return len(self.entries)


_CACHE: TuneCache | None = None


def get_cache() -> TuneCache:
    """The process-wide resolution cache, loaded once: the committed seed
    cache, overlaid with the cache ``REPRO_TUNE_CACHE`` names when set."""
    global _CACHE
    if _CACHE is None:
        _CACHE = TuneCache.load(SEED_CACHE_PATH)
        overlay = os.environ.get(CACHE_ENV_VAR)
        if overlay:
            _CACHE = TuneCache.load(overlay).merged_over(_CACHE)
    return _CACHE


def invalidate_cache() -> None:
    """Drop the memoized resolution cache (tests / after re-tuning)."""
    global _CACHE
    _CACHE = None


# ---------------------------------------------------------------------------
# Defaults and candidate enumeration
# ---------------------------------------------------------------------------
def default_block_config(
    spec: TuneSpec | None = None, *, shape: tuple[int, ...] | None = None,
    fmt: EMFormat | None = None, k_block: int = 128, grouping: str = "nc",
) -> BlockConfig:
    """The static tiling the kernels shipped with: 128^2 output tiles at
    the caller's ``k_block``.  Legal by construction — the kernels clamp
    blocks to the array extent and pad ragged tails, and the accumulator
    budget for ``(fmt, k_block)`` is enforced where the config is built."""
    if spec is not None:
        k_block, grouping = spec.k_block, spec.grouping
        if spec.kind == "conv":
            # im2col at the shipped GEMM tiles: always legal, any k_block.
            return BlockConfig(128, 128, k_block, grouping, impl="im2col")
    return BlockConfig(128, 128, k_block, grouping)


def _legal_k_blocks(fmt: EMFormat, k_block: int) -> list[int]:
    """The caller's group width plus power-of-two neighbours that keep the
    integer accumulator inside the fp32-exactness budget."""
    cands = {k_block, k_block // 2, k_block * 2, 64, 128}
    return sorted(
        kb for kb in cands
        if kb >= 16 and kb <= 512 and (kb & (kb - 1)) == 0
        and accumulation_bits(fmt, kb) < _MAX_ACC_BITS
    )


def gemm_candidates(spec: TuneSpec) -> list[BlockConfig]:
    """Candidate tilings for a GEMM spec, static default included (so the
    tuned winner can never lose to the shipped tiling)."""
    M, _, N = spec.shape
    bms = sorted({b for b in (32, 64, 128, 256) if b <= max(M, 128)})
    bns = sorted({b for b in (32, 64, 128, 256) if b <= max(N, 128)})
    out = [default_block_config(spec)]
    for kb in _legal_k_blocks(spec.fmt, spec.k_block):
        for bm in bms:
            for bn in bns:
                c = BlockConfig(bm, bn, kb, spec.grouping)
                if c not in out:
                    out.append(c)
    return out


def quantize_candidates(spec: TuneSpec) -> list[BlockConfig]:
    """Candidate row blocks for the quantizer (block_n unused, kept at the
    default for a well-formed BlockConfig)."""
    M, _ = spec.shape
    bms = sorted({b for b in (64, 128, 256, 512) if b <= max(M, 128)})
    out = [BlockConfig(256, 128, spec.k_block, spec.grouping)]  # shipped
    for bm in bms:
        c = BlockConfig(bm, 128, spec.k_block, spec.grouping)
        if c not in out:
            out.append(c)
    return out


def conv_candidates(spec: TuneSpec) -> list[BlockConfig]:
    """Candidate conv lowerings: the im2col default plus implicit-GEMM
    tilings when the layout is legal for ``spec.k_block``.

    Unlike the GEMM search, ``k_block`` is held fixed — for convs it *is*
    the scaling-group width (``cb * kh * kw``), i.e. the numerics.  For
    implicit candidates ``block_m`` stores ``bh`` (output rows per M-tile).
    """
    from .implicit_conv import ConvGeom, implicit_compatible

    geom = ConvGeom(*spec.shape[:13])
    out = [default_block_config(spec)]
    ok, _ = implicit_compatible(geom, spec.k_block)
    if not ok:
        return out
    bhs = [b for b in range(1, geom.oh + 1)
           if geom.oh % b == 0 and b * geom.ow <= 512]
    bns = sorted({b for b in (32, 64, 128) if b <= max(geom.o, 32)})
    for bh in bhs[-4:]:  # largest few row-tiles; tiny bh just adds grid steps
        for bn in bns:
            c = BlockConfig(bh, bn, spec.k_block, spec.grouping,
                            impl="implicit")
            if c not in out:
                out.append(c)
    return out


def candidates_for(spec: TuneSpec) -> list[BlockConfig]:
    if spec.kind == "gemm":
        return gemm_candidates(spec)
    if spec.kind == "conv":
        return conv_candidates(spec)
    return quantize_candidates(spec)


# ---------------------------------------------------------------------------
# Legality oracle (static verifier) and timing
# ---------------------------------------------------------------------------
def verify_config(spec: TuneSpec, config: BlockConfig):
    """Statically prove one candidate (grid coverage + accumulator budget)
    without compiling or executing — the autotuner's pruning step.  Returns
    the verifier's ``KernelReport``."""
    from repro.analysis.kernel_verify import (
        verify_candidate, verify_quantize_candidate)

    if spec.kind == "gemm":
        M, K, N = spec.shape
        return verify_candidate(
            (M, K, N), (spec.fmt, config.k_block),
            (config.block_m, config.block_n), grouping=config.grouping,
        )
    if spec.kind == "conv":
        from repro.analysis.kernel_verify import verify_implicit_conv_candidate
        from .implicit_conv import ConvGeom

        geom = ConvGeom(*spec.shape[:13])
        if config.impl == "implicit":
            return verify_implicit_conv_candidate(
                geom, spec.fmt, config.k_block, config.block_m,
                config.block_n, grouping=config.grouping,
            )
        # im2col lowers to the virtual GEMM — prove that.
        return verify_candidate(
            (geom.m0, geom.k0, geom.o), (spec.fmt, config.k_block),
            (config.block_m, config.block_n), grouping=config.grouping,
        )
    M, K = spec.shape
    return verify_quantize_candidate(
        (M, K), spec.fmt, config.k_block, config.block_m,
        grouping=config.grouping,
    )


def time_config(spec: TuneSpec, config: BlockConfig, n: int = 3) -> float:
    """Best-of-n wall time (us) of the fused pipeline at one tiling."""
    import jax
    import jax.numpy as jnp

    if spec.kind == "gemm":
        from .ops import lowbit_matmul_fused

        M, K, N = spec.shape
        x = jax.random.normal(jax.random.key(0), (M, K), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (K, N), jnp.float32) * 0.1

        def fn():
            return lowbit_matmul_fused(
                x, w, None, fmt=spec.fmt, k_block=config.k_block,
                block_m=config.block_m, block_n=config.block_n,
                grouping=config.grouping,
            )
    elif spec.kind == "conv":
        from repro.core.lowbit import QuantConfig
        from .implicit_conv import ConvGeom
        from .lowbit_conv import lowbit_conv_fused

        geom = ConvGeom(*spec.shape[:13])
        x = jax.random.normal(
            jax.random.key(0), (geom.n, geom.c, geom.h, geom.w), jnp.float32)
        w = jax.random.normal(
            jax.random.key(1), (geom.o, geom.c, geom.kh, geom.kw),
            jnp.float32) * 0.1
        implicit = config.impl == "implicit"
        cfg = QuantConfig(
            fmt=spec.fmt, k_block=config.k_block, grouping=config.grouping,
            stochastic=False, backend="pallas",
            conv_impl="implicit" if implicit else "im2col",
            # conv BlockConfigs store bh in block_m; the QuantConfig wants
            # the M-tile in GEMM rows (bh * OW) on the implicit path.
            block_m=config.block_m * geom.ow if implicit else config.block_m,
            block_n=config.block_n,
        )
        stride = (geom.sh, geom.sw)
        padding = [(geom.ph_lo, geom.ph_hi), (geom.pw_lo, geom.pw_hi)]

        f = jax.jit(lambda a, b: lowbit_conv_fused(
            a, b, None, stride=stride, padding=padding, cfg=cfg))

        def fn():
            return f(x, w)
    else:
        from .mls_quantize import mls_quantize_pallas

        M, K = spec.shape
        x = jax.random.normal(jax.random.key(0), (M, K), jnp.float32)

        # the operand must be a real jit argument, not a closure constant —
        # XLA would constant-fold the whole quantization otherwise
        f = jax.jit(lambda a: mls_quantize_pallas(
            a, spec.fmt, config.k_block, block_m=config.block_m,
            grouping=config.grouping,
        ))

        def fn():
            return f(x)

    jax.block_until_ready(fn())  # compile
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# Tuning
# ---------------------------------------------------------------------------
def tune(
    spec: TuneSpec,
    cache: TuneCache,
    timer: Callable[[TuneSpec, BlockConfig], float] | None = None,
    force: bool = False,
    log: Callable[[str], None] | None = None,
) -> BlockConfig:
    """Tune one spec: cache hit short-circuits (no timing), otherwise
    enumerate -> prune with the static verifier -> time survivors -> persist
    the winner.  ``timer`` is injectable for tests."""
    key = spec.key()
    if not force:
        hit = cache.get(key)
        if hit is not None:
            return hit
    timer = timer or time_config
    say = log or (lambda _m: None)
    timed = 0
    best: tuple[float, BlockConfig] | None = None
    for config in candidates_for(spec):
        report = verify_config(spec, config)
        if not report.ok:
            say(f"  pruned {config} ({report.violations[0].kind})")
            continue
        us = timer(spec, config)
        timed += 1
        say(f"  {config}: {us:.1f} us")
        if best is None or us < best[0]:
            best = (us, config)
    if best is None:  # cannot happen: the static default always proves
        raise RuntimeError(f"no legal candidate for {key}")
    cache.put(spec, best[1], best[0], timed=timed)
    return best[1]


def registry_specs() -> list[TuneSpec]:
    """The tuning specs declared by ``KERNEL_REGISTRY`` entries."""
    from repro.kernels.registry import KERNEL_REGISTRY

    return [e.tune for e in KERNEL_REGISTRY.values() if e.tune is not None]


def tune_all(
    cache: TuneCache,
    specs: Iterable[TuneSpec] | None = None,
    timer: Callable[[TuneSpec, BlockConfig], float] | None = None,
    force: bool = False,
    log: Callable[[str], None] | None = None,
) -> dict[str, BlockConfig]:
    say = log or (lambda _m: None)
    out = {}
    for spec in specs if specs is not None else registry_specs():
        say(f"tuning {spec.key()}")
        out[spec.key()] = tune(spec, cache, timer=timer, force=force, log=log)
    return out


# ---------------------------------------------------------------------------
# Staleness check (CI --check mode; also the audit's cache gate)
# ---------------------------------------------------------------------------
def check_cache(
    cache: TuneCache, specs: Iterable[TuneSpec] | None = None,
) -> dict:
    """Prove the cache is fresh: every registry spec has an entry, and
    every cached winner still passes the static verifier.  Returns a
    report dict with ``ok`` and per-problem ``failures``."""
    failures: list[str] = []
    specs = list(specs) if specs is not None else registry_specs()
    for spec in specs:
        if cache.get(spec.key()) is None:
            failures.append(
                f"registry shape {spec.key()} has no tuning-cache entry "
                f"(run: python -m repro.kernels.autotune --tune)"
            )
    checked = 0
    for key, ent in sorted(cache.entries.items()):
        try:
            spec = TuneSpec.from_json(ent)
            config = BlockConfig.from_json(ent["config"])
        except (KeyError, TypeError, ValueError) as e:
            failures.append(f"cache entry {key}: malformed ({e})")
            continue
        report = verify_config(spec, config)
        checked += 1
        if not report.ok:
            v = report.violations[0]
            failures.append(
                f"cache entry {key}: winner {config} no longer verifies "
                f"({v.kind} at {v.where}: {v.detail})"
            )
    return {
        "ok": not failures,
        "entries": len(cache),
        "verified": checked,
        "required_specs": [s.key() for s in specs],
        "load_warnings": cache.load_warnings,
        "failures": failures,
    }


# ---------------------------------------------------------------------------
# Hot-path resolution: explicit override > cache hit > proven-legal default
# ---------------------------------------------------------------------------
def resolve_block_config(
    kind: str,
    shape: tuple[int, ...],
    fmt: EMFormat,
    grouping: str = "nc",
    *,
    k_block: int | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    cache: TuneCache | None = None,
) -> BlockConfig:
    """Resolve the tiling for one kernel call — pure lookup, never times.

    Field-level precedence: an explicit non-``None`` ``k_block`` /
    ``block_m`` / ``block_n`` overrides the cached winner, which overrides
    the static default.  ``k_block`` in particular is *numerics* (the
    scaling-group width), so callers that pin it keep their quantization
    semantics even when the cache's winner searched a different width.
    """
    cache = cache if cache is not None else get_cache()
    config = cache.get(cache_key(kind, shape, fmt, grouping))
    if config is None:
        config = default_block_config(
            shape=shape, fmt=fmt,
            k_block=k_block if k_block is not None else 128,
            grouping=grouping,
        )
    over = {}
    if k_block is not None and k_block != config.k_block:
        over["k_block"] = k_block
    if block_m is not None:
        over["block_m"] = block_m
    if block_n is not None:
        over["block_n"] = block_n
    return config.replace(**over) if over else config


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.kernels.autotune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--tune", action="store_true",
                    help="search + time the registry shapes, persist winners")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: every registry shape cached and every "
                         "cached winner still proves legal; exit 1 otherwise")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help="cache file (default: committed seed for --check; "
                         f"$%s or %s for --tune)" % (
                             CACHE_ENV_VAR, DEFAULT_CACHE_PATH))
    ap.add_argument("--force", action="store_true",
                    help="re-time even on a cache hit")
    args = ap.parse_args(argv)
    if not (args.tune or args.check):
        ap.error("pick --tune and/or --check")

    rc = 0
    if args.tune:
        path = args.cache or os.environ.get(CACHE_ENV_VAR, DEFAULT_CACHE_PATH)
        cache = TuneCache.load(path)
        for w in cache.load_warnings:
            print(f"warning: {w}", file=sys.stderr)
        winners = tune_all(cache, force=args.force, log=print)
        out = cache.save(path)
        print(f"tuned {len(winners)} shape(s) -> {out}")

    if args.check:
        path = args.cache or SEED_CACHE_PATH
        cache = TuneCache.load(path)
        report = check_cache(cache)
        for w in report["load_warnings"]:
            print(f"warning: {w}", file=sys.stderr)
        print(f"checked {report['verified']} cached winner(s) in {path}; "
              f"{len(report['required_specs'])} registry spec(s) required")
        if not report["ok"]:
            print("TUNING-CACHE CHECK FAILURES:", file=sys.stderr)
            for f in report["failures"]:
                print(f"  - {f}", file=sys.stderr)
            rc = 1
        else:
            print("tuning cache: OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
