"""Pallas kernel micro-benchmarks (interpret mode on CPU: correctness-grade
timings, structural not wall-clock-representative of TPU).

For every bench-flagged ``KERNEL_REGISTRY`` entry with a tuning spec, two
rows are emitted: the *static default* tiling and the *tuned* tiling
resolved from the persistent autotuner cache (``kernels/tuned/
kernel_tune.json`` seed + local overlay) — served from the cache without
re-timing the search.  Rows carry ``blocks``/``grouping``/``tuned`` fields
in the JSON artifact so the perf trail records which tiling produced each
number, plus a ``peak_hbm_bytes`` bytes-moved estimate (interpret mode has
no HBM counters; the estimators live in ``repro.kernels.implicit_conv``).
The im2col-vs-implicit conv comparison rows assert the implicit path moves
>= 3x fewer bytes on the ResNet-20 CIFAR conv shape.

Runs inside the ``benchmarks/run.py`` CSV driver, or standalone with a JSON
artifact for the CI perf trail::

    PYTHONPATH=src python benchmarks/kernel_bench.py --json BENCH_kernels.json
"""
import argparse
import time

import jax

try:
    from benchmarks._record import make_payload, write_json
except ImportError:  # run as a script: benchmarks/ itself is sys.path[0]
    from _record import make_payload, write_json

from repro.core import FMT_IMAGENET, QuantConfig, lowbit_conv, lowbit_matmul
from repro.kernels import (
    KERNEL_REGISTRY,
    conv_geometry,
    default_interpret,
    im2col_conv_bytes,
    implicit_conv_bytes,
    lowbit_conv_fused,
)
from repro.kernels.autotune import (
    default_block_config,
    get_cache,
    time_config,
)

def _mode() -> str:
    """How the Pallas rows of this process ran: the interpreter, or Mosaic
    on the named device."""
    if default_interpret():
        return "interpret-mode"
    return f"mosaic:{jax.devices()[0].device_kind}"


# ResNet-20 CIFAR's dominant conv shape — the acceptance target for the
# implicit path's traffic win (estimator, not wall clock: interpret mode
# has no HBM counters)
_RESNET20_CONV = ((8, 16, 32, 32), (16, 16, 3, 3))
_MIN_IMPLICIT_BYTES_RATIO = 3.0


def _gemm_bytes(M: int, K: int, N: int, bm: int = 128, bn: int = 128) -> int:
    """Bytes-moved model of the fused GEMM: fp32 operands in, u8 codes
    written + re-fetched per output-tile sweep, fp32 out."""
    bm, bn = min(bm, M), min(bn, N)
    return (4 * (M * K + K * N)          # fp32 operands read by quantizers
            + 2 * (M * K + K * N)        # codes written, then first fetch
            + (M * K * (N // bn - 1) + K * N * (M // bm - 1))  # re-fetches
            + 4 * M * N)                 # fp32 output


def _entry_bytes(entry, config=None) -> int | None:
    """``peak_hbm_bytes`` estimate for a registry row (None when the
    entry's traffic has no model — nothing currently lacks one)."""
    spec = entry.tune
    bm = config.block_m if config is not None else 128
    bn = config.block_n if config is not None else 128
    if entry.name == "lowbit_conv_fused":
        geom = conv_geometry((2, 16, 8, 8), (16, 16, 3, 3), (1, 1), "SAME")
        return im2col_conv_bytes(geom, 32)["total"]
    if entry.name == "lowbit_conv_implicit":
        geom = conv_geometry((2, 16, 8, 8), (16, 16, 3, 3), (1, 1), "SAME")
        if config is not None and config.impl == "im2col":
            return im2col_conv_bytes(geom, 36, block_m=bm,
                                     block_n=bn)["total"]
        bh = config.block_m if config is not None else None
        bn_ = config.block_n if config is not None else None
        return implicit_conv_bytes(geom, 36, bh=bh, block_n=bn_)["total"]
    if spec is not None and spec.kind == "gemm":
        M, K, N = spec.shape
        return _gemm_bytes(M, K, N, bm, bn)
    if spec is not None and spec.kind == "quantize":
        M, K = spec.shape
        return 4 * M * K + M * K  # fp32 in, u8 codes out
    return None


def _time(f, *args, n=5):
    """Best-of-n wall time in us (min is far more noise-robust than mean
    for micro-benchmarks: noise is one-sided)."""
    f(*args)  # compile
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _row(name, us, derived, config=None, tuned=None, cached=None,
         hbm_bytes=None):
    r = {"name": name, "derived": derived}
    if us is not None:
        r["us_per_call"] = round(us, 1)
    if config is not None:
        r["blocks"] = {
            "block_m": config.block_m, "block_n": config.block_n,
            "k_block": config.k_block,
        }
        if getattr(config, "impl", ""):
            r["blocks"]["impl"] = config.impl
        r["grouping"] = config.grouping
    if tuned is not None:
        r["tuned"] = tuned
    if cached is not None:
        r["cached"] = cached
    if hbm_bytes is not None:
        r["peak_hbm_bytes"] = int(hbm_bytes)
    return r


def _tuned_rows(entry, cache):
    """(default, tuned) row pair for one registry entry's tuning spec.

    The tuned tiling is *resolved* from the cache, never re-searched here;
    when the cached winner equals the static default, the default's
    measurement is reused (so tuned <= default holds by construction in
    the degenerate case)."""
    spec = entry.tune
    base = f"kernel/{entry.name}_{entry.bench_tag}"
    default_cfg = default_block_config(spec)
    winner = cache.get(spec.key())
    us_default = time_config(spec, default_cfg, n=5)
    if winner is None or winner == default_cfg:
        us_tuned, tuned_cfg = us_default, default_cfg
    else:
        us_tuned, tuned_cfg = time_config(spec, winner, n=5), winner
    return [
        _row(f"{base}_default", us_default, _mode(),
             config=default_cfg, tuned=False,
             hbm_bytes=_entry_bytes(entry, default_cfg)),
        _row(f"{base}_tuned", us_tuned, _mode(),
             config=tuned_cfg, tuned=True, cached=winner is not None,
             hbm_bytes=_entry_bytes(entry, tuned_cfg)),
    ]


def _conv_impl_rows(quick: bool):
    """im2col-vs-implicit comparison: timed on the quick registry shape,
    estimator-only on the ResNet-20 CIFAR shape (the acceptance target —
    asserted, so the perf trail cannot silently regress the traffic win)."""
    rows = []
    shapes = [("2x16x8x8_o16k3", (2, 16, 8, 8), (16, 16, 3, 3), True)]
    xs, ws = _RESNET20_CONV
    tag = f"resnet20_{'x'.join(str(d) for d in xs)}_o{ws[0]}k3"
    shapes.append((tag, xs, ws, not quick))
    for tag, xshape, wshape, timed in shapes:
        geom = conv_geometry(xshape, wshape, (1, 1), "SAME")
        est = {
            "im2col": im2col_conv_bytes(geom, 36)["total"],
            "implicit": implicit_conv_bytes(geom, 36)["total"],
        }
        ratio = est["im2col"] / est["implicit"]
        assert ratio >= _MIN_IMPLICIT_BYTES_RATIO, (
            f"implicit conv must move >= {_MIN_IMPLICIT_BYTES_RATIO}x fewer "
            f"HBM bytes than im2col on {tag}: got {ratio:.2f}x"
        )
        for impl in ("im2col", "implicit"):
            us = None
            if timed:
                cfg = QuantConfig(fmt=FMT_IMAGENET, stochastic=False,
                                  backend="pallas", k_block=36,
                                  conv_impl=impl)
                x = jax.random.normal(jax.random.key(4), xshape)
                w = jax.random.normal(jax.random.key(5), wshape) * 0.1
                us = _time(
                    jax.jit(lambda a, b, c=cfg: lowbit_conv_fused(
                        a, b, None, (1, 1), "SAME", c)),
                    x, w,
                )
            r = _row(f"kernel/conv_{impl}_{tag}", us,
                     _mode() if timed else "bytes-model only",
                     hbm_bytes=est[impl])
            r["im2col_over_implicit_bytes"] = round(ratio, 2)
            rows.append(r)
    return rows


def run(quick: bool = True):
    # Pallas entry points come from KERNEL_REGISTRY — the same set the
    # static verifier (analysis/kernel_verify.py) proves and the autotuner
    # tunes, so the perf trail, the legality gate and the tuning cache can
    # never drift apart.
    cache = get_cache()
    rows = []
    for entry in KERNEL_REGISTRY.values():
        if not entry.bench:
            continue
        fn, _ = entry.fn_and_args()
        args = entry.concrete_args()
        us = _time(jax.jit(fn), *args)
        rows.append(_row(f"kernel/{entry.name}_{entry.bench_tag}", us,
                         _mode(), hbm_bytes=_entry_bytes(entry)))
        if entry.tune is not None:
            rows += _tuned_rows(entry, cache)

    rows += _conv_impl_rows(quick)

    # hand-coded XLA reference rows (not Pallas kernels, so not registered)
    x = jax.random.normal(jax.random.key(0), (256, 512))
    w = jax.random.normal(jax.random.key(1), (512, 256)) * 0.05
    cfg = QuantConfig(fmt=FMT_IMAGENET, stochastic=False)
    fp32_io = 4 * (x.size + w.size + x.shape[0] * w.shape[1])
    us = _time(jax.jit(lambda a, b: lowbit_matmul(a, b, None, cfg)), x, w)
    rows.append(_row("kernel/lowbit_matmul_fakequant_jit", us,
                     "XLA-fused reference", hbm_bytes=fp32_io))
    us = _time(jax.jit(lambda a, b: a @ b), x, w)
    rows.append(_row("kernel/fp32_matmul_jit", us, "baseline",
                     hbm_bytes=fp32_io))

    # conv backends: fake-quant XLA reference (+ a bigger Pallas shape with
    # --full; the quick Pallas conv row is the registry's example shape)
    n, c, o, hw = (2, 16, 16, 8) if quick else (8, 32, 32, 16)
    xc = jax.random.normal(jax.random.key(2), (n, c, hw, hw))
    wc = jax.random.normal(jax.random.key(3), (o, c, 3, 3)) * 0.1
    tag = f"{n}x{c}x{hw}x{hw}_o{o}k3"
    if not quick:
        cfg_p = QuantConfig(fmt=FMT_IMAGENET, stochastic=False,
                            backend="pallas", k_block=32)
        us = _time(
            jax.jit(lambda a, b: lowbit_conv_fused(a, b, None, (1, 1), "SAME",
                                                   cfg_p)),
            xc, wc,
        )
        rows.append(_row(f"kernel/lowbit_conv_fused_{tag}", us, _mode()))
    us = _time(
        jax.jit(lambda a, b: lowbit_conv(a, b, None, (1, 1), "SAME", cfg)),
        xc, wc,
    )
    geom = conv_geometry(xc.shape, wc.shape, (1, 1), "SAME")
    rows.append(_row(
        f"kernel/lowbit_conv_fakequant_jit_{tag}", us,
        "XLA-fused reference",
        hbm_bytes=4 * (xc.size + wc.size + geom.m0 * geom.o)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="larger shapes (still interpret mode off-TPU)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows as a BENCH_*.json artifact")
    args = ap.parse_args()
    rows = run(quick=not args.full)
    for r in rows:
        us = r.get("us_per_call")
        print(f'{r["name"]},{"" if us is None else f"{us:.1f}"},'
              f'"{r["derived"]}"', flush=True)
    if args.json:
        write_json(args.json, make_payload("kernel_bench", rows,
                                           quick=not args.full))


if __name__ == "__main__":
    main()
