"""Registry of every shipped Pallas kernel entry point.

One table, three consumers:

* :mod:`repro.analysis.kernel_verify` traces each entry (forward and — for
  the training ops — the custom-VJP backward) and statically proves grid
  coverage and accumulator exactness for every ``pallas_call`` it finds;
* ``benchmarks/kernel_bench.py`` times the entries flagged ``bench`` on
  their example shapes, so the perf trail and the verifier agree on what
  "the shipped kernels" are;
* the shape-keyed autotuner (:mod:`repro.kernels.autotune`) tunes each
  entry's ``tune`` spec — the workload key ``(kind, shape, fmt,
  grouping)`` whose winner the persistent cache must hold (CI enforces
  this with ``python -m repro.kernels.autotune --check``).

Entries build *abstract* example arguments (``jax.ShapeDtypeStruct``), so
registering and tracing a kernel never allocates or executes anything;
``concrete_args`` materializes random inputs only when a benchmark asks.
The Pallas execution mode is the caller's: ``build(interpret)`` forwards
it to the kernels, where ``None`` resolves through
:mod:`repro.kernels.runtime` (Mosaic on a TPU).  The static verifier traces
with ``interpret=True``.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core.formats import FMT_IMAGENET
from repro.core.lowbit import QuantConfig
from .autotune import TuneSpec
from .implicit_conv import conv_geometry, conv_tune_dims
from .lowbit_conv import lowbit_conv_fused, lowbit_matmul_qd
from .mls_matmul import mls_matmul_pallas
from .mls_quantize import mls_quantize_pallas
from .ops import lowbit_matmul_fused

__all__ = ["KERNEL_REGISTRY", "KernelEntry"]


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One verifiable/benchable Pallas kernel entry point.

    ``build(interpret)`` returns ``(fn, abstract_args)`` — a traceable
    callable and example ``ShapeDtypeStruct`` arguments.  ``needs_grad`` marks training
    ops whose custom-VJP backward GEMMs must be verified too (the verifier
    traces ``jax.vjp`` through them).  ``bench_tag`` names the example
    shape in benchmark rows (kept stable for the perf trail).  ``tune`` is
    the entry's autotuning workload (``None`` when another entry's spec
    already covers the same cache key — e.g. the raw-codes GEMM is tuned
    through the fused wrapper).
    """

    name: str
    description: str
    build: Callable[[bool | None], tuple[Callable, tuple]]
    needs_grad: bool = False
    bench: bool = True
    bench_tag: str = ""
    tune: TuneSpec | None = None

    def fn_and_args(
        self, interpret: bool | None = None
    ) -> tuple[Callable, tuple]:
        return self.build(interpret)

    def trace(self, interpret: bool | None = True):
        """ClosedJaxpr of the forward (+ backward when ``needs_grad``)."""
        fn, avals = self.build(interpret)
        if self.needs_grad:
            def fwd_bwd(*args):
                y, vjp = jax.vjp(fn, *args)
                return y, vjp(jnp.ones_like(y))
            return jax.make_jaxpr(fwd_bwd)(*avals)
        return jax.make_jaxpr(fn)(*avals)

    def concrete_args(self, seed: int = 0) -> tuple:
        """Random concrete inputs matching the example abstract shapes."""
        _, avals = self.build(None)
        keys = jax.random.split(jax.random.key(seed), max(len(avals), 2))
        out = []
        for k, a in zip(keys, avals):
            if jnp.issubdtype(a.dtype, jnp.floating):
                out.append(jax.random.normal(k, a.shape, a.dtype))
            else:
                info = jnp.iinfo(a.dtype)
                out.append(jax.random.randint(
                    k, a.shape, 0, min(int(info.max), 255) + 1
                ).astype(a.dtype))
        return tuple(out)


_F32 = jnp.float32


def _build_quantize(interpret):
    def fn(x):
        return mls_quantize_pallas(x, FMT_IMAGENET, 128, interpret=interpret)
    return fn, (jax.ShapeDtypeStruct((256, 512), _F32),)


def _build_matmul(interpret):
    kb, M, K, N = 128, 256, 512, 256

    def fn(xc, xsg, xst, wc, wsg, wst):
        return mls_matmul_pallas(
            xc, xsg, xst, wc, wsg, wst, FMT_IMAGENET,
            k_block=kb, block_m=128, block_n=128, interpret=interpret,
        )
    avals = (
        jax.ShapeDtypeStruct((M, K), jnp.uint8),
        jax.ShapeDtypeStruct((M, K // kb), _F32),
        jax.ShapeDtypeStruct((), _F32),
        jax.ShapeDtypeStruct((K, N), jnp.uint8),
        jax.ShapeDtypeStruct((K // kb, N), _F32),
        jax.ShapeDtypeStruct((), _F32),
    )
    return fn, avals


def _build_matmul_fused(interpret):
    def fn(x, w):
        return lowbit_matmul_fused(x, w, None, fmt=FMT_IMAGENET,
                                   interpret=interpret)
    return fn, (jax.ShapeDtypeStruct((256, 512), _F32),
                jax.ShapeDtypeStruct((512, 256), _F32))


def _conv_cfg(interpret) -> QuantConfig:
    return QuantConfig(fmt=FMT_IMAGENET, stochastic=False, backend="pallas",
                       k_block=32, pallas_interpret=interpret)


def _build_conv_fused(interpret):
    cfg = _conv_cfg(interpret)

    def fn(x, w):
        return lowbit_conv_fused(x, w, None, (1, 1), "SAME", cfg)
    return fn, (jax.ShapeDtypeStruct((2, 16, 8, 8), _F32),
                jax.ShapeDtypeStruct((16, 16, 3, 3), _F32))


def _implicit_conv_cfg(interpret) -> QuantConfig:
    # k_block = cb*kh*kw = 4*3*3: legal implicit grouping for C=16 3x3 convs
    return QuantConfig(fmt=FMT_IMAGENET, stochastic=False, backend="pallas",
                       k_block=36, conv_impl="implicit",
                       pallas_interpret=interpret)


def _build_conv_implicit(interpret):
    cfg = _implicit_conv_cfg(interpret)

    def fn(x, w):
        return lowbit_conv_fused(x, w, None, (1, 1), "SAME", cfg)
    return fn, (jax.ShapeDtypeStruct((2, 16, 8, 8), _F32),
                jax.ShapeDtypeStruct((16, 16, 3, 3), _F32))


_ICONV_GEOM = conv_geometry((2, 16, 8, 8), (16, 16, 3, 3), (1, 1), "SAME")


def _build_matmul_qd(interpret):
    cfg = _conv_cfg(interpret)

    def fn(x, w):
        return lowbit_matmul_qd(x, w, None, cfg)
    return fn, (jax.ShapeDtypeStruct((64, 96), _F32),
                jax.ShapeDtypeStruct((96, 64), _F32))


KERNEL_REGISTRY: dict[str, KernelEntry] = {
    e.name: e
    for e in (
        KernelEntry(
            name="mls_quantize_pallas",
            description="fused MLS dynamic quantization (paper Alg. 2)",
            build=_build_quantize,
            bench_tag="256x512",
            tune=TuneSpec("quantize", (256, 512), FMT_IMAGENET, 128),
        ),
        KernelEntry(
            name="mls_matmul_pallas",
            description="quantized-domain GEMM (paper Eq. 6-8)",
            build=_build_matmul,
            bench=False,  # raw-codes timing is covered by the fused row
            bench_tag="256x512x256",
            tune=None,  # same cache key as lowbit_matmul_fused's spec
        ),
        KernelEntry(
            name="lowbit_matmul_fused",
            description="dynamic-quantize-both-operands fused GEMM",
            build=_build_matmul_fused,
            bench_tag="256x512x256",
            tune=TuneSpec("gemm", (256, 512, 256), FMT_IMAGENET, 128),
        ),
        KernelEntry(
            name="lowbit_conv_fused",
            description="im2col conv with fwd/wgrad/dgrad quantized GEMMs "
                        "(paper Alg. 1)",
            build=_build_conv_fused,
            needs_grad=True,
            bench_tag="2x16x8x8_o16k3",
            # the forward im2col GEMM of the example shape:
            # (N*OH*OW, C*kh*kw, O) = (2*8*8, 16*3*3, 16) at k_block=32
            tune=TuneSpec("gemm", (128, 144, 16), FMT_IMAGENET, 32),
        ),
        KernelEntry(
            name="lowbit_conv_implicit",
            description="implicit-GEMM conv, quantize fused into the GEMM "
                        "prologue (no materialized im2col)",
            build=_build_conv_implicit,
            needs_grad=True,
            bench_tag="2x16x8x8_o16k3",
            # conv specs key on the full geometry + k_block; the tuner
            # races im2col against implicit tilings at fixed numerics
            tune=TuneSpec("conv", conv_tune_dims(_ICONV_GEOM, 36),
                          FMT_IMAGENET, 36),
        ),
        KernelEntry(
            name="lowbit_matmul_qd",
            description="linear-layer training op, all three GEMMs "
                        "quantized-domain",
            build=_build_matmul_qd,
            needs_grad=True,
            bench=False,
            bench_tag="64x96x64",
            tune=TuneSpec("gemm", (64, 96, 64), FMT_IMAGENET, 32),
        ),
    )
}
