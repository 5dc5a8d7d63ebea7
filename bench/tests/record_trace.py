"""Record the small chip trace that ``test_trace.py`` reduces.

On a TPU, from the root of a checkout::

    python bench/tests/record_trace.py

It runs a few calls of the program's quantized-domain GEMM
(``kernels.lowbit_conv.qd_gemm``: two quantize kernels and one GEMM
kernel) and of a plain XLA matrix product, inside the benchmark's host
spans, under the profiler, and writes ``bench/tests/data/small.xplane.pb.gz``
and the compiled HLO text ``small.hlo.txt.gz`` beside it.  It prints the
trace's planes and lines, and a few events of each device line.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(TESTS))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

DATA = os.path.join(TESTS, "data")
CALLS = 2


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench import trace as bench_trace
    from repro.core import FMT_CIFAR
    from repro.kernels.lowbit_conv import qd_gemm

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 1

    def step(x, w, key):
        y = qd_gemm(x, w, jax.random.fold_in(key, 0),
                    jax.random.fold_in(key, 1), fmt=FMT_CIFAR)
        return y, jnp.tanh(x @ w).sum()

    kx, kw, kr = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (512, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 128), jnp.float32)
    compiled = jax.jit(step).lower(x, w, kr).compile()
    jax.block_until_ready(compiled(x, w, kr))
    out = os.path.join(ROOT, ".cache", "bench", "record_trace")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1  # the benchmark's spans, not every thread
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    with TraceAnnotation(bench_trace.WINDOW_SPAN):
        for i in range(CALLS):
            with TraceAnnotation(bench_trace.STEP_SPAN):
                with TraceAnnotation("bench.data_fetch"):
                    key = jax.random.fold_in(kr, i)
                with TraceAnnotation("bench.dispatch"):
                    y, s = compiled(x, w, key)
                with TraceAnnotation("bench.readback"):
                    float(s)
                    jax.block_until_ready(y)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    os.makedirs(DATA, exist_ok=True)
    import gzip

    with open(path, "rb") as src, gzip.open(
            os.path.join(DATA, "small.xplane.pb.gz"), "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(DATA, "small.hlo.txt.gz"), "wt") as f:
        f.write(compiled.as_text())

    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: lines {[ln.name for ln in lines]}")
        if plane.name.startswith("/device:"):
            for ln in lines:
                evs = list(ln.events)
                print(f"  line {ln.name!r}: {len(evs)} events")
                for ev in evs[:6]:
                    try:
                        stats = dict(list(ev.stats)[:6])
                    except (TypeError, ValueError):
                        stats = repr(ev.stats)[:200]
                    print(f"    {ev.name!r} {ev.start_ns} {ev.duration_ns} "
                          f"{stats}")
    print(f"wrote {os.path.getsize(path)} bytes of trace to {DATA}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
