"""Chip benchmark of the low-bit (MLS) CNN training step.

One run is one process on one chip::

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It loads the cell (``bench/workloads/<cell>.json``, which names its
configuration ``bench/configs/<config>.json``), makes the weights and a
pool of distinct batches on the device from ``--seed``, compiles the
program's train step (``repro.train.make_cnn_train_step``), drives it
through three steps that the plain reference (``reference.py``) checks
after the window, then trains for ``--seconds`` in a closed loop (one step
in flight, loss read back after each).  The step is called as the program
hands it out: one the program has jitted already, with whatever arguments
it donates, is called as it is, and any other is put under ``jax.jit``
(``jit_program``).  With ``--trace 0`` the last line of standard output
holds the cell's end-to-end metrics; with ``--trace 1`` the window is
profiled, the line holds the per-layer metrics
(``bench/metrics/<metric>.py``) that ``BENCHMARK.json`` lists for the
cell, and standard error gets the device time per program scope and the
host's dispatch call by runtime event (``layers.report``).

The run fails, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the chip's kind is not in ``peaks.json``,
when the step consumes its batch (the pool is reused; a step may donate
only its parameters and optimizer state), and when anything compiles
inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# run as a script: import the benchmark as a package (its trace.py must not
# shadow the standard library's) and the program from src/
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench", "trace")
WARM_STEPS = 3  # set-up steps; the reference follows them


class BenchError(Exception):
    """A run that must fail closed: no result line, non-zero exit."""


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
def _load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's workload and configuration files, and its chips and
    metrics as ``BENCHMARK.json`` lists them (a cell it does not list runs
    on one chip and reports no metric)."""
    path = os.path.join(BENCH, "workloads", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no workload file {path}")
    workload = _load_json(path)
    config = _load_json(BENCH, "configs", workload["config"] + ".json")
    spec = _load_json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in spec["workloads"]}.get(name, {})
    e2e = [m["name"] for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m["name"] for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return {"name": name, "workload": workload, "config": config,
            "chips": entry.get("chips", 1),
            "end_to_end": e2e, "per_layer": per_layer, "units": units}


def seed_key(seed: int):
    """A JAX key from any non-negative seed (64 bits are folded in)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# device, peaks, compilation cache, compile watch
# ---------------------------------------------------------------------------
def check_device(chips: int) -> tuple[object, dict]:
    """The first chip and its peaks; fail closed on no TPU, too few
    chips, or a chip kind ``peaks.json`` does not know."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"no TPU found: JAX sees {len(devices)} "
                         f"{dev.platform} device(s) ({dev.device_kind})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks = _load_json(BENCH, "peaks.json")
    if dev.device_kind not in peaks:
        raise BenchError(f"chip kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    return dev, peaks[dev.device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    ``<checkout>/.cache/jax``; every program is cached."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class CompileWatch:
    """Counts JAX compilation events while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.events: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and ("compile" in event or "trace" in event):
            self.events.append(event)


class GcPauses:
    """Durations of Python's garbage collections until ``stop()``."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def stop(self) -> None:
        gc.callbacks.remove(self._on)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def program_step(cell: dict):
    """The program's un-jitted train step for the cell."""
    from repro.core import QuantConfig
    from repro.core.formats import EMFormat
    from repro.models.cnn import CNNConfig
    from repro.train import make_cnn_train_step

    cfg, wl = cell["config"], cell["workload"]
    model = CNNConfig(arch=cfg["arch"], num_classes=cfg["num_classes"],
                      width_mult=cfg["width_mult"], in_hw=cfg["in_hw"],
                      in_ch=cfg["in_ch"])
    qcfg = None
    if wl["numerics"] == "mls":
        qcfg = QuantConfig(fmt=EMFormat(*wl["fmt"]),
                           gs_fmt=EMFormat(*wl["gs_fmt"]),
                           grouping=wl["grouping"],
                           stochastic=wl["rounding"] == "stochastic",
                           backend="pallas")
    return make_cnn_train_step(model, qcfg, cfg["lr"],
                               rounding_seed=wl["rounding_seed"])


def reference_numerics(wl: dict, control: bool = False):
    """What the reference computes in: the precision the cell states, or
    with ``control`` the nearest precision below it (the workload's
    ``control``).  The program's float convolutions and classifier run at
    XLA's default precision, whose operands the platform rounds
    (``reference.XLA_DEFAULT``)."""
    import jax

    from bench.reference import XLA_DEFAULT, Numerics

    spec = wl["control"] if control else {}
    fmt = spec.get("fmt", wl.get("fmt")) if wl["numerics"] == "mls" else None
    default = XLA_DEFAULT.get(jax.default_backend())
    return Numerics(fmt=tuple(fmt) if fmt else None,
                    gs_fmt=tuple(wl.get("gs_fmt", (8, 1))),
                    stochastic=wl.get("rounding") == "stochastic",
                    operand=spec.get("operand", default),
                    cotangent=spec.get("cotangent", default))


def make_inputs(cell: dict, seed: int):
    """(weights, batch maker, keys) from the seed; weights made on the
    device in one jitted call."""
    import jax

    from bench import data, model

    cfg, wl = cell["config"], cell["workload"]
    k_init, k_data, k_ref, k_ctl = jax.random.split(seed_key(seed), 4)
    params = jax.jit(lambda k: model.init_params(k, cfg))(k_init)
    make_batch = jax.jit(lambda k, j: data.batch(
        jax.random.fold_in(k, j), wl.get("batch", cfg["batch"]),
        cfg["in_hw"], cfg["num_classes"]))
    return params, (lambda j: make_batch(k_data, j)), k_ref, k_ctl


def run_reference(cell: dict, p0_host, batch_of, key, num,
                  batch_rows: int | None = None) -> dict:
    """The reference's three steps from ``p0_host`` on batches 0..2."""
    import jax
    import jax.numpy as jnp

    from bench import reference

    cfg = cell["config"]
    params = jax.device_put(p0_host)
    mu = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for i in range(WARM_STEPS):
        b = batch_of(i)
        if batch_rows is not None:
            b = jax.tree.map(lambda a: a[:batch_rows], b)
        params, mu, loss, grads = reference.step(
            params, mu, b, jax.random.fold_in(key, i), cfg, num)
        losses.append(float(loss))
        if i == 0:
            g1 = jax.tree.leaves(jax.device_get(grads))
    return {"losses": losses, "g1": g1,
            "p3": jax.tree.leaves(jax.device_get(params))}


def jit_program(cell: dict, step_wrapper=None):
    """The program's train step (under ``step_wrapper`` where one is given)
    as the program hands it out, as its users call it; its first call
    compiles.  A step that is jitted already (it has ``lower``) is called as
    it is: an outer ``jax.jit`` would drop the arguments the program's own
    jit donates.  Any other step is put under ``jax.jit``."""
    import jax

    fn = program_step(cell)
    if step_wrapper is not None:
        fn = step_wrapper(fn)
    return fn if hasattr(fn, "lower") else jax.jit(fn)


def host_copy(tree):
    """The tree's arrays read back into host memory of their own.  On the
    CPU ``jax.device_get`` gives views of the device buffers, and a step
    cannot consume a donated array while a view of it lives."""
    import jax
    import numpy as np

    return jax.tree.map(np.array, jax.device_get(tree))


def compiled_hlo(step, *args) -> str:
    """The compiled step's HLO text with this checkout's metadata.  The
    persistent cache's key leaves metadata out, so a cached executable may
    carry another checkout's ``op_name``s; the key takes it in here."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return step.lower(*args).compile().as_text()
    finally:
        jax.config.update(key, was)


def first_steps(step, params, opt, batches, p0_host):
    """Drive the step through the set-up steps on batches 0..2 (the first
    compiles); return the state and what the check reads of them.  The step
    may consume (donate) ``params`` and ``opt``, and nothing else: the
    window reuses the pool of batches."""
    import jax
    import numpy as np

    prog = {"losses": [], "p0": jax.tree.leaves(p0_host)}
    for i in range(WARM_STEPS):
        params, opt, loss, _ = step(params, opt, batches[i], np.int32(i))
        prog["losses"].append(float(loss))
        if i == 0:
            prog["mu1"] = jax.tree.leaves(host_copy(opt.mu))
    if any(x.is_deleted() for x in jax.tree.leaves(batches[:WARM_STEPS])):
        raise BenchError("the program's step consumed its batch (it donates "
                         "argument 2); the benchmark reuses its pool of "
                         "batches, so a step may donate only its parameters "
                         "and optimizer state (arguments 0 and 1)")
    prog["p3"] = jax.tree.leaves(host_copy(params))
    return params, opt, prog


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             *, dev, step_wrapper=None) -> dict:
    """Set up, measure and check one run; return the result record."""
    import jax
    import numpy as np

    from bench import check, model
    from repro.optim import sgdm_init

    cfg, wl = cell["config"], cell["workload"]
    watch = CompileWatch()
    params, batch_of, k_ref, _ = make_inputs(cell, seed)
    batches = [batch_of(j) for j in range(wl["pool"])]
    p0_host = host_copy(params)
    opt = jax.jit(sgdm_init)(params)
    step = jit_program(cell, step_wrapper)
    hlo_text = ""
    if trace:  # its HLO sorts the trace's device ops by class and scope
        hlo_text = compiled_hlo(step, params, opt, batches[0], np.int32(0))
    params, opt, prog = first_steps(step, params, opt, batches, p0_host)
    setup_s = time.perf_counter() - T_START

    from jax.profiler import TraceAnnotation

    trace_dir = os.path.join(TRACE_DIR, cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    step_s, dispatch_s, losses = [], [], []
    gc_pauses = GcPauses()
    i = WARM_STEPS
    watch.armed = True
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            t = time.perf_counter()
            with TraceAnnotation("bench.step"):
                with TraceAnnotation("bench.data_fetch"):
                    batch = batches[i % len(batches)]
                with TraceAnnotation("bench.dispatch"):
                    params, opt, loss, acc = step(params, opt, batch,
                                                  np.int32(i))
                td = time.perf_counter()
                with TraceAnnotation("bench.readback"):
                    losses.append(float(loss))
                    float(acc)
            now = time.perf_counter()
            step_s.append(now - t)
            dispatch_s.append(td - t)
            i += 1
            if now - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    watch.armed = False
    gc_pauses.stop()
    if trace:
        jax.profiler.stop_trace()
    if watch.events:
        raise BenchError(f"{len(watch.events)} compilation event(s) inside "
                         f"the window: {sorted(set(watch.events))}")
    stats = dev.memory_stats() or {}
    _say(f"[memory] {stats}")
    record = {"steps": len(step_s), "window_s": window_s,
              "setup_s": setup_s,
              "images_per_s": len(step_s) * batches[0]["label"].shape[0]
              / window_s,
              # buffers plus the region reserved for the programs' temps
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)
                                       + stats.get("peak_bytes_reserved", 0)),
              "failed": sum(1 for v in losses if not math.isfinite(v))}
    q = statistics.quantiles(step_s, n=10) if len(step_s) > 1 else step_s
    med = statistics.median(step_s)
    longest = sorted(range(len(step_s)), key=step_s.__getitem__)[-3:]
    _say(f"[window] {len(step_s)} steps in {window_s:.3f} s; step median "
         f"{med * 1e3:.3f} ms, p90 {q[-1] * 1e3:.3f} ms, of it dispatch "
         f"median {statistics.median(dispatch_s) * 1e3:.3f} ms; "
         f"{sum(x for x in step_s if x > 3 * med):.3f} s in steps over 3x "
         f"the median; longest steps (index, ms) "
         f"{[(j, round(step_s[j] * 1e3, 1)) for j in reversed(longest)]}; "
         f"{len(gc_pauses.pauses)} collections of Python's garbage, "
         f"{sum(gc_pauses.pauses):.3f} s; last loss {losses[-1]!r}")

    # the program's state is freed before the reference runs
    del params, opt, batches, step, batch, loss, acc
    gc.collect()
    if trace:
        from bench import layers
        from bench import trace as bench_trace

        path = bench_trace.find_xspace(trace_dir)
        record["trace"], lay, unexpected = bench_trace.reduce_trace(path,
                                                                    hlo_text)
        if unexpected:
            _say(f"[trace] {len(unexpected)} tpu_custom_call(s) that no class "
                 f"rule expects, counted as gemm: {unexpected[:5]}")
        for line in layers.report(lay):
            _say(line)
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    ref = run_reference(cell, p0_host, batch_of, k_ref, reference_numerics(wl))
    _say(f"[reference] {WARM_STEPS} steps in "
         f"{time.perf_counter() - t_ref:.1f} s")
    numbers = check.compare(prog, ref, cfg["weight_decay"])
    record["numbers"] = numbers
    record["leaves"] = model.leaf_paths(p0_host)
    record["correct"] = check.verdict(numbers, wl["limits"])
    return record


# ---------------------------------------------------------------------------
# metrics and the result line
# ---------------------------------------------------------------------------
def read_metric(name: str, ctx: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def result_line(cell: dict, record: dict, trace: bool, dev, peaks: dict,
                count: int) -> dict:
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count,
              "memory_peak_bytes": record["memory_peak_bytes"]}
    out = {"correct": record["correct"], "attempted": record["steps"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        red = record["trace"]
        ctx = {"cell": cell, "trace": red, "peaks": peaks}
        for name in cell["per_layer"]:
            value = read_metric(name, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": cell["units"][name]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        for name in cell["end_to_end"]:
            metrics[name] = {"value": record[name],
                             "unit": cell["units"][name]}
    limits = cell["workload"]["limits"]
    out["checks"] = {k: {"value": record["numbers"][k], "limit": limits[k]}
                     for k in limits}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        try:
            import jax
            import repro.train  # noqa: F401
        except ImportError as e:
            raise BenchError(f"cannot import the program ({e}); run from "
                             f"the root of a checkout") from e
        dev, peaks = check_device(cell["chips"])
        _say(f"[device] {dev.platform} {dev.device_kind} x"
             f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
        record = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          dev=dev)
    except (BenchError, FileNotFoundError) as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    out = result_line(cell, record, bool(args.trace), dev, peaks,
                      len(jax.devices()))
    w, leaves = record["numbers"]["worst"], record["leaves"]
    _say(f"[check] worst loss at step {w['loss_step']}, worst gradient leaf "
         f"{leaves[w['grad_leaf']]}, worst change leaf "
         f"{leaves[w['update_leaf']]}, {w['unmoved_leaves']} leaves left out "
         f"as unmoved")
    _say("[numbers] " + json.dumps({k: v for k, v in record["numbers"].items()
                                    if k != "worst"}))
    for k, v in out["checks"].items():
        _say(f"check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
