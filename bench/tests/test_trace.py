"""The trace reduction: HLO classes, interval arithmetic, a chip trace."""
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip; the persistent cache is off,
    since a described chip cannot read its entries back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def test_classifier_on_compiled_qd_gemm(one_chip):
    from repro.core import FMT_CIFAR
    from repro.kernels.lowbit_conv import qd_gemm

    def fn(x, w, key):
        return qd_gemm(x, w, jax.random.fold_in(key, 0),
                       jax.random.fold_in(key, 1), fmt=FMT_CIFAR,
                       interpret=False)

    args = (jax.ShapeDtypeStruct((512, 256), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((256, 128), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=one_chip))
    text = jax.jit(fn).lower(*args).compile().as_text()
    classes, unexpected = trace.classify_hlo(text)
    assert sorted(classes.values()) == ["gemm", "quantize", "quantize"]
    assert unexpected == []


def test_merge_and_gaps_by_hand():
    busy = trace.merge([(5, 9), (0, 2), (1, 3), (9, 10), (12, 15)])
    assert busy == [(0, 3), (5, 10), (12, 15)]
    assert trace.gaps(busy, 0, 20) == [(3, 5), (10, 12), (15, 20)]
    assert trace.gaps(busy, 6, 13) == [(10, 12)]


def test_reduce_events_by_hand():
    # window 100..200 ns; a quantize op, an overlapping gemm op, an xla op
    # and one op half outside the window
    ops = {"/device:TPU:0": [("q.1", 110, 130), ("g.2", 120, 150),
                             ("fusion.3", 170, 180), ("fusion.4", 190, 260),
                             ("fusion.5", 20, 40)]}
    host = {trace.WINDOW_SPAN: [(100, 200)],
            trace.STEP_SPAN: [(100, 160), (160, 200), (210, 220)],
            "bench.dispatch": [(100, 112), (160, 171)],
            "bench.readback": [(150, 160), (180, 200)]}
    red = trace.reduce_events(ops, host, {"q.1": "quantize", "g.2": "gemm"})
    ns = 1e-9
    assert red.window_s == pytest.approx(100 * ns)
    # busy: [110,150) + [170,180) + [190,200) = 60 ns
    assert red.busy_s == pytest.approx(60 * ns)
    assert red.steps == 2
    assert red.class_s["quantize"] == pytest.approx(20 * ns)
    assert red.class_s["gemm"] == pytest.approx(30 * ns)
    assert red.class_s["xla"] == pytest.approx(20 * ns)
    assert red.top_ops[0] == ("gemm:g.2", pytest.approx(30 * ns))
    # gaps: [100,110) dispatch, [150,170) readback 10 / dispatch 10 ->
    # the first found wins ties, [180,190) readback
    assert [g for g, _ in red.idle_gaps] == ["bench.dispatch",
                                             "bench.dispatch",
                                             "bench.readback"]
    assert [s for _, s in red.idle_gaps] == pytest.approx(
        [20 * ns, 10 * ns, 10 * ns])
    assert red.ms_per_step("quantize") == pytest.approx(10 * ns * 1e3)


def test_reduce_averages_over_chips():
    ops = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": [("a", 0, 30)]}
    red = trace.reduce_events(ops, {trace.WINDOW_SPAN: [(0, 40)]}, {})
    assert red.busy_s == pytest.approx(20e-9)
    assert red.class_s["xla"] == pytest.approx(20e-9)


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_events({}, {}, {})


def test_op_name_of_a_trace_event():
    assert trace.op_name("%fusion.6 = (u8[8,128]{1,0}) fusion(%a), "
                         "kind=kLoop") == "fusion.6"
    assert trace.op_name("%jvp__.62 = f32[8,128]{1,0} custom-call(%a)") \
        == "jvp__.62"
    assert trace.op_name("jit_step(5150686997839066650)") \
        == "jit_step(5150686997839066650)"


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite by ``record_trace.py``: two calls
    of ``qd_gemm`` (two quantize kernels and one GEMM kernel each) and an
    XLA matrix product, inside the benchmark's host spans."""
    import gzip

    with gzip.open(os.path.join(DATA, "small.hlo.txt.gz"), "rt") as f:
        classes, unexpected = trace.classify_hlo(f.read())
    assert classes == {"step.3": "quantize", "step.4": "quantize",
                       "step.5": "gemm"}
    assert unexpected == []
    ops, host, _ = trace.read_xspace(os.path.join(DATA, "small.xplane.pb.gz"))
    red = trace.reduce_events(ops, host, classes)
    assert red.steps == 2 and red.chips == 1
    assert red.window_s == pytest.approx(237_478_905e-9)
    # values read off the recorded events by hand (ns)
    assert red.busy_s == pytest.approx(36_353e-9)
    assert red.class_s["quantize"] == pytest.approx(10_015e-9)
    assert red.class_s["gemm"] == pytest.approx(4_960e-9)
    assert red.class_s["xla"] == pytest.approx(21_378e-9)
    assert red.top_ops[0] == ("quantize:step.3", pytest.approx(7_975e-9))
    assert red.idle_gaps[0][0] == "bench.data_fetch"
    # the union of intervals, against a sweep over every event boundary
    lo, hi = host[trace.WINDOW_SPAN][0]
    evs = [(max(s, lo), min(e, hi)) for _, s, e in ops["/device:TPU:0"]
           if min(e, hi) > max(s, lo)]
    pts = sorted({p for ev in evs for p in ev})
    swept = sum(b - a for a, b in zip(pts, pts[1:])
                if any(s <= a and b <= e for s, e in evs))
    assert red.busy_s == pytest.approx(swept * 1e-9)
