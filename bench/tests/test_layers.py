"""Per-layer attribution: program scopes of HLO instructions, device time
by scope, the host's dispatch and its runtime spans, a chip trace."""
import gzip
import os

import pytest

from bench import layers, run, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# A weight-gradient conv with the SGD update fused into it, a bare update
# op, XLA's copies, and a parameter whose path holds a scope word.
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4], param_1: f32[4]) -> (f32[4], f32[4]) {
  %param_0 = f32[4]{0} parameter(0)
  %param_1 = f32[4]{0} parameter(1)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_1), metadata={op_name="jit(step)/optimizer/mul" stack_frame_id=3}
  %convolution.2 = f32[4]{0} convolution(%param_0, %param_1), dim_labels=b0f_0io->b0f, metadata={op_name="jit(step)/transpose(jvp(conv))/conv_general_dilated" stack_frame_id=4}
  ROOT %tuple.3 = (f32[4]{0}, f32[4]{0}) tuple(%multiply.1, %convolution.2)
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %rsqrt.4 = f32[4]{0} rsqrt(%param_0.1), metadata={op_name="jit(step)/jvp(bn)/rsqrt"}
}

ENTRY %main.9 (params__fc____w__.1: f32[4]) -> (f32[4], f32[4]) {
  %params__fc____w__.1 = f32[4]{0} parameter(0), metadata={op_name="params[\\'fc\\'][\\'w\\']"}
  %copy-start = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%params__fc____w__.1)
  %copy-done = f32[4]{0} copy-done(%copy-start)
  %subtract.5 = f32[4]{0} subtract(%copy-done, %copy-done), metadata={op_name="jit(step)/sub"}
  %bn_fusion = f32[4]{0} fusion(%subtract.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(bn)/rsqrt"}
  ROOT %multiply_subtract_fusion = (f32[4]{0}, f32[4]{0}) fusion(%bn_fusion, %copy-done), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(conv))/conv_general_dilated" stack_frame_id=4}
}
"""


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/transpose(jvp(conv))/conv_general_dilated", "conv"),
    ("jit(step)/jvp(conv)/quantize/rounding/threefry2x32", "rounding"),
    ("jit(step)/transpose(jvp(conv))/quantize/mls_quantize", "quantize"),
    ("jit(step)/jvp(gemm)/implicit_conv", "gemm"),  # whole tokens only
    ("jit(step)/jvp(act)/jit(relu)/max", "act"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/sub", "unscoped"),
    ("jit(step)/conv_general_dilated", "unscoped"),
    ("params['fc']['w']", "unscoped"),  # a parameter path
    ("reduce_sum", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_by_hand(op_name, scope):
    assert layers.scope_of(op_name) == scope


def test_hlo_scopes_by_hand():
    scopes, mixed = layers.hlo_scopes(HLO)
    assert scopes["multiply_subtract_fusion"] == "conv"  # its root's
    assert scopes["bn_fusion"] == "bn"
    assert scopes["multiply.1"] == "optimizer"
    for name in ("params__fc____w__.1", "copy-start", "copy-done",
                 "subtract.5", "tuple.3", "param_0"):
        assert scopes[name] == "unscoped", name
    assert mixed["multiply_subtract_fusion"] == {"conv", "optimizer"}
    assert mixed["bn_fusion"] == {"bn"}


def _window():
    """Device ops and the benchmark thread's host events of one window,
    100..300 ns, two steps."""
    ops = {"/device:TPU:0": [
        ("multiply_subtract_fusion", 110, 130), ("bn_fusion", 120, 150),
        ("copy-done", 170, 180), ("subtract.5", 190, 200),
        ("not-in-hlo.7", 240, 250), ("bn_fusion", 280, 330),
        ("copy-done", 20, 40)]}
    host = [("bench.window", 100, 300),
            ("bench.step", 100, 200), ("bench.step", 200, 300),
            ("bench.step", 310, 320),
            ("bench.dispatch", 100, 150),
            ("PjitFunction(step)", 102, 148),
            ("PjitFunction(step)", 103, 147),
            ("ParseArguments", 104, 110),
            ("Wait for donation holds", 112, 114),
            ("Wait for donation holds", 115, 118),
            ("bench.readback", 150, 200),
            ("np.asarray(jax.Array)", 151, 199),
            ("bench.dispatch", 200, 260),
            ("PjitFunction(step)", 201, 251),
            ("ParseArguments", 202, 212),
            ("bench.readback", 260, 300),
            ("PjitFunction(other)", 261, 270),  # not in a dispatch span
            ("bench.dispatch", 310, 320),  # outside the window
            ("PjitFunction(step)", 311, 319)]
    return ops, host


def test_reduce_layers_by_hand():
    ops, host = _window()
    lay = layers.reduce_layers(ops, host, HLO)
    ns = 1e-9
    assert lay.steps == 2
    assert lay.scope_s["conv"] == pytest.approx(20 * ns)
    assert lay.scope_s["bn"] == pytest.approx(50 * ns)  # 30 + 20 clipped
    assert lay.scope_s["unscoped"] == pytest.approx(30 * ns)
    assert lay.scope_s["optimizer"] == 0.0
    assert lay.mixed_s == {"conv+optimizer": pytest.approx(20 * ns)}
    assert lay.dispatch_s == pytest.approx((46 + 50) * ns)
    assert lay.bench_dispatch_s == pytest.approx((50 + 60) * ns)
    assert lay.ms_per_step(lay.dispatch_s) == pytest.approx(48 * ns * 1e3)
    # self times: the outer PjitFunction of step 1 covers 2 ns beyond the
    # inner one, which keeps 44 - 6 - 2 - 3 = 33; step 2's keeps 50 - 10
    assert lay.runtime_self_s == {
        "PjitFunction(step)": pytest.approx((2 + 33 + 40) * ns),
        "ParseArguments": pytest.approx((6 + 10) * ns),
        "Wait for donation holds": pytest.approx(5 * ns)}
    assert sum(lay.runtime_self_s.values()) == pytest.approx(lay.dispatch_s)
    assert lay.runtime_count == {"PjitFunction(step)": 3, "ParseArguments": 2,
                                 "Wait for donation holds": 2}
    # idle gaps of 10, 20, 10, 40 and 30 ns: in 150..170 readback's
    # np.asarray covers 19 ns; in 200..240 the PjitFunction covers 39 ns
    # (its ParseArguments only 10, under half the gap)
    owners = {round(g / ns): (o, share) for o, share, g in lay.gap_owners}
    assert owners[40] == ("bench.dispatch>PjitFunction(step)",
                          pytest.approx(39 / 40))
    assert owners[20] == ("bench.readback>np.asarray(jax.Array)",
                          pytest.approx(19 / 20))


def test_scope_times_add_up_to_class_times():
    ops, host = _window()
    spans: dict[str, list[tuple[int, int]]] = {}
    for name, s, e in host:
        spans.setdefault(name, []).append((s, e))
    classes, _ = trace.classify_hlo(HLO)
    red = trace.reduce_events(ops, spans, classes)
    lay = layers.reduce_layers(ops, host, HLO)
    assert sum(lay.scope_s.values()) == pytest.approx(sum(red.class_s.values()))


def test_nest_self_time_by_hand():
    nested = layers.nest([("c", 3, 5), ("a", 0, 10), ("b", 2, 8),
                          ("d", 8, 9)])
    assert [n for n, _, _ in nested.events] == ["a", "b", "c", "d"]
    assert nested.depth == [0, 1, 2, 1]
    assert nested.parent == [-1, 0, 1, 0]
    assert nested.self_ns == [10 - 6 - 1, 6 - 2, 2, 1]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        layers.reduce_layers({}, [("bench.step", 0, 1)], HLO)


def test_recorded_chip_trace():
    """The trace ``record_trace.py`` recorded on a TPU v5 lite: two calls of
    a compiled step (no scopes in its HLO), each inside the benchmark's
    dispatch span.  The Python line holds ``PjitFunction(jit(step))``, nested
    in itself, and ``ParseArguments``; the runtime's line of the main thread
    holds the execute call, with three output-buffer allocations each."""
    ops, _, thread = trace.read_xspace(os.path.join(DATA, "small.xplane.pb.gz"))
    lay = layers.reduce_layers(ops, thread, _recorded_hlo())
    assert lay.steps == 2
    # no scope in that program: every device op is unscoped, and the total
    # is the class total test_trace.py reads by hand
    assert lay.scope_s["unscoped"] == pytest.approx(36_353e-9)
    assert sum(lay.scope_s.values()) == lay.scope_s["unscoped"]
    # values read off the recorded events by hand (ns): the outer
    # PjitFunction of each call, 443,750 and 502,980
    assert lay.dispatch_s == pytest.approx((443_750 + 502_980) * 1e-9)
    assert lay.dispatch_s <= lay.bench_dispatch_s
    self_s = lay.runtime_self_s
    assert sum(self_s.values()) == pytest.approx(lay.dispatch_s)
    # 580 + 59,420 and 880 + 69,359 of the two nested pairs
    assert self_s["PjitFunction(jit(step))"] == pytest.approx(130_239e-9)
    assert self_s["DeferredTpuAllocator::Allocate"] == pytest.approx(
        (92_730 + 85_090 + 64_131 + 83_900 + 92_940 + 85_260) * 1e-9)
    assert self_s["ParseArguments"] == pytest.approx((3_420 + 4_390) * 1e-9)
    assert lay.runtime_count["DeferredTpuAllocator::Allocate"] == 6
    assert lay.runtime_count["PjitFunction(jit(step))"] == 4
    # the buffer-hold waits follow the call inside bench.dispatch: not in it
    assert "Wait for donation holds" not in self_s
    # the longest idle gap: the first call's key derivation compiled there
    owner = lay.gap_owners[0][0]
    assert owner.startswith("bench.data_fetch>")
    assert owner.endswith(">PJRT_Client_Compile")


def _recorded_hlo() -> str:
    with gzip.open(os.path.join(DATA, "small.hlo.txt.gz"), "rt") as f:
        return f.read()


SCOPE_METRICS = {"conv.ms_per_step": "conv", "bn.ms_per_step": "bn",
                 "optimizer.ms_per_step": "optimizer",
                 "unscoped.ms_per_step": "unscoped"}


def _read(red) -> dict:
    ctx = {"cell": None, "trace": red, "peaks": {}}
    return {name: run.read_metric(name, ctx)
            for name in ("dispatch.ms_per_step", *SCOPE_METRICS)}


def _expected(lay) -> dict:
    out = {"dispatch.ms_per_step": lay.ms_per_step(lay.dispatch_s)}
    for name, scope in SCOPE_METRICS.items():
        out[name] = lay.ms_per_step(lay.scope_s[scope])
    return out


def test_metrics_read_the_window_by_hand():
    """The dispatch and scope metrics read what ``reduce_layers`` gives,
    through the same pass that reduces the device classes."""
    ops, host = _window()
    spans: dict[str, list[tuple[int, int]]] = {}
    for name, s, e in host:
        spans.setdefault(name, []).append((s, e))
    red, lay, _ = trace.reduce_window(ops, spans, host, HLO)
    assert lay == layers.reduce_layers(ops, host, HLO)
    got = _read(red)
    assert got == _expected(lay)
    ns_ms = 1e-9 * 1e3
    assert got == {"dispatch.ms_per_step": pytest.approx(48 * ns_ms),
                   "conv.ms_per_step": pytest.approx(10 * ns_ms),
                   "bn.ms_per_step": pytest.approx(25 * ns_ms),
                   "optimizer.ms_per_step": None,  # nothing in that scope
                   "unscoped.ms_per_step": pytest.approx(15 * ns_ms)}
    classes, _ = trace.classify_hlo(HLO)
    assert red.class_s == trace.reduce_events(ops, spans, classes).class_s


def test_metrics_read_the_recorded_chip_trace():
    """On the recorded trace (no scopes in its program) the dispatch metric
    reads the two calls' ``PjitFunction`` time, ``unscoped`` every device
    op, the other scopes nothing; every field that the reduction had before
    reads as ``reduce_events`` gives it."""
    hlo = _recorded_hlo()
    path = os.path.join(DATA, "small.xplane.pb.gz")
    red, lay, unexpected = trace.reduce_trace(path, hlo)
    ops, host, thread = trace.read_xspace(path)
    assert lay == layers.reduce_layers(ops, thread, hlo)
    got = _read(red)
    assert got == _expected(lay)
    assert got["dispatch.ms_per_step"] == pytest.approx(
        (443_750 + 502_980) / 2 * 1e-6)
    assert got["unscoped.ms_per_step"] == pytest.approx(36_353 / 2 * 1e-6)
    assert got["conv.ms_per_step"] is None
    classes, _ = trace.classify_hlo(hlo)
    before = trace.reduce_events(ops, host, classes)
    for field in ("window_s", "busy_s", "steps", "class_s", "top_ops",
                  "idle_gaps", "chips"):
        assert getattr(red, field) == getattr(before, field), field
    assert unexpected == []


def test_report_counts_runtime_events_per_step():
    ops, host = _window()
    lines = layers.report(layers.reduce_layers(ops, host, HLO))
    assert lines[1].startswith("[dispatch] PjitFunction")
    assert "'ParseArguments': [0.0, 1.0]" in lines[1]
    assert "'PjitFunction(step)': [0.0, 1.5]" in lines[1]
