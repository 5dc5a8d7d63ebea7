"""Reduction of a profiler trace of the measured window to device times.

The program's Pallas kernels carry no names in the compiled step (every one
is ``pallas_call`` with empty kernel metadata), so each device operation is
sorted by the compiled step's own HLO, by its result and operand types:

* a ``tpu_custom_call`` whose result holds ``u8`` is **quantize** (it
  writes 1-byte codes);
* a ``tpu_custom_call`` with a ``u8`` operand and a float32 result is
  **gemm** (it contracts codes);
* any other ``tpu_custom_call`` is unexpected: it is reported and counted
  as gemm;
* every other operation is **xla**.

Busy time is the union of the device operations' intervals inside the
window (the benchmark's ``bench.window`` host span); idle gaps are the
rest of the window, each labelled by the benchmark's host span that
overlaps it most.  The same pass reads the device time per program scope
and the host's dispatch call from the benchmark thread's events
(``layers.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

CLASSES = ("quantize", "gemm", "xla")
WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"
HOST_SPANS = ("bench.data_fetch", "bench.dispatch", "bench.readback")
OPS_LINE = "XLA Ops"
MAIN_THREAD = "main/"  # the runtime's host line of the main thread

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(")
_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def _braced(line: str, opener: str) -> str:
    """Text inside the braces that ``opener`` (ending in ``{``) opens."""
    start = line.find(opener)
    if start < 0:
        return ""
    i, depth = start + len(opener), 1
    while i < len(line) and depth:
        depth += {"{": 1, "}": -1}.get(line[i], 0)
        i += 1
    return line[start + len(opener):i - 1]


def classify_hlo(hlo_text: str) -> tuple[dict[str, str], list[str]]:
    """Map each ``tpu_custom_call`` instruction of compiled HLO text to
    quantize or gemm; also return the names no class rule expects."""
    classes: dict[str, str] = {}
    unexpected: list[str] = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, result = m.group(1), m.group(2)
        operand_types = _braced(line, "operand_layout_constraints={")
        if "u8[" in result:
            classes[name] = "quantize"
        elif result.startswith("f32[") and "u8[" in operand_types:
            classes[name] = "gemm"
        else:
            classes[name] = "gemm"
            unexpected.append(name)
    return classes, unexpected


@dataclasses.dataclass
class Reduced:
    """What the per-layer metrics read from one traced window."""

    window_s: float  # length of the window (host span)
    busy_s: float  # union of device-op intervals in it, mean over chips
    steps: int  # train steps whose host span started in the window
    class_s: dict[str, float]  # device seconds per class, mean over chips
    top_ops: list[tuple[str, float]]  # (class:name, seconds), largest first
    idle_gaps: list[tuple[str, float]]  # (host span, seconds), longest first
    chips: int
    # device seconds per program scope, mean over chips (layers.SCOPES)
    scope_s: dict[str, float] = dataclasses.field(default_factory=dict)
    dispatch_s: float = 0.0  # union of PjitFunction events in bench.dispatch
    # self seconds by name of the runtime events inside those calls
    runtime_self_s: dict[str, float] = dataclasses.field(default_factory=dict)

    def ms_per_step(self, cls: str) -> float | None:
        return self.per_step(self.class_s.get(cls, 0.0))

    def per_step(self, seconds: float) -> float | None:
        """``seconds`` of the window in ms per step; None where the window
        holds no step or nothing was read."""
        if self.steps == 0 or seconds <= 0.0:
            return None
        return seconds / self.steps * 1e3


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of half-open intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of [lo, hi) that merged ``busy`` does not cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _overlap(a: tuple[int, int], b: tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce_events(device_ops: dict[str, list[tuple[str, int, int]]],
                  host: dict[str, list[tuple[int, int]]],
                  classes: dict[str, str], top: int = 10) -> Reduced:
    """Reduce device ops ``{plane: [(name, start_ns, end_ns)]}`` and host
    spans ``{span name: [(start_ns, end_ns)]}`` to a :class:`Reduced`."""
    windows = host.get(WINDOW_SPAN, [])
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0][0], windows[-1][1]
    steps = sum(1 for s, _ in host.get(STEP_SPAN, []) if lo <= s < hi)
    chips = max(len(device_ops), 1)
    class_s = dict.fromkeys(CLASSES, 0.0)
    per_op: dict[str, float] = {}
    busy_ns = 0
    all_gaps: list[tuple[int, int]] = []
    spans = [(name, iv) for name in HOST_SPANS for iv in host.get(name, [])]
    for events in device_ops.values():
        clipped = []
        for name, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            cls = classes.get(name, "xla")
            class_s[cls] += (e - s) * 1e-9 / chips
            key = f"{cls}:{name}"
            per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9 / chips
        busy = merge(clipped)
        busy_ns += sum(e - s for s, e in busy)
        all_gaps.extend(gaps(busy, lo, hi))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    longest = []
    for g in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        label, best = "host:other", 0
        for name, iv in spans:
            ov = _overlap(g, iv)
            if ov > best:
                label, best = name, ov
        longest.append((label, (g[1] - g[0]) * 1e-9))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / chips,
                   steps=steps, class_s=class_s, top_ops=ops,
                   idle_gaps=longest, chips=chips)


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event (the trace names
    each op by its whole HLO text, ``%name = type op(...)``)."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def read_xspace(path: str):
    """Device ops, the benchmark's host spans, and every event of the host
    thread that runs the benchmark's loop, of one ``.xplane.pb`` (read
    gzip-compressed where the name ends in ``.gz``).

    The profiler writes that thread as two lines: the one that holds the
    benchmark's ``bench.window`` span (Python's spans, ``PjitFunction``,
    ``ParseArguments``) and the runtime's line of the process's main thread,
    ``main/<tid>`` (buffer allocation, waits on buffer holds, the
    executable's launch)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device_ops: dict[str, list[tuple[str, int, int]]] = {}
    host: dict[str, list[tuple[int, int]]] = {}
    lines: list[tuple[str, list[tuple[str, int, int]]]] = []
    wanted = {WINDOW_SPAN, STEP_SPAN, *HOST_SPANS}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (op_name(ev.name), int(ev.start_ns), int(ev.end_ns))
                        for ev in line.events)
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                events = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                          for ev in line.events]
                lines.append((line.name, events))
                for name, s, e in events:
                    if name in wanted:
                        host.setdefault(name, []).append((s, e))
    for spans in host.values():
        spans.sort()
    spans_line = next((i for i, (_, evs) in enumerate(lines)
                       if any(n == WINDOW_SPAN for n, _, _ in evs)), None)
    thread = [] if spans_line is None else lines[spans_line][1] + [
        ev for i, (name, evs) in enumerate(lines)
        if i != spans_line and name.startswith(MAIN_THREAD) for ev in evs]
    return device_ops, host, thread


def find_xspace(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_window(device_ops: dict[str, list[tuple[str, int, int]]],
                  host: dict[str, list[tuple[int, int]]],
                  thread: list[tuple[str, int, int]], hlo_text: str):
    """The :class:`Reduced` of one window, with its scope and dispatch
    fields, the :class:`layers.Layers` they come from, and the custom
    calls no class rule expects."""
    from bench import layers  # it builds on this module

    classes, unexpected = classify_hlo(hlo_text)
    red = reduce_events(device_ops, host, classes)
    lay = layers.reduce_layers(device_ops, thread, hlo_text)
    red.scope_s = dict(lay.scope_s)
    red.dispatch_s = lay.dispatch_s
    red.runtime_self_s = dict(lay.runtime_self_s)
    return red, lay, unexpected


def reduce_trace(path: str, hlo_text: str):
    return reduce_window(*read_xspace(path), hlo_text)
