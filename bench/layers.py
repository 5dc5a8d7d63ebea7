"""Per-layer attribution of a traced window: device time by program scope,
host dispatch by JAX's runtime spans.

The program names its layers with ``jax.named_scope`` (``conv``, ``fc``,
``bn``, ``act``, ``loss``, ``optimizer``; inside the MLS conv ``im2col``,
``col2im``, ``rounding``, ``quantize``, ``gemm``).  The names reach the
compiled step's HLO as each instruction's ``op_name`` metadata, a fusion
carrying its root's.  So every device op of the trace, which is named by
its HLO instruction, maps to one scope:

* the innermost vocabulary word among the identifier tokens of the
  instruction's ``op_name`` (``jit(step)/transpose(jvp(conv))/...`` is
  ``conv``);
* **unscoped** where the instruction has no metadata from the step (XLA's
  copies and relayouts), parameter-path metadata (``params['fc']['w']``),
  or no vocabulary word.

The host half is read from the runtime's own spans: JAX's
``PjitFunction(<name>)`` events inside the benchmark's ``bench.dispatch``
spans are the dispatch call, and every runtime event nested in them
(``ParseArguments``, the executable's launch, its output-buffer
allocations) keeps its self time and its count by name.

``trace.reduce_trace`` runs this reduction in the same pass as the class
reduction; a traced ``run.py`` run prints :func:`report`'s ``[scopes]``,
``[dispatch]`` and ``[gaps]`` lines on standard error.
"""
from __future__ import annotations

import dataclasses
import re

from bench import trace

SCOPES = ("conv", "fc", "bn", "act", "loss", "optimizer",
          "im2col", "col2im", "rounding", "quantize", "gemm")
UNSCOPED = "unscoped"
DISPATCH_EVENT = "PjitFunction("
DISPATCH_SPAN = "bench.dispatch"

_STEP_PREFIX = re.compile(r"jit\([^()]*\)/")
_TOKEN = re.compile(r"[A-Za-z_]\w*")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_META_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_of(op_name: str | None) -> str:
    """The scope of one ``op_name``: its innermost vocabulary word, or
    unscoped where it is not from the step or names no scope."""
    if not op_name:
        return UNSCOPED
    m = _STEP_PREFIX.match(op_name)
    if m is None:  # parameter paths and XLA's own names
        return UNSCOPED
    words = [t for t in _TOKEN.findall(op_name[m.end():]) if t in SCOPES]
    return words[-1] if words else UNSCOPED


def hlo_scopes(hlo_text: str) -> tuple[dict[str, str], dict[str, frozenset]]:
    """Scope of every instruction of compiled HLO text, and for each fusion
    the scopes of the ops its fused computation holds (nested fusions
    included)."""
    scopes: dict[str, str] = {}
    inner: dict[str, set[str]] = {}  # computation -> scopes of its ops
    calls: dict[str, list[str]] = {}  # computation -> computations it calls
    fusions: dict[str, str] = {}  # fusion instruction -> fused computation
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            comp = m.group(1)
            inner.setdefault(comp, set())
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.group(1), m.group(2)
        meta = _META_OP_NAME.search(rest)
        scope = scope_of(meta.group(1) if meta else None)
        scopes[name] = scope
        if scope != UNSCOPED:
            inner.setdefault(comp, set()).add(scope)
        called = _CALLS.search(rest)
        if called is not None:
            calls.setdefault(comp, []).append(called.group(1))
            if " fusion(" in rest:
                fusions[name] = called.group(1)

    def closure(c: str, seen: set[str]) -> set[str]:
        out = set(inner.get(c, ()))
        for sub in calls.get(c, ()):
            if sub not in seen:
                seen.add(sub)
                out |= closure(sub, seen)
        return out

    mixed = {f: frozenset(closure(c, {c})) for f, c in fusions.items()}
    return scopes, mixed


# ---------------------------------------------------------------------------
# host events: nesting, dispatch, gaps
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Nested:
    """Events of one host thread in nesting order, each with its depth, its
    parent's index (-1 at the top) and its self time."""

    events: list[tuple[str, int, int]]  # (name, start_ns, end_ns), sorted
    depth: list[int]
    parent: list[int]
    self_ns: list[int]


def nest(events: list[tuple[str, int, int]]) -> Nested:
    """Nest one thread's events by their intervals; an event's self time is
    its duration less the time its direct children cover."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    depth, parent, self_ns = [], [], [e - s for _, s, e in evs]
    stack: list[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        depth.append(len(stack))
        if stack:
            self_ns[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return Nested(evs, depth, parent, self_ns)


def dispatch_split(nested: Nested, lo: int, hi: int
                   ) -> tuple[float, dict[str, float], dict[str, int]]:
    """(seconds in the union of ``PjitFunction`` events inside the
    ``bench.dispatch`` spans that start in [lo, hi), self seconds and count
    by name of every event inside those ``PjitFunction`` events, themselves
    included)."""
    evs = nested.events
    inside = [False] * len(evs)  # within a dispatch span of the window
    calls = [False] * len(evs)  # within a PjitFunction event in one
    for i, (name, s, _) in enumerate(evs):
        p = nested.parent[i]
        inside[i] = (p >= 0 and inside[p]) or (
            name == DISPATCH_SPAN and lo <= s < hi)
        calls[i] = (p >= 0 and calls[p]) or (
            inside[i] and name.startswith(DISPATCH_EVENT))
    union = trace.merge([(s, e) for i, (n, s, e) in enumerate(evs)
                         if calls[i] and n.startswith(DISPATCH_EVENT)])
    self_s: dict[str, float] = {}
    count: dict[str, int] = {}
    for i, (name, _, _) in enumerate(evs):
        if calls[i]:
            self_s[name] = self_s.get(name, 0.0) + nested.self_ns[i] * 1e-9
            count[name] = count.get(name, 0) + 1
    return sum(e - s for s, e in union) * 1e-9, self_s, count


def gap_owners(nested: Nested, gaps: list[tuple[int, int]]
               ) -> list[tuple[str, float, float]]:
    """For each gap, the innermost host event that overlaps at least half
    of it, as ``(path, share of the gap, gap seconds)``; the path runs from
    the nearest benchmark span above that event down to it, middle elided.
    ``host:other`` where no event overlaps half the gap."""
    kids: list[list[int]] = [[] for _ in nested.events]
    top: list[int] = []
    for i, p in enumerate(nested.parent):
        (kids[p] if p >= 0 else top).append(i)
    out = []
    for g in gaps:
        glen = g[1] - g[0]
        best, best_ov, level = -1, 0, top
        while True:  # an event's ancestors overlap at least as much
            for i in level:
                _, s, e = nested.events[i]
                ov = min(e, g[1]) - max(s, g[0])
                if 2 * ov >= glen:
                    best, best_ov, level = i, ov, kids[i]
                    break
            else:
                break
        if best < 0:
            out.append(("host:other", 0.0, glen * 1e-9))
            continue
        path, i = [], best
        while i >= 0:
            path.append(nested.events[i][0])
            if path[-1].startswith("bench."):
                break
            i = nested.parent[i]
        path.reverse()
        if len(path) > 3:
            path = [path[0], path[1], "...", path[-1]]
        out.append((">".join(path), best_ov / glen, glen * 1e-9))
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Layers:
    """What a traced window holds per layer."""

    steps: int  # train steps whose host span started in the window
    scope_s: dict[str, float]  # device seconds per scope, mean over chips
    mixed_s: dict[str, float]  # device seconds of fusions mixing scopes
    dispatch_s: float  # union of PjitFunction events in bench.dispatch
    bench_dispatch_s: float  # the bench.dispatch spans themselves
    runtime_self_s: dict[str, float]  # self seconds by runtime event name
    runtime_count: dict[str, int]  # events by runtime event name
    gap_owners: list[tuple[str, float, float]]  # longest idle gaps first

    def ms_per_step(self, seconds: float) -> float | None:
        if self.steps == 0 or seconds <= 0.0:
            return None
        return seconds / self.steps * 1e3


def reduce_layers(device_ops: dict[str, list[tuple[str, int, int]]],
                  bench_thread: list[tuple[str, int, int]],
                  hlo_text: str, top: int = 10) -> Layers:
    """Reduce device ops ``{plane: [(name, start_ns, end_ns)]}`` and the
    benchmark thread's host events to a :class:`Layers`."""
    nested = nest(bench_thread)
    windows = [(s, e) for n, s, e in nested.events if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} host span")
    lo, hi = windows[0][0], windows[-1][1]
    scopes, mixes = hlo_scopes(hlo_text)
    chips = max(len(device_ops), 1)
    scope_s = dict.fromkeys((*SCOPES, UNSCOPED), 0.0)
    mixed_s: dict[str, float] = {}
    all_gaps: list[tuple[int, int]] = []
    for events in device_ops.values():
        clipped = []
        for name, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            scope_s[scopes.get(name, UNSCOPED)] += (e - s) * 1e-9 / chips
            if len(mixes.get(name, ())) > 1:
                key = "+".join(sorted(mixes[name]))
                mixed_s[key] = mixed_s.get(key, 0.0) + (e - s) * 1e-9 / chips
        all_gaps.extend(trace.gaps(trace.merge(clipped), lo, hi))
    dispatch_s, self_s, count = dispatch_split(nested, lo, hi)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return Layers(
        steps=sum(1 for n, s, _ in nested.events
                  if n == trace.STEP_SPAN and lo <= s < hi),
        scope_s=scope_s, mixed_s=mixed_s, dispatch_s=dispatch_s,
        bench_dispatch_s=sum(e - s for n, s, e in nested.events
                             if n == DISPATCH_SPAN and lo <= s < hi) * 1e-9,
        runtime_self_s=self_s, runtime_count=count,
        gap_owners=gap_owners(nested, longest))


def report(lay: Layers) -> list[str]:
    """The ``[scopes]``, ``[dispatch]`` and ``[gaps]`` lines."""
    def ms(x: float) -> float:
        return round(lay.ms_per_step(x) or 0.0, 4)

    scopes = {k: ms(v) for k, v in sorted(lay.scope_s.items(),
                                          key=lambda kv: -kv[1]) if v > 0}
    mixed = {k: ms(v) for k, v in sorted(lay.mixed_s.items(),
                                         key=lambda kv: -kv[1])}
    top = sorted(lay.runtime_self_s.items(), key=lambda kv: -kv[1])[:8]
    calls = {k: round(lay.runtime_count[k] / max(lay.steps, 1), 2)
             for k, _ in top}
    return [
        f"[scopes] ms per step {scopes}; total "
        f"{ms(sum(lay.scope_s.values()))}; fusions mixing scopes {mixed}",
        f"[dispatch] PjitFunction {ms(lay.dispatch_s)} ms per step of "
        f"bench.dispatch {ms(lay.bench_dispatch_s)}; top self time per step "
        f"(name: [ms, events]) { {k: [ms(v), calls[k]] for k, v in top} }",
        "[gaps] " + "; ".join(f"{g * 1e3:.3f} ms under {owner} ({share:.0%})"
                              for owner, share, g in lay.gap_owners),
    ]
