"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics and the result's `breakdown` read.

  busy_s       union of the intervals in which an operation ran on a
               device, inside the traced window, averaged over devices
  window_s     length of the traced window: the host span named
               `window` that the harness puts around the timed loop
  device_ops   device self time by operation name, largest first (a
               container such as a `while` loop keeps only the time its
               inner operations leave uncovered)
  idle_gaps    device idle time inside the window, by the innermost
               host span the gap's midpoint fell in, largest first

Device operations are the events of the `XLA Ops` line of each
`/device:TPU:<n>` plane; host spans are the `TraceAnnotation` events of
the host plane's lines.  Both carry times on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) between disjoint busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class SpanIndex:
    """Host spans by name, for finding the innermost span at a time.
    Spans of one name never overlap: the harness issues its calls one
    after the other from one thread."""

    def __init__(self, spans: Dict[str, List[Interval]]):
        self.by_name = {n: sorted(v) for n, v in spans.items() if v}
        self.starts = {n: [s for s, _ in v] for n, v in self.by_name.items()}

    def innermost(self, t: float, default: str) -> str:
        """Name of the shortest span that contains time t."""
        best, width = default, float("inf")
        for name, ivs in self.by_name.items():
            i = bisect.bisect_right(self.starts[name], t) - 1
            if i >= 0 and t < ivs[i][1] and ivs[i][1] - ivs[i][0] < width:
                best, width = name, ivs[i][1] - ivs[i][0]
        return best


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, self time) of events on one line, where an event either
    holds another (a loop and its body's operations) or is disjoint from
    it: each event's time minus the time of the events directly in it."""
    own = [e - s for _, s, e in ops]
    stack: List[Tuple[float, int]] = []          # (end, index) of holders
    for i in sorted(range(len(ops)), key=lambda j: (ops[j][1], -ops[j][2])):
        _, s, e = ops[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return [(ops[i][0], own[i]) for i in range(len(ops))]


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce(device_ops: List[List[Tuple[str, float, float]]],
           spans: Dict[str, List[Interval]]) -> Summary:
    """Summary of a window from per-device op events (name, start_ns,
    end_ns) and host spans (name -> [(start_ns, end_ns)])."""
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"no host span named {WINDOW_SPAN!r} in the trace")
    if not device_ops:
        raise ValueError("no device plane with operations in the trace")
    lo = min(s for s, _ in spans[WINDOW_SPAN])
    hi = max(e for _, e in spans[WINDOW_SPAN])
    busy_total, by_op, idle = 0.0, {}, {}
    for ops in device_ops:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _, s, e in inside])
        busy_total += total(busy)
        for name, d in self_times(inside):
            by_op[name] = by_op.get(name, 0.0) + d
        index = SpanIndex({n: v for n, v in spans.items()
                           if n != WINDOW_SPAN})
        for s, e in gaps(busy, lo, hi):
            k = index.innermost((s + e) / 2, default="between calls")
            idle[k] = idle.get(k, 0.0) + (e - s)
    n = len(device_ops)
    ns = 1e-9
    rank = lambda d: sorted(((k, v * ns / n) for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return Summary(busy_s=busy_total * ns / n, window_s=(hi - lo) * ns,
                   device_ops=rank(by_op)[:10], idle_gaps=rank(idle)[:10])


def op_name(event_name: str) -> str:
    """`fusion.12` for a TPU op event named by its HLO text
    (`%fusion.12 = f32[...] fusion(...)`); other names as they are."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def events(profile, span_names: Sequence[str]):
    """(per-device op events, host spans) of a `jax.profiler.ProfileData`;
    host spans are kept only for `span_names`."""
    device_ops, spans = [], {n: [] for n in span_names}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [(op_name(ev.name), ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                device_ops.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    return device_ops, spans


def read_dir(trace_dir: str, span_names: Sequence[str]) -> Summary:
    """Summary of the one `.xplane.pb` the profiler wrote under a
    directory."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    ops, spans = events(ProfileData.from_file(files[0]),
                        [WINDOW_SPAN, *span_names])
    return reduce(ops, spans)
