"""Record a window with the JAX profiler and reduce its trace to numbers.

A traced window writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`reduce` reads it with :class:`jax.profiler.ProfileData` and gives:

* the traced window: the ``chipbench.window`` span on a host plane;
* device busy time: the union of the op intervals on each device plane's
  ``XLA Ops`` line, clipped to the window, averaged over the devices;
* device time per op name;
* idle time, each gap between device ops put down to the innermost
  ``chipbench.*`` span the host had open at the gap's middle.

Run ``python3 -m chipbench.trace <file.xplane.pb>`` to list the planes and
lines of a trace.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    op_s: Dict[str, float]        # device seconds by op name, all devices
    gap_s: Dict[str, float]       # idle seconds by host span, all devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}")
    return found[0]


@contextlib.contextmanager
def traced(trace_dir: Optional[Path]) -> Iterator:
    """Trace the body into ``trace_dir`` inside the window span.

    Yields the span factory the body opens its own spans with: the
    profiler's ``TraceAnnotation`` while tracing, a no-op otherwise.
    """
    if trace_dir is None:
        yield lambda name: contextlib.nullcontext()
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no event per Python call
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield jax.profiler.TraceAnnotation
    finally:
        jax.profiler.stop_trace()


def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class _SpanIndex:
    """Innermost host span at a time: spans grouped by nesting depth."""

    def __init__(self, spans: List[Tuple[float, float, str]]):
        levels: Dict[int, List[Tuple[float, float, str]]] = (
            collections.defaultdict(list))
        stack: List[float] = []
        for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1] <= s:
                stack.pop()
            levels[len(stack)].append((s, e, name))
            stack.append(e)
        self._levels = [(sorted(v), [x[0] for x in sorted(v)])
                        for _, v in sorted(levels.items(), reverse=True)]

    def at(self, t: float) -> str:
        for spans, starts in self._levels:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and spans[i][1] > t:
                return spans[i][2][len(SPAN_PREFIX):]
        return "outside"


Event = Tuple[float, float, str]  # start ns, end ns, name


def load_events(path: Path) -> Tuple[List[Event], List[List[Event]]]:
    """The ``chipbench.*`` host spans, and each device plane's ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    devices: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [(ev.start_ns, ev.end_ns, ev.name)
                   for line in plane.lines if line.name == DEVICE_OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
        else:
            spans.extend((ev.start_ns, ev.end_ns, ev.name)
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX))
    return spans, devices


def reduce(path: Path) -> TraceSummary:
    return summarize(*load_events(path))


def summarize(spans: List[Event], devices: List[List[Event]]
              ) -> TraceSummary:
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    if not devices:
        raise ValueError("no device ran an op in the window")
    w0, w1 = windows[0]
    index = _SpanIndex(spans)
    op_s: Dict[str, float] = collections.defaultdict(float)
    gap_s: Dict[str, float] = collections.defaultdict(float)
    busy_ns = 0.0
    for ops in devices:
        clipped = []
        for s, e, name in ops:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                clipped.append((s, e))
                op_s[name] += (e - s) * 1e-9
        merged = _merge(clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gap_s[index.at((gs + ge) / 2)] += (ge - gs) * 1e-9
    n = len(devices)
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                        devices=n, op_s=dict(op_s), gap_s=dict(gap_s))


def describe(path: Path) -> None:
    """Print each plane, its lines, their event counts and a few names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            names = collections.Counter(ev.name for ev in events)
            print(f"  line {line.name!r}: {len(events)} events; "
                  f"top names {names.most_common(6)}")


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        describe(Path(arg))
