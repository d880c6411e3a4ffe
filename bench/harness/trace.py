"""From a profiler trace to busy time, idle gaps and kernel time.

The JAX profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it.  A device plane is named
`/device:TPU:<n>`; its "XLA Ops" line holds one event per operation
run on the chip.  Host planes hold the benchmark's own
`TraceAnnotation` spans and the runtime's, on the same clock.

    busy      union of the op intervals inside the traced window
    idle      window minus busy; each gap is labelled by the shortest
              host span that covers its middle
    kernel    sum of the durations of the op events a kernel's file
              names (bench/kernels/<kernel>.py EVENTS), and their count
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import re
from collections import defaultdict
from typing import Iterable, Sequence

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")     # one per chip
WINDOW_SPAN = "bench.traced"       # the client's annotation of the window


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Event]]     # device plane -> op events
    host: list[Event]                      # every host event, all threads


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # averaged over devices
    ops: list[Event]                       # op events in the window
    gaps: list[tuple[str, float]]          # (label, seconds), longest first
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, patterns: Sequence[str]) -> tuple[float, int]:
        """(seconds, calls) of the ops whose name starts with a pattern,
        summed over devices."""
        hits = [e for e in self.ops if matches(e.name, patterns)]
        return sum(e.dur_ns for e in hits) * 1e-9, len(hits)

    def top_ops(self, n: int = 10) -> list[list]:
        by = defaultdict(float)
        for e in self.ops:
            by[e.name] += e.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]


def matches(name: str, patterns: Iterable[str]) -> bool:
    return any(name == p or name.startswith(p + ".")
               or name.startswith(p + "_") or name.startswith(p + "(")
               for p in patterns)


@contextlib.contextmanager
def capture(out_dir: pathlib.Path):
    """Profile the body; yields a list that holds the .xplane.pb path
    once the body ends."""
    import jax

    found: list[pathlib.Path] = []
    jax.profiler.start_trace(str(out_dir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield found
    finally:
        jax.profiler.stop_trace()
    found.extend(sorted(out_dir.glob("plugins/profile/*/*.xplane.pb")))


def load(path: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_ops: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return Trace(device_ops, host)


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list[list[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def label(t: float, host: Sequence[Event], exclude=(WINDOW_SPAN,)) -> str:
    """The shortest host span (of non-zero length) covering time t, else
    'no host span'."""
    best = None
    for e in host:
        if e.dur_ns > 0 and e.start_ns <= t <= e.end_ns \
                and e.name not in exclude:
            if best is None or e.dur_ns < best.dur_ns:
                best = e
    return best.name if best is not None else "no host span"


def summarize(trace: Trace, window: tuple[float, float] | None = None,
              n_gaps: int = 10) -> Summary:
    """Reduce a trace over `window` (ns); by default the window is the
    client's `bench.traced` span."""
    if window is None:
        spans = [e for e in trace.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        window = (spans[0].start_ns, spans[0].end_ns)
    lo, hi = window
    if not trace.device_ops:
        raise ValueError("trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    busy_total, ops, gaps = 0.0, [], []
    for plane, events in sorted(trace.device_ops.items()):
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        ops.extend(inside)
        busy = clip(union((e.start_ns, e.end_ns) for e in inside), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(label((s + e) / 2, trace.host), (e - s) * 1e-9)
                for s, e in gaps[:n_gaps]]
    n = len(trace.device_ops)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   ops=ops, gaps=labelled, n_devices=n)
