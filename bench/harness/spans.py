"""The program's own spans in a profiler trace: the bulk scorer's stage
times per chunk, the idle the host loop answers for, and the device time
that belongs to no kernel.

The scorer's `bulk/*` spans (src/repro/obs/trace.py) each enter a
`jax.profiler.TraceAnnotation`, so a traced sweep holds them on the host
lines of the trace, on the clock of the device's ops (see trace.py).

    chunks      `bulk/score` spans in the window: one per chunk scored
    per chunk   summed time of the named spans in the window / chunks
    host idle   time in which the device runs no op and the main thread
                is in no `bulk/sync` span: idle that the host loop, not
                the device queue, answers for
    outside     device time of the ops that match no kernel's EVENTS
                (bench/kernels/*.py): relayout copies, pads, slices,
                softmax

A device op event may carry the whole HLO instruction as its name
("%leaf_gather.1 = f32[7,256]{...} custom-call(...)"); `op_name` cuts
it to the instruction's own name before it is matched.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

from harness.trace import Event, clip, matches, union

CHUNK_SPAN = "bulk/score"
SYNC_SPAN = "bulk/sync"


def op_name(name: str) -> str:
    """'%leaf_gather.1 = f32[...] custom-call(...)' -> 'leaf_gather.1'."""
    return name.split(" = ", 1)[0].lstrip("%")


def window(host: Iterable[Event], span: str) -> tuple[float, float]:
    """(start, end) in ns of the first host event named `span`."""
    for e in host:
        if e.name == span:
            return e.start_ns, e.end_ns
    raise ValueError(f"trace has no {span!r} span")


def intervals(host: Iterable[Event], names: Sequence[str], lo: float,
              hi: float) -> list[list[float]]:
    """The named spans, clipped to the window [lo, hi] (ns)."""
    return clip(((e.start_ns, e.end_ns) for e in host if e.name in names),
                lo, hi)


def chunks(host: Iterable[Event], lo: float, hi: float) -> int:
    return len(intervals(host, (CHUNK_SPAN,), lo, hi))


def per_chunk_ms(host: Sequence[Event], names: Sequence[str], lo: float,
                 hi: float) -> Optional[float]:
    """Summed time of the named spans in the window, per chunk, in ms;
    None where the window holds no chunk span."""
    n = chunks(host, lo, hi)
    if n == 0:
        return None
    return 1e-6 * sum(e - s for s, e in intervals(host, names, lo, hi)) / n


def overlap_ns(a: Sequence[Sequence[float]],
               b: Sequence[Sequence[float]]) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_idle_s(ops: Iterable[Event], host: Sequence[Event], lo: float,
                hi: float) -> Optional[float]:
    """Seconds of the window in which one device's `ops` run nothing and
    no `bulk/sync` span is open; None where the window holds no chunk
    span (a program without the scorer's spans)."""
    if chunks(host, lo, hi) == 0:
        return None
    busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi)
    sync = union(intervals(host, (SYNC_SPAN,), lo, hi))
    busy_ns = sum(e - s for s, e in busy)
    sync_idle_ns = sum(e - s for s, e in sync) - overlap_ns(busy, sync)
    return (hi - lo - busy_ns - sync_idle_ns) * 1e-9


def outside_s(ops: Iterable[Event], patterns: Sequence[str]) -> float:
    """Summed device time of the ops no kernel pattern matches."""
    return 1e-9 * sum(e.dur_ns for e in ops
                      if not matches(op_name(e.name), patterns))
