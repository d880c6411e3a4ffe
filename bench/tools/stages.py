#!/usr/bin/env python3
"""Run one bulk cell with --trace 1, keep its profiler trace under OUT,
and print the scorer's stage readings from the program's own spans.

    python3 bench/tools/stages.py --workload covertype-bulk \
        --seed 5 --seconds 5 --out traces/covertype-bulk

Prints the run's result line, then one JSON line: per chunk (one chunk
per `bulk/score` span in the traced window) the time of each `bulk/*`
span, the main thread's sum (prefetch wait + score + sync + sink)
against the chunk period (traced window / chunks), the idle share the host loop answers for,
the device time outside the kernels, and the ten longest device ops
with the event stats that carry their HLO metadata.
"""
import argparse
import json
import pathlib
import sys
import time
from collections import defaultdict

T0 = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import device, runner, spans, spec, trace  # noqa: E402

STAGES = ("bulk/read", "bulk/quantize", "bulk/quantize_wait",
          "bulk/prefetch_wait", "bulk/score", "bulk/sync", "bulk/sink")
MAIN = ("bulk/prefetch_wait", "bulk/score", "bulk/sync", "bulk/sink")


def op_stats(path: pathlib.Path, names) -> dict:
    """The stats of the first device op event of each name."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if e.name in names and e.name not in out:
                    out[e.name] = {k: str(v)[:200] for k, v in e.stats}
    return out


def readings(tr: trace.Trace, bench_dir: pathlib.Path) -> dict:
    """The stage readings of a one-device trace, over its traced window."""
    lo, hi = spans.window(tr.host, trace.WINDOW_SPAN)
    n = spans.chunks(tr.host, lo, hi)
    out = {"window_s": (hi - lo) * 1e-9, "chunks": n,
           "period_ms": 1e-6 * (hi - lo) / n if n else None,
           "per_chunk_ms": {s: spans.per_chunk_ms(tr.host, (s,), lo, hi)
                            for s in STAGES},
           "main_sum_ms": spans.per_chunk_ms(tr.host, MAIN, lo, hi)}
    (ops,) = tr.device_ops.values()
    inside = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
    idle = spans.host_idle_s(inside, tr.host, lo, hi)
    out["idle_share.bulk.host"] = (None if idle is None
                                   else 100.0 * idle / ((hi - lo) * 1e-9))
    patterns = [p for k in spec.kernel_names(bench_dir)
                for p in spec.kernel(bench_dir, k).EVENTS]
    out["outside_kernels_ms"] = (1e3 * spans.outside_s(inside, patterns) / n
                                 if n else None)
    by = defaultdict(float)
    for e in inside:
        by[e.name] += e.dur_ns * 1e-9
    out["top_ops"] = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.configure_compile_cache(spec.ROOT)
    devices = device.require_chips(cell.chips)
    sys.path.insert(0, str(spec.ROOT / "src"))
    out = pathlib.Path(args.out).resolve()
    line = runner.run_cell(cell, args.seed, args.seconds, True, devices,
                           T0, trace_dir=out)
    print(json.dumps(line), flush=True)
    (path,) = sorted(out.glob("plugins/profile/*/*.xplane.pb"))
    got = readings(trace.load(path), cell.bench_dir)
    stats = op_stats(path, {name for name, _ in got["top_ops"]})
    got["top_ops"] = [[name, secs, stats.get(name, {})]
                      for name, secs in got["top_ops"]]
    print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
