#!/usr/bin/env python3
"""Run one cell with --trace 1, keep its profiler trace under OUT, and
print what the trace holds: planes, lines, and per line the event names
with their count and total time.  This is how the event names in
bench/kernels/*.py were read off a real trace.

    python3 bench/tools/inspect_trace.py --workload covertype-bulk \
        --seed 5 --seconds 5 --out traces/covertype-bulk
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

from harness import device, runner, spec  # noqa: E402


def describe(path: pathlib.Path, top: int = 25) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name} lines={[ln.name for ln in lines]}")
        for line in lines:
            by = defaultdict(lambda: [0, 0.0, None, None])
            for e in line.events:
                b = by[e.name]
                b[0] += 1
                b[1] += e.duration_ns
                b[2] = e.start_ns if b[2] is None else min(b[2], e.start_ns)
                b[3] = dict(e.stats) if b[3] is None else b[3]
            rows = sorted(by.items(), key=lambda kv: -kv[1][1])[:top]
            print(f"  LINE {line.name!r} distinct={len(by)}")
            for name, (n, ns, first, stats) in rows:
                st = {k: str(v)[:120] for k, v in (stats or {}).items()}
                print(f"    {n:7d} {ns / 1e6:12.3f} ms first={first} "
                      f"{name[:100]!r} {json.dumps(st)[:300]}")


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
    try:
        line = runner.run_cell(cell, args.seed, args.seconds, True, devices,
                               T0, trace_dir=out)
        print(json.dumps(line), flush=True)
    except Exception as e:              # noqa: BLE001 - still show the trace
        print(f"run failed: {e!r}", flush=True)
    for p in sorted(out.glob("plugins/profile/*/*.xplane.pb")):
        describe(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())
