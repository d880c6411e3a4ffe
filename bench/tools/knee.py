#!/usr/bin/env python3
"""Sweep the offered rate of an online traffic mix once, to find the
knee: the highest rate at which replies keep pace with arrivals and the
backlog does not grow.

    python3 bench/tools/knee.py --workload covertype-online --seed 3 \
        --seconds 6 --rates 2000,4000,8000

One process, one server: set-up and compiles are paid once.  For each
rate it prints offered and answered rates, p50/p99 and the median
latency of the first and last fifth of the requests (a growing backlog
shows as the last fifth waiting far longer); it stops after two rates
that do not keep pace, and prints the knee last.
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import data, device, model, spec  # noqa: E402


def keeps_pace(rec: dict) -> bool:
    """Replies keep pace with arrivals and the backlog does not grow:
    the window drains within a second of its end, and the last fifth
    of the requests waits no more than twice as long (plus 5 ms) as the
    first fifth."""
    return (rec["answered_per_s"] >= 0.97 * rec["offered_per_s"]
            and rec["drain_s"] < 1.0
            and rec["last_fifth_p50_ms"]
            <= 2 * rec["first_fifth_p50_ms"] + 5.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.configure_compile_cache(spec.ROOT)
    devices = device.require_chips(cell.chips)
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.serving.engine import GBDTServer

    drv = spec.client(cell)
    knee, misses = None, 0
    x, _ = data.generate(cell.config, args.seed)
    server = GBDTServer(model.to_program(
        model.random_ensemble(cell.config, x, args.seed)))
    try:
        for b in server.buckets:
            server.predict_batch(x[:b])
        print(json.dumps({"device": device.record(devices),
                          "block_n": server.predictor.config.block_n,
                          "block_t": server.predictor.config.block_t}),
              flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            tr = {**cell.traffic, "rate_per_s": rate}
            m0 = server.metrics.snapshot()
            t0 = time.perf_counter()
            _, lat, late, _ = drv._offer(server.batcher, x, tr,
                                         args.seconds, args.seed, 1)
            wall = time.perf_counter() - t0
            m1 = server.metrics.snapshot()
            ok = lat[~np.isnan(lat)]
            fifth = max(len(lat) // 5, 1)
            rec = {"rate": rate, "offered_per_s": len(lat) / args.seconds,
                   "answered_per_s": len(ok) / wall,
                   "drain_s": wall - args.seconds,
                   "p50_ms": float(np.percentile(ok, 50) * 1e3),
                   "p99_ms": float(np.percentile(ok, 99) * 1e3),
                   "first_fifth_p50_ms": float(np.nanmedian(lat[:fifth])
                                               * 1e3),
                   "last_fifth_p50_ms": float(np.nanmedian(lat[-fifth:])
                                              * 1e3),
                   "send_late_p99_ms": float(np.nanpercentile(late, 99)
                                             * 1e3),
                   "rows_per_batch": (m1["requests"] - m0["requests"])
                   / max(m1["batches"] - m0["batches"], 1)}
            print(json.dumps(rec), flush=True)
            if keeps_pace(rec):
                knee = rate
            elif rec["rate"] > (knee or 0):
                misses += 1
                if misses == 2:
                    break
    finally:
        server.close()
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
