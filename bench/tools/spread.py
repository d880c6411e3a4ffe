#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, for setting bounds.

    python3 bench/tools/spread.py DIR [SET ...]

DIR holds one file per run named run_<cell>_<set>_<seed>.out, whose last
line is the run's result.  For each cell, set and metric it prints the
median and the quartile spread, (Q3 - Q1) / median from Python's
`statistics.quantiles(values, n=4)`; per cell and metric, the wider of
the spreads of the named sets (default A and B) and five times it (the
bound it suggests, never under 1%); and the runs that were not correct.
"""
import collections
import json
import pathlib
import re
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values):
    """The spread with the run farthest from the median left out."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])


def main() -> int:
    root = pathlib.Path(sys.argv[1])
    bound_sets = set(sys.argv[2:]) or {"A", "B"}
    runs = collections.defaultdict(lambda: collections.defaultdict(dict))
    bad = []
    for path in sorted(root.glob("run_*.out")):
        m = re.match(r"run_(.+)_([A-Za-z0-9]+)_(\d+)\.out$", path.name)
        lines = path.read_text().strip().splitlines()
        if not m or not lines:
            bad.append((path.name, "no result"))
            continue
        res = json.loads(lines[-1])
        cell, set_, seed = m.groups()
        if not res.get("correct"):
            bad.append((path.name, res.get("checks")))
        for k, v in res["metrics"].items():
            runs[cell][k].setdefault(set_, []).append(v["value"])
    for cell, metrics in runs.items():
        for k, sets in sorted(metrics.items()):
            out = {"cell": cell, "metric": k}
            widest = 0.0
            for s, vals in sorted(sets.items()):
                if len(vals) >= 2:
                    sp = spread(vals)
                    if s in bound_sets:
                        widest = max(widest, sp)
                    out[s] = {"n": len(vals),
                              "median": statistics.median(vals),
                              "spread": sp}
            out["widest"] = widest
            trimmed = [trimmed_spread(v) for k, v in sets.items()
                       if k in bound_sets and len(v) >= 3]
            if trimmed:
                out["mean_trimmed"] = sum(trimmed) / len(trimmed)
            out["bound_5x"] = max(5 * widest, 0.01)
            print(json.dumps(out))
    for name, why in bad:
        print(json.dumps({"not_correct": name, "checks": why}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
