#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, for setting bounds.

    python3 bench/tools/spread.py DIR [SET ...]

DIR holds one file per run named run_<cell>_<set>_<seed>.out, whose last
line is the run's result.  For each cell, set and metric it prints the
median, the quartile spread, (Q3 - Q1) / median from Python's
`statistics.quantiles(values, n=4)`, and the range, (max - min) / median
with the run farthest from the median left out where two or more remain;
per cell and metric, over the named sets (default A and B), the wider
quartile spread and five times it (the bound it suggests, never under
1%), the mean of the trimmed quartile spreads, and the mean range and
widest range each over half of the metric's bound in BENCHMARK.json (a
cell whose runs read over 1 there is too noisy for that bound); and the
runs that were not correct.
"""
import collections
import json
import pathlib
import re
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def _without_farthest(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def trimmed_spread(values):
    """The quartile spread with the run farthest from the median left
    out."""
    return spread(_without_farthest(values))


def trimmed_range(values):
    """(max - min) / median, the run farthest from the median left out
    where two or more remain."""
    rest = _without_farthest(values)
    if len(rest) < 2:
        rest = values
    return (max(rest) - min(rest)) / statistics.median(values)


def bounds(root=ROOT):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"]: m["bound"] for m in spec["end_to_end"]}


def read_runs(root):
    """({cell: {metric: {set: [values]}}}, [(file, why not correct)])."""
    runs = collections.defaultdict(lambda: collections.defaultdict(dict))
    bad = []
    for path in sorted(root.glob("run_*.out")):
        m = re.match(r"run_(.+)_([A-Za-z0-9]+)_(\d+)\.out$", path.name)
        lines = path.read_text().strip().splitlines()
        if not m or not lines:
            bad.append((path.name, "no result"))
            continue
        res = json.loads(lines[-1])
        cell, set_, _seed = m.groups()
        if not res.get("correct"):
            bad.append((path.name, res.get("checks")))
        for k, v in res["metrics"].items():
            runs[cell][k].setdefault(set_, []).append(v["value"])
    return runs, bad


def summarize(runs, bound_sets, bound_of):
    """One record per cell and metric (see the module docstring)."""
    out = []
    for cell, metrics in runs.items():
        for k, sets in sorted(metrics.items()):
            rec = {"cell": cell, "metric": k}
            widest = 0.0
            for s, vals in sorted(sets.items()):
                if len(vals) >= 2:
                    sp = spread(vals)
                    if s in bound_sets:
                        widest = max(widest, sp)
                    rec[s] = {"n": len(vals),
                              "median": statistics.median(vals),
                              "spread": sp, "range": trimmed_range(vals)}
            rec["widest"] = widest
            counted = [v for s, v in sets.items()
                       if s in bound_sets and len(v) >= 3]
            if counted:
                rec["mean_trimmed"] = (sum(map(trimmed_spread, counted))
                                       / len(counted))
                ranges = [trimmed_range(v) for v in counted]
                rec["mean_range"] = sum(ranges) / len(ranges)
                if k in bound_of:
                    half = bound_of[k] / 2
                    rec["mean_range_over_half_bound"] = \
                        rec["mean_range"] / half
                    rec["widest_range_over_half_bound"] = max(ranges) / half
            rec["bound_5x"] = max(5 * widest, 0.01)
            out.append(rec)
    return out


def main() -> int:
    root = pathlib.Path(sys.argv[1])
    runs, bad = read_runs(root)
    for rec in summarize(runs, set(sys.argv[2:]) or {"A", "B"}, bounds()):
        print(json.dumps(rec))
    for name, why in bad:
        print(json.dumps({"not_correct": name, "checks": why}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
