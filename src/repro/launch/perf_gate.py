"""Perf-regression gate over the committed `results/perf` trajectory.

The benches (`benchmarks/{scoring,predictor,training,mesh}_bench.py`)
each write one JSON per scenario into `results/perf/` — committed
full-run numbers that document the perf story PR by PR.  This gate
gives that story teeth: it re-runs the benches in `--quick` mode into
a scratch dir and compares each fresh scenario against its committed
baseline, exiting non-zero when a gated metric regressed.

What is compared (and what deliberately is not):

* **ratio metrics** (`speedup_vs_*`, `parity_ratio_*`) — scale-free,
  so a quick run on different hardware is still comparable to the
  committed full run.  Gate: fresh >= baseline * (1 - tolerance).
  The default tolerance is WIDE (0.6): quick mode uses smaller models
  whose speedups are legitimately lower, and CI boxes are noisy — the
  band catches collapse-class regressions (a 2.7x speedup falling to
  ~1x), not percent-level drift.  Tighten with --tolerance for local
  investigation.
* **error metrics** (`max_abs_err`, `*_max_abs_err*`) — fresh must
  stay within max(baseline * (1 + tol), 1e-5): parity must not rot.
* **flag metrics** (`exact`, `splits_equal_vs_*`) — a True baseline
  must stay True.
* **zero metrics** (`compiles`, `binarize_calls`, `*_dispatches`) — a
  0 baseline must stay 0 (the compiled-shape / quantized-first
  contracts).
* **absolute wall/throughput numbers** (`us_per_call`, `rows_per_s`,
  `wall_s`) are NOT gated: a quick run cannot be held to full-run
  absolutes, and machine-relative numbers do not transfer.

Scenarios with a committed baseline but no fresh quick run (e.g.
`mesh-bench__k8` — quick mode only runs K in {1,4}) are reported as
skipped, not failed.

CPU only.  The gate runs each bench in a child process; on a TPU host a
parent that has touched JAX holds the chip and the child would fail or
hang, and the committed baselines are CPU-host numbers anyway.  On the
chip, run `python chip_smoke.py` (one process) instead.

  PYTHONPATH=src python -m repro.launch.perf_gate --quick --check
  # positive control / offline compare: gate pre-existing JSONs
  PYTHONPATH=src python -m repro.launch.perf_gate --check \
      --fresh-dir /tmp/fresh
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import tempfile
from typing import Any, Optional

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
BASELINE_DIR = REPO_ROOT / "results" / "perf"

# Gated metric families (see module docstring for the policy).
RATIO_PREFIXES = ("speedup_vs_", "parity_ratio_")
ERR_METRICS = ("max_abs_err", "leaf_max_abs_err_vs_seed")
FLAG_PREFIXES = ("exact", "splits_equal_vs_")
ZERO_METRICS = ("compiles", "binarize_calls", "boost_binarize_dispatches",
                "refit_histogram_dispatches")
ERR_FLOOR = 1e-5

RATIO_TOL = 0.6
ERR_TOL = 0.5

# bench key -> (module, baseline-file prefixes it produces)
BENCHES = {
    "scoring": ("benchmarks.scoring_bench", ("scoring-bench__",)),
    "predictor": ("benchmarks.predictor_bench",
                  ("predictor-bench__", "layout-sweep__")),
    "training": ("benchmarks.training_bench", ("training-bench__",)),
    "mesh": ("benchmarks.mesh_bench", ("mesh-bench__",)),
}


def classify(metric: str) -> Optional[str]:
    """Which gate family a scenario-JSON field belongs to (None = not
    gated)."""
    if metric.startswith(RATIO_PREFIXES):
        return "ratio"
    if metric in ERR_METRICS:
        return "err"
    if any(metric == p or metric.startswith(p) for p in FLAG_PREFIXES):
        return "flag"
    if metric in ZERO_METRICS:
        return "zero"
    return None


def load_dir(path: pathlib.Path) -> dict[str, dict[str, Any]]:
    """{scenario-file-stem: parsed JSON} for every *.json in `path`."""
    out = {}
    for p in sorted(pathlib.Path(path).glob("*.json")):
        try:
            out[p.stem] = json.loads(p.read_text())
        except ValueError as e:
            raise ValueError(f"unparseable scenario JSON {p}: {e}") from e
    return out


def compare(baselines: dict[str, dict], fresh: dict[str, dict], *,
            ratio_tol: float = RATIO_TOL, err_tol: float = ERR_TOL
            ) -> list[dict[str, Any]]:
    """Gate every baseline scenario against its fresh counterpart.

    Returns one row per (scenario, gated metric):
    {scenario, metric, kind, base, fresh, status, detail} with status
    in {"ok", "REGRESSION", "skipped"}.  Pure function of its inputs —
    the positive-control test injects fabricated fresh dicts here.
    """
    rows: list[dict[str, Any]] = []
    for name, base in sorted(baselines.items()):
        if name not in fresh:
            rows.append({"scenario": name, "metric": "-", "kind": "-",
                         "base": None, "fresh": None, "status": "skipped",
                         "detail": "no fresh quick run for this scenario"})
            continue
        got = fresh[name]
        for metric, bval in sorted(base.items()):
            kind = classify(metric)
            if kind is None:
                continue
            row = {"scenario": name, "metric": metric, "kind": kind,
                   "base": bval, "fresh": got.get(metric),
                   "status": "ok", "detail": ""}
            if metric not in got:
                row["status"] = "REGRESSION"
                row["detail"] = "metric missing from fresh run " \
                                "(schema break)"
                rows.append(row)
                continue
            fval = got[metric]
            if kind == "ratio":
                floor = float(bval) * (1.0 - ratio_tol)
                if float(fval) < floor:
                    row["status"] = "REGRESSION"
                    row["detail"] = (f"{fval:.3f} < {floor:.3f} "
                                     f"(= baseline {float(bval):.3f} "
                                     f"* {1 - ratio_tol:.2f})")
            elif kind == "err":
                cap = max(float(bval) * (1.0 + err_tol), ERR_FLOOR)
                if float(fval) > cap:
                    row["status"] = "REGRESSION"
                    row["detail"] = f"{fval:.3e} > cap {cap:.3e}"
            elif kind == "flag":
                if bool(bval) and not bool(fval):
                    row["status"] = "REGRESSION"
                    row["detail"] = f"baseline {metric}={bval} " \
                                    f"degraded to {fval}"
            elif kind == "zero":
                if int(bval) == 0 and int(fval) != 0:
                    row["status"] = "REGRESSION"
                    row["detail"] = f"baseline 0 grew to {fval}"
            rows.append(row)
    return rows


def run_benches(bench_keys: list[str], out_dir: pathlib.Path, *,
                quick: bool = True) -> None:
    """Run each bench as a subprocess writing scenario JSONs into
    `out_dir` (fresh interpreter per bench: jit caches and dispatch
    counters cannot leak between scenarios or from the gate itself)."""
    env_path = f"{REPO_ROOT / 'src'}:{REPO_ROOT}"
    for key in bench_keys:
        mod, _ = BENCHES[key]
        cmd = [sys.executable, "-m", mod, "--out-dir", str(out_dir)]
        if quick:
            cmd.append("--quick")
        print(f"[perf-gate] running {' '.join(cmd[1:])}", file=sys.stderr)
        proc = subprocess.run(
            cmd, cwd=REPO_ROOT, capture_output=True, text=True,
            env={**__import__('os').environ, "PYTHONPATH": env_path})
        if proc.returncode != 0:
            raise RuntimeError(
                f"bench {mod} exited {proc.returncode}:\n"
                f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


def format_report(rows: list[dict[str, Any]]) -> str:
    lines = []
    for r in rows:
        if r["status"] == "ok":
            continue
        lines.append(f"  {r['status']:<10} {r['scenario']}:{r['metric']} "
                     f"{r['detail']}")
    n_ok = sum(r["status"] == "ok" for r in rows)
    n_bad = sum(r["status"] == "REGRESSION" for r in rows)
    n_skip = sum(r["status"] == "skipped" for r in rows)
    lines.append(f"  {n_ok} gated metrics ok, {n_bad} regressions, "
                 f"{n_skip} skipped")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.perf_gate",
        description="gate fresh --quick bench runs against the "
                    "committed results/perf baselines")
    ap.add_argument("--baseline-dir", default=str(BASELINE_DIR))
    ap.add_argument("--fresh-dir", default="",
                    help="compare scenario JSONs already in this dir "
                         "instead of running the benches (positive-"
                         "control tests, offline debugging)")
    ap.add_argument("--benches", default=",".join(BENCHES),
                    help=f"comma list from {sorted(BENCHES)}")
    ap.add_argument("--quick", action="store_true",
                    help="run benches in --quick mode (the CI setting)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any regression")
    ap.add_argument("--tolerance", type=float, default=RATIO_TOL,
                    help="ratio-metric tolerance band (default "
                         f"{RATIO_TOL}; fresh >= base*(1-t))")
    ap.add_argument("--json-out", default="",
                    help="also write the full gate report here as JSON")
    args = ap.parse_args(argv)

    keys = [k.strip() for k in args.benches.split(",") if k.strip()]
    unknown = sorted(set(keys) - set(BENCHES))
    if unknown:
        ap.error(f"unknown benches {unknown}; known: {sorted(BENCHES)}")

    baselines = load_dir(pathlib.Path(args.baseline_dir))
    if not baselines:
        print(f"[perf-gate] no baselines in {args.baseline_dir}; "
              "nothing to gate", file=sys.stderr)
        return 0
    # only gate baselines the selected benches can reproduce
    prefixes = tuple(p for k in keys for p in BENCHES[k][1])
    gated = {n: b for n, b in baselines.items()
             if n.startswith(prefixes)}
    ungated = sorted(set(baselines) - set(gated))
    if ungated:
        print(f"[perf-gate] not gated (no selected bench writes them): "
              f"{ungated}", file=sys.stderr)

    if args.fresh_dir:
        fresh = load_dir(pathlib.Path(args.fresh_dir))
    else:
        with tempfile.TemporaryDirectory(prefix="perf-gate-") as td:
            run_benches(keys, pathlib.Path(td), quick=args.quick)
            fresh = load_dir(pathlib.Path(td))

    rows = compare(gated, fresh, ratio_tol=args.tolerance)
    print(format_report(rows), file=sys.stderr)
    if args.json_out:
        out = pathlib.Path(args.json_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rows, indent=1, default=str))
    regressed = any(r["status"] == "REGRESSION" for r in rows)
    if regressed:
        print("[perf-gate] REGRESSION: fresh quick run fell outside "
              "the tolerance band of the committed baselines",
              file=sys.stderr)
        return 1 if args.check else 0
    print("[perf-gate] ok: fresh quick run within tolerance of "
          "committed baselines", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
