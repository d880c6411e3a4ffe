"""One run of one cell: set-up, measured window, checks, result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Stdout's last line is the result object; earlier stdout lines describe
the run (device, plan, kernels that ran, compiles in set-up and in the
window).  Stderr's last lines are the compared numbers beside their
limits.  Without the chips the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Optional

from harness import device, spec
from harness.trace import Summary
from harness.work import Run, dims

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


@dataclasses.dataclass
class Context:
    """What a client gets: the cell, the run's arguments and clocks."""
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    t_process0: float               # time.perf_counter() at process start
    clock: Any                      # device.CompileClock
    workdir: pathlib.Path           # scratch under TMPDIR, removed after
    emit: Callable[[dict], None]    # an earlier stdout line
    trace_dir: Optional[pathlib.Path] = None   # keep the trace here

    def trace_out(self) -> pathlib.Path:
        return self.trace_dir or self.workdir / "trace"


@dataclasses.dataclass
class Check:
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a client returns."""
    e2e: dict                        # end-to-end metric -> value
    counters: dict                   # the program's counters and spans
    checks: dict                     # name -> Check
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Optional[Summary] = None
    traced_rows: int = 0


def emit_stdout(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


def per_layer(cell: spec.Cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(cell, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(cell: spec.Cell, outcome: Outcome, devices, trace: bool,
           run: Run) -> dict:
    correct = outcome.failed == 0 and all(c.ok for c in
                                          outcome.checks.values())
    if trace:
        metrics = per_layer(cell, run)
    else:
        metrics = {m["name"]: {"value": float(outcome.e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    dev = {**device.record(devices),
           "memory_peak_bytes": outcome.memory_peak_bytes}
    line: dict[str, Any] = {"correct": correct,
                            "attempted": outcome.attempted,
                            "failed": outcome.failed,
                            "metrics": metrics, "device": dev}
    if trace and outcome.trace is not None:
        dev["busy_s"] = outcome.trace.busy_s
        dev["window_s"] = outcome.trace.window_s
        line["breakdown"] = {"device_ops": outcome.trace.top_ops(10),
                             "idle_gaps": [list(g) for g in
                                           outcome.trace.gaps[:10]]}
    line["checks"] = {k: {"value": c.value, "limit": c.limit}
                      for k, c in outcome.checks.items()}
    return line


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, t_process0: float,
             trace_dir: Optional[pathlib.Path] = None) -> dict:
    """Everything after the look for a chip; returns the result line."""
    if not 0 < seconds:
        raise ValueError("--seconds must be positive")
    clock = device.CompileClock()
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        ctx = Context(cell, seed, seconds, trace, t_process0, clock,
                      workdir, emit_stdout, trace_dir)
        outcome = spec.client(cell).run(ctx)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    pk = spec.peaks(cell.bench_dir, devices[0].device_kind) \
        if devices[0].platform == "tpu" else {}
    run = Run(cell.bench_dir, dims(cell.config), pk, outcome.e2e,
              outcome.counters, outcome.trace, outcome.traced_rows)
    return result(cell, outcome, devices, trace, run)


def main(argv=None, t_process0: Optional[float] = None) -> int:
    t_process0 = time.perf_counter() if t_process0 is None else t_process0
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    device.configure_compile_cache(spec.ROOT)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    sys.path.insert(0, str(spec.ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the system under test is missing: {e}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices, t_process0)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
