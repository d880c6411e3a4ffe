"""Bulk sweep: whole passes of `BulkScorer` over the configuration's table.

Set-up writes the table as a .npy under the run's scratch directory,
builds the default `Predictor` plan, reads every page of the table once
through the window's `NpyMemmapSource`, and scores the last full chunk
and the tail chunk through that source, the window's scorer and an
`NpySink`, so that every shape the sweeps use is compiled and the first
timed sweep pays nothing the later ones do not.  The window then starts
whole sweeps, `NpyMemmapSource` -> `BulkScorer.score` -> `NpySink` (one
output file per sweep), until `--seconds` have passed.  Its stdout line
gives each sweep's seconds and rows (`sweep_s`, `sweep_rows`), and the
part of each sweep spent in the scorer's chunk loop (`sweep_chunks_s`),
so that a slow sweep shows and where its time went.

    rows_per_s   rows written to the sinks / (end of the last sweep that
                 started in the window - window start)

Traffic parameters: "output" (the scorer's output entry) and
"check_rows" (rows compared with the reference in every sweep's output).
With --trace 1 one more sweep runs under the profiler.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import data, device, model, program, reference, trace
from harness.runner import Check, Outcome


TOUCH_ROWS = 16384        # rows read at a time to fault the table in


def _sweep(scorer, source, sink_path, resume_from=0):
    import jax

    from repro.scoring.sinks import NpySink

    with jax.profiler.TraceAnnotation("bench.scoring.score"):
        return scorer.score(source, NpySink(sink_path),
                            resume_from=resume_from)


def _warm(scorer, source, chunk, sink_path) -> None:
    """The timed path once over its last two chunks (a full one and
    the tail), after every page of the source has been read once."""
    from repro.scoring.scorer import plan_chunks

    n = source.n_rows
    for s in range(0, n, TOUCH_ROWS):
        source.read(s, min(s + TOUCH_ROWS, n)).sum()
    spans = plan_chunks(n, chunk)
    _sweep(scorer, source, sink_path, resume_from=max(len(spans) - 2, 0))


def run(ctx) -> Outcome:
    import jax

    from repro.core.predictor import Predictor
    from repro.scoring.scorer import BulkScorer, ScoreConfig
    from repro.scoring.sources import NpyMemmapSource

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    x, _ = data.generate(cfg, ctx.seed)
    mdl = model.random_ensemble(cfg, x, ctx.seed)
    n = x.shape[0]
    rows_path = ctx.workdir / "rows.npy"
    np.save(rows_path, x)
    check = data.sample_rows(n, int(tr["check_rows"]), ctx.seed)
    x_check = x[check].copy()

    plan = Predictor.build(model.to_program(mdl))
    source = NpyMemmapSource(rows_path)
    scorer = BulkScorer(plan, ScoreConfig(output=tr["output"]))
    chunk = scorer.resolve_chunk_rows(n)
    del x
    t_warm = time.perf_counter()
    _warm(scorer, source, chunk, ctx.workdir / "warm.npy")
    warm_s = time.perf_counter() - t_warm
    devices = jax.devices()[:ctx.cell.chips]

    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process0
    ctx.emit({"phase": "setup", "device": device.record(devices),
              "plan": program.plan_record(plan), "impls": program.impls(),
              "rows": n, "chunk_rows": chunk, "setup_s": setup_s,
              "warm_s": warm_s,
              **ctx.clock.take()})

    outs, sweeps, ends = [], [], [t0]
    while not sweeps or ends[-1] < t0 + ctx.seconds:
        outs.append(ctx.workdir / f"out{len(outs)}.npy")
        sweeps.append(_sweep(scorer, source, outs[-1]))
        ends.append(time.perf_counter())
    t_end = ends[-1]
    in_window = ctx.clock.take()
    rows = sum(r.n_rows for r in sweeps)
    chunks = sum(r.metrics["chunks"] for r in sweeps)
    ctx.emit({"phase": "window", "sweeps": len(sweeps), "rows": rows,
              "seconds": t_end - t0,
              "sweep_s": [b - a for a, b in zip(ends, ends[1:])],
              "sweep_rows": [r.n_rows for r in sweeps],
              "sweep_chunks_s": [r.metrics["wall_s"] for r in sweeps],
              "compiles_in_window": in_window["compiles"]
              + in_window["cache_hits"], **in_window})

    summary = None
    if ctx.trace:
        outs.append(ctx.workdir / "out_traced.npy")
        with trace.capture(ctx.trace_out()) as found:
            _sweep(scorer, source, outs[-1])
        summary = trace.summarize(trace.load(found[0]))
    peak = device.memory_peak_bytes(devices)
    del scorer, plan, source
    gc.collect()

    want = reference.proba(reference.raw_f64(mdl, x_check))
    err, bad = 0.0, 0
    for path in outs:
        got = np.load(path, mmap_mode="r")
        err = max(err, reference.max_abs_err(got[check], want))
        bad += reference.bad_rows(got)
        path.unlink()
    return Outcome(
        e2e={"rows_per_s": rows / (t_end - t0), "setup_s": setup_s},
        counters={"chunk_rows": chunk,
                  "quantize_s": sum(r.metrics["quantize_s"]
                                    for r in sweeps),
                  "chunks": chunks, "rows_per_s": rows / (t_end - t0)},
        checks={"proba_max_abs_err": Check(
                    err, float(cfg["limits"]["proba_max_abs_err"])),
                "bad_rows": Check(bad, 0)},
        attempted=rows + (n if ctx.trace else 0), failed=bad, memory_peak_bytes=peak,
        trace=summary, traced_rows=n if ctx.trace else 0)
