"""Bulk sweep over a model of non-symmetric trees (a node table).

The window, the warm-up and the checks of `bulk.py` (whose `_sweep` and
`_warm` run here), over `model_nodes.random_node_ensemble` scored by the
program's default plan for a `NodeEnsemble`, and checked against
`reference_nodes`, a float64 walk of the same trees.  Besides the bulk
counters it hands the readers the table's logical sizes,
`nodes_per_tree` and `leaves_per_tree`, which `work.dims` does not
carry.

    rows_per_s   rows written to the sinks / (end of the last sweep that
                 started in the window - window start)

Traffic parameters: as `bulk.py`'s.
"""
from __future__ import annotations

import gc
import pathlib
import time

import numpy as np

from harness import (data, device, model_nodes, program, reference,
                     reference_nodes, spec, trace)
from harness.runner import Check, Outcome

bulk = spec.load_module(pathlib.Path(__file__).with_name("bulk.py"),
                        "bench_client_bulk")


def run(ctx) -> Outcome:
    import jax

    # the program's node-table type first: a program without it fails
    # here, in seconds, before any data is made
    from repro.core.trees import NodeEnsemble  # noqa: F401
    from repro.core.predictor import Predictor
    from repro.scoring.scorer import BulkScorer, ScoreConfig
    from repro.scoring.sources import NpyMemmapSource

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    x, _ = data.generate(cfg, ctx.seed)
    mdl = model_nodes.random_node_ensemble(cfg, x, ctx.seed)
    n = x.shape[0]
    rows_path = ctx.workdir / "rows.npy"
    np.save(rows_path, x)
    check = data.sample_rows(n, int(tr["check_rows"]), ctx.seed)
    x_check = x[check].copy()

    plan = Predictor.build(model_nodes.to_program(mdl))
    source = NpyMemmapSource(rows_path)
    scorer = BulkScorer(plan, ScoreConfig(output=tr["output"]))
    chunk = scorer.resolve_chunk_rows(n)
    del x
    t_warm = time.perf_counter()
    bulk._warm(scorer, source, chunk, ctx.workdir / "warm.npy")
    warm_s = time.perf_counter() - t_warm
    devices = jax.devices()[:ctx.cell.chips]

    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process0
    ctx.emit({"phase": "setup", "device": device.record(devices),
              "plan": {**program.plan_record(plan),
                       "lowered": plan.describe().get("lowered")},
              "impls": program.impls(), "rows": n, "chunk_rows": chunk,
              "setup_s": setup_s, "warm_s": warm_s, **ctx.clock.take()})

    outs, sweeps, ends = [], [], [t0]
    while not sweeps or ends[-1] < t0 + ctx.seconds:
        outs.append(ctx.workdir / f"out{len(outs)}.npy")
        sweeps.append(bulk._sweep(scorer, source, outs[-1]))
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
            bulk._sweep(scorer, source, outs[-1])
        summary = trace.summarize(trace.load(found[0]))
    peak = device.memory_peak_bytes(devices)
    del scorer, plan, source
    gc.collect()

    want = reference.proba(reference_nodes.raw_f64(mdl, x_check))
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
                  "chunks": chunks, "rows_per_s": rows / (t_end - t0),
                  "nodes_per_tree": int(mdl["node_left"].shape[1]),
                  "leaves_per_tree": int(mdl["leaf_values"].shape[1])},
        checks={"proba_max_abs_err": Check(
                    err, float(cfg["limits"]["proba_max_abs_err"])),
                "bad_rows": Check(bad, 0)},
        attempted=rows + (n if ctx.trace else 0), failed=bad,
        memory_peak_bytes=peak, trace=summary,
        traced_rows=n if ctx.trace else 0)
