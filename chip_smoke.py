#!/usr/bin/env python3
"""Chip smoke: the GBDT serve, score and train path on a TPU.

    python chip_smoke.py              # one chip: serve, score, train
    python chip_smoke.py --chips 4    # four chips: row- and tree-sharded
                                      # scoring against the one-chip plan

Drives the system through the entry points a user calls — `GBDTServer`
(default `PredictConfig`), `BulkScorer`, `GBDTTrainer`, and on four
chips `Predictor.sharded` / `BulkScorer(mesh=)` — at the full width of
the paper's covertype workload: 54 features, 254 borders, depth 8,
7 classes, 10,000 trees (a ~72 MB leaf table).  The model is random,
made from `--seed` over borders computed from the synthetic covertype
rows (`repro.data.synthetic`); nothing is downloaded.

Every output is checked against an independent numpy float64 oracle.
Each phase prints one JSON line (device, resolved plan, the kernel
implementation that ran per op, compile seconds, oracle errors); the
last line is ``{"ok": true, "device": {...}}``.  A phase error, an
oracle miss, or a device that is not a TPU exits non-zero without that
line.  Everything runs in this one process: a TPU belongs to one
process at a time, so nothing here starts a child.  The persistent
compile cache goes where `repro.launch.compile_cache` places it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Oracle tolerances.  Scores sum 10,000 float32 leaf values (|raw| of a
# few units); float32 accumulation in any order drifts by about
# sqrt(T) * 2^-24 * |raw| ~ 1e-5, so 1e-4 leaves a 10x margin.  A leaf
# contraction truncated to bfloat16 on the MXU errs by about
# sqrt(T) * 2^-9 * |leaf| ~ 2e-3 and fails it.
SCORE_ATOL = 1e-4
# Histogram cells hold a handful of float32 stats: exact up to a few
# ulps; bfloat16 stats would err by ~2^-9 * |g| ~ 2e-3.
HIST_ATOL = 1e-4
# Tree-sharded scores reassociate the float tree sum across shards:
# docs/distributed.md's ~1e-6 relative bound, with the test suite's 4x.
TREE_SHARD_RTOL = 4e-6
# A trained plan re-scoring its own pool must match the trainer's
# accumulated raw predictions to float32 association noise.
SERVE_DRIFT_ATOL = 1e-4

ORACLE_ROWS = 300
SCORE_ROWS = 2048
HIST_ROWS = 8192
TRAIN_ITERATIONS = 3
SHARD_ROWS = 4096


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


# --------------------------------------------------------------------------
# Model, data and the float64 oracle
# --------------------------------------------------------------------------
def covertype_setup(seed: int, n_trees: int, scale: float = 1.0):
    """(ensemble, x_all, y_all, borders, n_borders): synthetic covertype
    rows (464,800 x 54 at scale 1) and a random covertype-shaped
    ensemble over borders computed from them."""
    import jax.numpy as jnp

    from repro.configs import gbdt_workloads
    from repro.core import quantize
    from repro.core.trees import ObliviousEnsemble
    from repro.data import synthetic

    wl = gbdt_workloads.get("covertype")
    ds = synthetic.covertype(scale=scale, seed=seed)
    x = np.concatenate([ds.x_train, ds.x_test]).astype(np.float32)
    y = np.concatenate([ds.y_train, ds.y_test]).astype(np.int32)
    borders, n_borders = quantize.compute_borders(x, quantize.MAX_BINS - 1)
    nb = np.asarray(n_borders)
    rng = np.random.default_rng(seed)
    depth, n_classes = wl.params.depth, wl.n_classes
    sf = rng.integers(0, x.shape[1], (n_trees, depth)).astype(np.int32)
    sb = (1 + rng.integers(0, 1 << 30, (n_trees, depth))
          % np.maximum(nb[sf], 1)).astype(np.int32)
    lv = rng.normal(0.0, 0.01, (n_trees, 1 << depth, n_classes)).astype(
        np.float32)
    ens = ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                            jnp.asarray(lv), borders, n_borders)
    return ens, x, y, borders, n_borders


def oracle_raw(ens, x: np.ndarray) -> np.ndarray:
    """Float64 tree sum straight from the model arrays: no kernels."""
    borders = np.asarray(ens.borders, np.float64)
    sf = np.asarray(ens.split_features)
    sb = np.asarray(ens.split_bins)
    lv = np.asarray(ens.leaf_values, np.float64)
    bins = (np.asarray(x, np.float64)[:, None, :] > borders[None]).sum(1)
    go = bins[:, sf] >= sb[None]                          # (n, T, D)
    idx = (go * (1 << np.arange(sf.shape[1]))).sum(-1)    # (n, T)
    leaves = lv[np.arange(sf.shape[0])[None, :], idx]     # (n, T, C)
    return np.asarray(ens.base_score, np.float64)[None] + leaves.sum(1)


def oracle_proba(raw: np.ndarray) -> np.ndarray:
    e = np.exp(raw - raw.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)))


# --------------------------------------------------------------------------
# Instrumentation
# --------------------------------------------------------------------------
class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.seconds += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def take(self) -> dict:
        with self._lock:
            out = {"compile_s": self.seconds,
                   "cache_hits": self.cache_hits}
            self.seconds, self.cache_hits = 0.0, 0
        return out


def device_record() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def plan_record(plan) -> dict:
    c = plan.config
    return {"strategy": c.strategy, "backend": c.backend,
            "layout": c.layout, "block_n": c.block_n,
            "block_t": c.block_t}


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------
def phase_serve(ens, x_test, clock) -> tuple[dict, object]:
    from repro.kernels import registry
    from repro.serving.engine import GBDTServer

    registry.reset_call_stats()
    t0 = time.perf_counter()
    server = GBDTServer(ens)
    rows = x_test[:ORACLE_ROWS]
    want = oracle_proba(oracle_raw(ens, rows))
    # online requests through the deadline batcher, a few in flight
    n_online = 32
    with ThreadPoolExecutor(max_workers=8) as pool:
        online = list(pool.map(server.predict, rows[:n_online]))
    err = max_err(np.stack(online), want[:n_online])
    # bulk requests at several bucket sizes (the last one chunks)
    sizes = (16, 64, 200, ORACLE_ROWS)     # 300 > the top bucket
    for n in sizes:
        err = max(err, max_err(server.predict_batch(rows[:n]), want[:n]))
    snap = server.metrics.snapshot()
    server.close()
    record = {"phase": "serve", "device": device_record(),
              "plan": plan_record(server.predictor),
              "impls": registry.dispatched_impls(),
              "requests": snap["requests"], "batches": snap["batches"],
              "batch_sizes": list(sizes), "buckets": list(server.buckets),
              "recompiles": snap["recompiles"],
              "oracle_rows": ORACLE_ROWS, "oracle_max_abs_err": err,
              "tolerance": SCORE_ATOL,
              "wall_s": time.perf_counter() - t0, **clock.take()}
    check(err <= SCORE_ATOL, f"serve: oracle error {err} > {SCORE_ATOL}")
    return record, server.predictor


def phase_score(plan, x_test, clock) -> dict:
    from repro.kernels import registry
    from repro.scoring.scorer import BulkScorer, ScoreConfig
    from repro.scoring.sources import ArraySource

    registry.reset_call_stats()
    t0 = time.perf_counter()
    scorer = BulkScorer(plan, ScoreConfig(output="raw"))
    res = scorer.score(ArraySource(x_test[:SCORE_ROWS]))
    got = np.asarray(res.output)[:ORACLE_ROWS]
    err = max_err(got, oracle_raw(plan.ensemble, x_test[:ORACLE_ROWS]))
    record = {"phase": "score", "device": device_record(),
              "plan": plan_record(plan),
              "impls": registry.dispatched_impls(),
              "rows": res.n_rows, "chunk_rows": res.chunk_rows,
              "chunks": res.metrics["chunks"],
              "chunk_shapes": list(res.chunk_shapes),
              "recompiles": res.metrics.get("compiles"),
              "oracle_rows": ORACLE_ROWS, "oracle_max_abs_err": err,
              "tolerance": SCORE_ATOL,
              "wall_s": time.perf_counter() - t0, **clock.take()}
    check(err <= SCORE_ATOL, f"score: oracle error {err} > {SCORE_ATOL}")
    return record


def histogram_oracle_err(pool_bins, seed: int) -> float:
    """One deepest-level histogram (depth 8: 128 leaves at level 7,
    255 bins, 14 stats) through the registered op vs np.add.at."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed + 1)
    bins = np.asarray(pool_bins[:HIST_ROWS])
    n, f = bins.shape
    n_leaves, n_bins, n_stats = 128, 255, 14
    leaf = rng.integers(0, n_leaves, n).astype(np.int32)
    g = rng.normal(size=(n, n_stats)).astype(np.float32)
    got = ops.histogram(jnp.asarray(bins.T), jnp.asarray(leaf),
                        jnp.asarray(g), n_bins=n_bins, n_leaves=n_leaves)
    want = np.zeros((f, n_leaves * n_bins, n_stats))
    seg = leaf[None, :] * n_bins + bins.T.astype(np.int64)     # (F, n)
    for j in range(f):
        np.add.at(want[j], seg[j], g.astype(np.float64))
    return max_err(got, want)


def phase_train(x, y, borders, n_borders, seed, clock) -> dict:
    from repro.configs import gbdt_workloads
    from repro.core import quantize
    from repro.core.boosting import BoostingParams
    from repro.core.losses import make_loss
    from repro.kernels import registry
    from repro.training.gbdt import GBDTTrainer

    registry.reset_call_stats()
    t0 = time.perf_counter()
    wl = gbdt_workloads.get("covertype")
    pool = quantize.quantize_pool(x, borders)
    hist_err = histogram_oracle_err(pool.bins, seed)
    params = BoostingParams(n_trees=TRAIN_ITERATIONS, depth=wl.params.depth,
                            learning_rate=wl.params.learning_rate,
                            max_bins=quantize.MAX_BINS - 1, seed=seed)
    trainer = GBDTTrainer(make_loss(wl.loss, n_classes=wl.n_classes),
                          params)
    _, history = trainer.fit_pool(pool, y, borders=borders,
                                  n_borders=n_borders)
    loss = [float(v) for v in history["train_loss"]]
    record = {"phase": "train", "device": device_record(),
              "impls": registry.dispatched_impls(),
              "rows": int(x.shape[0]), "features": int(x.shape[1]),
              "depth": params.depth, "iterations": TRAIN_ITERATIONS,
              "train_loss": loss, "serve_drift": history["serve_drift"],
              "serve_drift_tolerance": SERVE_DRIFT_ATOL,
              "hist_oracle_rows": HIST_ROWS,
              "hist_oracle_max_abs_err": hist_err,
              "hist_tolerance": HIST_ATOL,
              "iter_p50_ms": history["metrics"]["iter_p50_ms"],
              "wall_s": time.perf_counter() - t0, **clock.take()}
    check(hist_err <= HIST_ATOL,
          f"train: histogram oracle error {hist_err} > {HIST_ATOL}")
    check(loss[-1] < loss[0], f"train: loss did not fall: {loss}")
    check(history["serve_drift"] <= SERVE_DRIFT_ATOL,
          f"train: serve drift {history['serve_drift']}")
    return record


def phase_sharded(ens, x_test, clock) -> dict:
    """Four chips: row-sharded bulk scoring (bit-exact to one chip) and
    the tree-sharded plan (within the reassociation tolerance), with
    per-device memory showing the shards on every device."""
    import jax
    from jax.sharding import AxisType

    from repro.core.predictor import Predictor
    from repro.kernels import registry
    from repro.scoring.scorer import BulkScorer, ScoreConfig
    from repro.scoring.sources import ArraySource

    registry.reset_call_stats()
    t0 = time.perf_counter()
    devices = jax.devices()
    mesh = jax.make_mesh((len(devices),), ("data",), (AxisType.Auto,),
                         devices=devices)
    plan = Predictor.build(ens)
    rows = x_test[:SHARD_ROWS]
    source = ArraySource(rows)
    one = np.asarray(BulkScorer(plan, ScoreConfig(output="raw"))
                     .score(source).output)
    err = max_err(one[:ORACLE_ROWS], oracle_raw(ens, rows[:ORACLE_ROWS]))
    by_rows = np.asarray(BulkScorer(
        plan, ScoreConfig(output="raw", shard_axis="rows"), mesh=mesh)
        .score(source).output)
    rows_exact = bool(np.array_equal(by_rows, one))
    single = np.asarray(plan.raw(rows))
    by_trees = np.asarray(plan.sharded(mesh, shard_axis="trees")(rows))
    scale = max(float(np.max(np.abs(single))), 1.0)
    tree_err = max_err(by_trees, single.astype(np.float64))
    memory = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    record = {"phase": "sharded", "device": device_record(),
              "plan": plan_record(plan),
              "impls": registry.dispatched_impls(),
              "rows": SHARD_ROWS, "mesh": dict(mesh.shape),
              "rows_bit_exact": rows_exact,
              "trees_max_abs_err": tree_err,
              "trees_tolerance": TREE_SHARD_RTOL * scale,
              "oracle_max_abs_err": err, "tolerance": SCORE_ATOL,
              "bytes_in_use_per_device": memory,
              "wall_s": time.perf_counter() - t0, **clock.take()}
    check(err <= SCORE_ATOL, f"sharded: oracle error {err}")
    check(rows_exact, "sharded: row-sharded scores differ from one chip")
    check(tree_err <= TREE_SHARD_RTOL * scale,
          f"sharded: tree-sharded error {tree_err}")
    check(all(m for m in memory), f"sharded: a device holds nothing: "
          f"{memory}")
    return record


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-scoring phase, on a "
                         "4-device mesh")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.configure()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs on the chip "
              "only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import gbdt_workloads

    clock = CompileClock()
    n_trees = gbdt_workloads.get("covertype").paper_iterations
    ens, x, y, borders, n_borders = covertype_setup(args.seed, n_trees)
    x_test = x[len(x) - len(x) // 4:]
    emit({"phase": "setup", "device": device_record(),
          "compile_cache": cache_dir, "trees": n_trees,
          "depth": ens.depth, "features": ens.n_features,
          "borders": int(ens.borders.shape[0]),
          "classes": ens.n_outputs, "rows": int(x.shape[0])})
    try:
        if args.chips == 4:
            emit(phase_sharded(ens, x_test, clock))
        else:
            serve, plan = phase_serve(ens, x_test, clock)
            emit(serve)
            emit(phase_score(plan, x_test, clock))
            emit(phase_train(x, y, borders, n_borders, args.seed, clock))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = device_record()
    emit({"ok": True, "device": {"platform": d["platform"],
                                 "kind": d["kind"], "count": d["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
