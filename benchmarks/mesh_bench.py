"""Mesh weak-scaling benchmark: row-sharded pool scoring vs devices.

The paper parallelizes prediction across cores by blocking the document
axis per OpenMP thread; the JAX analog is `Predictor.sharded` over a
device mesh, each shard scoring its own (N/K, F) row panel through the
full registry pipeline.  This bench measures that weak scaling on
*virtual* host devices (``--xla_force_host_platform_device_count``):
one subprocess per device count K — XLA pins the device count at first
init, so every K needs a fresh process — timing the same prequantized
bulk scenario the scoring bench gates (quantize once, score many; the
score calls are pure u8 kernel work, no binarize).

Even on a single physical core the K=4 mesh wins: each shard's bins
panel and per-shard intermediates fit the last-level cache, where the
unsharded call streams the full panel through memory per pipeline
stage — the same cache-blocking effect the paper engineers per core.
The committed JSONs (results/perf/mesh-bench__k*.json) pin that curve,
and ``--check`` gates exact parity (sharded == single-device, bit for
bit) plus >= 1.5x at K=4 vs K=1.

CPU only: the one-process-per-K design needs virtual host devices.  A
TPU belongs to one process at a time, so children started here could
not reach the chip; `python chip_smoke.py --chips 4` exercises the
sharded paths on a real four-chip host, in one process.

  PYTHONPATH=src python -m benchmarks.mesh_bench [--quick] [--check]
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[1] / "results" / "perf"
REPO = pathlib.Path(__file__).resolve().parents[1]

# The bulk-prequant scenario: multiclass model at covertype-like dims
# (54 features, 7 classes, 254 borders — the paper's evaluation
# dataset family), 100 trees of depth 6, 16384 rows.  16384 keeps each
# K=4 shard's working set (4096 rows x 54 u8 bins + staged
# intermediates) inside the last-level cache — the blocking win this
# bench exists to measure; much larger N pushes even the per-shard
# panel past the cache and the curve flattens.
SCENARIO = dict(n_trees=100, depth=6, n_features=54, n_borders=254,
                n_outputs=7, n_rows=16384)

WORKER = """
import os
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count={k}"
import json
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from repro.core.predictor import PredictConfig, Predictor
from repro.core.trees import ObliviousEnsemble
from repro.kernels import registry

T, D, F, B, C, N = {t}, {d}, {f}, {b}, {c}, {n}
rng = np.random.default_rng(42)
sf = rng.integers(0, F, size=(T, D)).astype(np.int32)
sb = rng.integers(1, B + 1, size=(T, D)).astype(np.int32)
lv = rng.normal(size=(T, 1 << D, C)).astype(np.float32)
borders = np.sort(rng.normal(size=(B, F)).astype(np.float32), axis=0)
ens = ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                        jnp.asarray(lv), jnp.asarray(borders),
                        jnp.asarray(np.full((F,), B, np.int32)))
x = rng.normal(size=(N, F)).astype(np.float32)

plan = Predictor.build(ens, PredictConfig(strategy="staged",
                                          backend="ref", layout="soa"))
pool = plan.quantize(x)                      # once, outside the loop
ref = np.asarray(plan.raw(pool))             # single-device reference
mesh = jax.make_mesh(({k},), ("data",), (AxisType.Auto,))
fn = plan.sharded(mesh)

registry.reset_call_stats()
for _ in range({warmup}):
    fn(pool).block_until_ready()
walls = []
for _ in range({reps}):
    t0 = time.perf_counter()
    fn(pool).block_until_ready()
    walls.append(time.perf_counter() - t0)
n_binarize = sum(v for key, v in registry.call_stats().items()
                 if key[0].startswith("binarize"))
wall = float(np.median(walls))
print(json.dumps({{
    "k": {k}, "wall_s": wall, "rows_per_s": N / wall,
    "exact": bool((np.asarray(fn(pool)) == ref).all()),
    "binarize_calls": n_binarize,
    "layout": plan.config.layout,
}}))
"""


def eprint(*a):
    print(*a, file=sys.stderr, flush=True)


def run_worker(k: int, warmup: int, reps: int) -> dict:
    body = WORKER.format(k=k, warmup=warmup, reps=reps,
                         t=SCENARIO["n_trees"], d=SCENARIO["depth"],
                         f=SCENARIO["n_features"],
                         b=SCENARIO["n_borders"],
                         c=SCENARIO["n_outputs"], n=SCENARIO["n_rows"])
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    if out.returncode != 0:
        raise RuntimeError(f"K={k} worker failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _write_scenario_json(out_dir: pathlib.Path, name: str, scenario: str,
                         fields: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    payload = {
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "scenario": scenario,
        "layout": "soa",
        **fields,
    }
    (out_dir / f"{name}.json").write_text(json.dumps(payload, indent=1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="K in {1,4} and fewer timed reps")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless every K matched the "
                         "single-device reference exactly, dispatched "
                         "zero binarize calls, and K=4 cleared 1.5x")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device counts (overrides "
                         "quick/full defaults)")
    ap.add_argument("--reps", type=int, default=0,
                    help="timed reps per K (0 = 9 quick / 15 full)")
    ap.add_argument("--out-dir", default=str(RESULTS_DIR))
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args()

    if args.devices:
        ks = [int(s) for s in args.devices.split(",")]
    else:
        ks = [1, 4] if args.quick else [1, 2, 4, 8]
    reps = args.reps or (9 if args.quick else 15)
    warmup = 3

    s = SCENARIO
    eprint(f"# mesh bench: bulk-prequant, {s['n_rows']} rows x "
           f"{s['n_features']} features, {s['n_trees']} trees depth "
           f"{s['depth']}, {s['n_outputs']} classes, soa/staged/ref; "
           f"host devices K={ks}, {warmup} warmup + {reps} timed "
           f"(median), one subprocess per K")

    results = {}
    for k in ks:
        results[k] = run_worker(k, warmup, reps)
        r = results[k]
        eprint(f"K={k}: {r['rows_per_s']:10.0f} rows/s  "
               f"wall={r['wall_s'] * 1e3:7.2f} ms  "
               f"exact={r['exact']}  binarize_calls="
               f"{r['binarize_calls']}")

    base = results[min(ks)]["rows_per_s"]
    print("name,us_per_call,derived")
    for k in ks:
        r = results[k]
        speedup = r["rows_per_s"] / base
        r["speedup_vs_k1"] = speedup
        print(f"mesh/k{k},{r['wall_s'] * 1e6:.1f},"
              f"rows_per_s={r['rows_per_s']:.0f};"
              f"speedup_vs_k1={speedup:.2f};exact={int(r['exact'])}")

    if not args.no_write:
        out_dir = pathlib.Path(args.out_dir)
        common = {**SCENARIO, "warmup": warmup, "reps": reps,
                  "backend": "ref", "quick": bool(args.quick)}
        for k in ks:
            _write_scenario_json(
                out_dir, f"mesh-bench__k{k}", "mesh-bulk-prequant",
                {**common, "devices": k, **results[k]})
        eprint(f"# wrote result JSONs to {out_dir}")

    if args.check:
        for k in ks:
            if not results[k]["exact"]:
                eprint(f"FAIL: K={k} sharded output diverges from the "
                       "single-device reference (row sharding must be "
                       "bit-exact)")
                return 1
            if results[k]["binarize_calls"]:
                eprint(f"FAIL: K={k} pool scoring dispatched "
                       f"{results[k]['binarize_calls']} binarize calls "
                       "(the prequantized path must dispatch zero)")
                return 1
        if 4 in results and 1 in results:
            ratio = results[4]["rows_per_s"] / results[1]["rows_per_s"]
            if ratio < 1.5:
                eprint(f"FAIL: K=4 weak scaling {ratio:.2f}x is below "
                       "the 1.5x gate vs K=1")
                return 1
            eprint(f"# weak-scaling gate: K4/K1 = {ratio:.2f}x >= 1.5x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
