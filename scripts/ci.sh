#!/usr/bin/env bash
# CI smoke: tier-1 tests + the import-time-sensitive entrypoints.
#
# The failure class this guards against: an import that works on one jax
# version and not the pinned one (e.g. `from jax import shard_map`)
# breaks the *entire* suite at collection.  Importing every package
# module first localizes such a break to one line of output.
#
#   bash scripts/ci.sh          # full tier-1 run
#   CI_QUICK=1 bash scripts/ci.sh   # skip the slow learning tests
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# The quick benches below write their scenario JSONs here instead of
# results/perf (which stays the committed full-run trajectory); the
# perf-regression gate at the end compares this dir against the
# committed baselines — reusing the runs CI does anyway.
PERF_FRESH="$(mktemp -d)"
trap 'rm -rf "$PERF_FRESH"' EXIT

echo "== import check (every repro module) =="
python - <<'EOF'
import importlib, pathlib, pkgutil, sys

import repro
failures = []
for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
    try:
        importlib.import_module(mod.name)
    except Exception as e:          # noqa: BLE001 — report, keep walking
        failures.append((mod.name, repr(e)))
for name, err in failures:
    print(f"IMPORT FAIL {name}: {err}", file=sys.stderr)
sys.exit(1 if failures else 0)
EOF

echo "== tier-1 pytest =="
if [[ "${CI_QUICK:-0}" == "1" ]]; then
    python -m pytest -x -q tests/test_serving.py tests/test_kernels.py \
        tests/test_kernel_blocks.py tests/test_sharding.py \
        tests/test_quantized.py
else
    python -m pytest -x -q
fi

echo "== differential kernel harness (full registry capability matrix) =="
# every (op x impl x layout x bin-dtype) cell of registry.table(),
# enumerated at collection time, vs the ref oracle — its own step so a
# kernel/oracle divergence is named before the broader suite output
python -m pytest -x -q tests/test_differential.py

echo "== kernel contract checker (static capability/dtype/VMEM lints) =="
# abstract-traces the full registry matrix (no execution, no compiles)
# and fails on any unsuppressed contract violation: uint8 widening,
# bitpacked float excursions, VMEM working sets past the tuning
# models, plan transfer/retrace hygiene, capability claims.
# --no-write keeps the committed results/analysis/ artifact.
python -m repro.launch.analyze --check --no-write >/dev/null

echo "== kernel registry smoke (introspection surface) =="
python -c "from repro.kernels import registry; rows = registry.table(); \
  assert all(any(r['op'] == op for r in rows) for op in registry.CORE_OPS); \
  print(registry.format_table())"

echo "== layout capability smoke (every layout covers the ops it claims) =="
python - <<'EOF'
from repro.core import layout
from repro.kernels import registry

for name, spec in layout.LAYOUTS.items():
    for op in spec.claimed_ops:
        impls = registry.impls_for_layout(op, name)
        assert impls, f"layout {name} claims op {op} but no impl consumes it"
# the integer bitpacked pipeline must keep its own structure kernels
assert registry.impls_for_layout("leaf_index", "bitpacked"), \
    "bitpacked lost its leaf_index impls"
assert registry.impls_for_layout("fused_predict", "bitpacked"), \
    "bitpacked lost its fused_predict impls"
assert "layouts" in registry.format_table().splitlines()[0]
print(layout.format_layout_table())
EOF

echo "== quickstart example =="
python examples/quickstart.py

echo "== serving benchmark (quick) =="
python -m benchmarks.serving_bench --quick >/dev/null

echo "== scoring smoke (BulkScorer end-to-end via launch/score.py) =="
# small synthetic dataset through the bulk-scoring CLI; --check verifies
# the streamed output against the one-shot Predictor path bit-for-bit
python -m repro.launch.score --dataset covertype --scale 0.002 \
    --trees 10 --chunk 256 --strategy staged --backend ref \
    --check >/dev/null

echo "== scoring benchmark (quick, parity + chunk-shape + throughput gate) =="
# --check fails the build unless BulkScorer output matches the naive
# predict_batch loop exactly, every bulk run compiled <= 2 chunk
# shapes, and the best scorer beats the naive loop (1.2x floor in
# quick mode).  --out-dir diverts the scenario JSONs to the perf-gate
# scratch dir (the committed results/perf/ JSONs stay untouched).
python -m benchmarks.scoring_bench --quick --check \
    --out-dir "$PERF_FRESH" >/dev/null

echo "== train smoke (streamed source -> GBDTTrainer -> exact serve parity) =="
# --check fails unless serve parity is EXACT (0.0), boosting performed
# zero binarize dispatches, histogram dispatches stayed <= depth, the
# source exceeded one chunk (genuinely out-of-core), and the train loss
# decreased
python -m repro.launch.train_gbdt --dataset covertype --scale 0.002 \
    --repeat 2 --trees 6 --depth 3 --chunk 512 --max-bins 32 \
    --backend ref --check >/dev/null

echo "== training benchmark (quick: seed-float vs pool vs streamed) =="
# --check fails unless the pool path reproduces the seed float scan to
# the leaf-value level, streamed == pool, and a warmed pool refit
# performs zero new histogram dispatches (compiled-shape contract)
python -m benchmarks.training_bench --quick --check \
    --out-dir "$PERF_FRESH" >/dev/null

echo "== predictor smoke benchmark (prepared / prequantized / registry / layouts) =="
# --check fails the build if the prepared-plan path is below parity
# with the kwarg path it replaced, if a quantized scenario
# (prepared+prequantized vs prepared-float, quantize-once score-many
# over ModelRegistry) diverges from its float path (ref backend, so
# same kernel math), or if any lowered layout (all four: soa /
# depth_major / depth_grouped / bitpacked swept over a mixed-depth
# ensemble) diverges from the jnp reference — the layout parity gate.
# --out-dir diverts this run's JSONs to the perf-gate scratch dir so
# the committed results/perf/ trajectory is not clobbered.
python -m benchmarks.predictor_bench --quick --check \
    --out-dir "$PERF_FRESH" >/dev/null

echo "== mesh smoke (sharded parity tests + weak-scaling gate) =="
# row-sharded pool/float predict must match single-device bit-for-bit
# on every layout with zero binarize dispatches, tree-sharded psum to
# reassociated-float tolerance, and K x R registry replicas must route;
# the tests force 4 host devices in their own subprocesses, so no
# XLA_FLAGS leaks into this shell
python -m pytest -x -q tests/test_distributed_gbdt.py
# weak-scaling gate: one subprocess per device count, exact parity at
# every K and >= 1.5x rows/s at K=4 vs K=1 on the prequantized bulk
# scenario.  --out-dir diverts the JSONs to the perf-gate scratch dir.
python -m benchmarks.mesh_bench --quick --check \
    --out-dir "$PERF_FRESH" >/dev/null

echo "== observability smoke (span tracer + metrics hub end to end) =="
# a tiny bulk-scoring run with --trace-out/--metrics-out, then assert
# the Chrome trace parses and contains the span taxonomy CI depends on
# (compile/<entry> instants and the bulk/* stage spans) and the metrics
# export carries the scoring snapshot
OBS_TRACE="$PERF_FRESH/obs-trace.json"
OBS_METRICS="$PERF_FRESH/obs-metrics.json"
python -m repro.launch.score --dataset covertype --scale 0.002 \
    --trees 10 --chunk 256 --strategy staged --backend ref \
    --trace-out "$OBS_TRACE" --metrics-out "$OBS_METRICS" >/dev/null
python - "$OBS_TRACE" "$OBS_METRICS" <<'EOF'
import json, sys

trace = json.load(open(sys.argv[1]))
names = [e["name"] for e in trace["traceEvents"]]
for want in ("compile/", "bulk/read", "bulk/quantize", "bulk/quantize_wait",
             "bulk/prefetch_wait", "bulk/score", "bulk/sync", "bulk/sink"):
    assert any(n.startswith(want) for n in names), \
        f"trace missing {want} spans: {sorted(set(names))[:20]}"
assert all({"ph", "pid", "tid"} <= set(e) for e in
           trace["traceEvents"]), "malformed Chrome trace events"
assert all("ts" in e for e in trace["traceEvents"] if e["ph"] != "M"), \
    "timed events missing ts"
metrics = json.load(open(sys.argv[2]))
snap = metrics["metrics"]["scoring/bulk"]
assert snap["rows"] > 0 and "rows_per_s" in snap, snap
print(f"obs smoke OK: {len(names)} events, "
      f"{snap['rows']} rows metered")
EOF

echo "== perf-regression gate (fresh quick runs vs committed baselines) =="
# compares the scenario JSONs the benches above just wrote against the
# committed results/perf trajectory: speedup ratios within the
# tolerance band, parity errors capped, exactness flags and
# zero-dispatch contracts intact.  Exits non-zero on regression.
python -m repro.launch.perf_gate --check --fresh-dir "$PERF_FRESH"

echo "CI OK"
