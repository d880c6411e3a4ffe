"""Mesh-sharded GBDT prediction: parity, routing and metric-merge tests.

Multi-device cases run in subprocesses with forced host devices (same
pattern as tests/test_distributed.py — XLA locks the device count at
first init, so the main pytest process must stay single-device).  The
contracts under test:

* row-sharded pool/float predict is *bit-exact* vs single-device on
  every layout (each row's addend order is unchanged — shards just
  partition rows);
* a sharded pool predict performs ZERO binarize dispatches (the PR-3
  fallback that re-pinned per-shard plans to soa and re-binarized is
  the regression this guards);
* tree-sharded predict matches to reassociated-float tolerance (psum
  reorders the tree sum);
* uneven row counts (not divisible by the mesh) pad internally and
  return exactly the unpadded rows;
* K models x R replicas route round-robin and `predict_multi` still
  quantizes once per schema fingerprint.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENSEMBLE_SETUP = """
from repro.core.trees import ObliviousEnsemble
from repro.core.predictor import Predictor
from repro.kernels import registry
from repro.kernels.ops import PAD_SPLIT_BIN
from jax.sharding import AxisType

def make_ens(T, D, F, B, C, seed=0, leaf_scale=1.0):
    rng = np.random.default_rng(seed)
    depths = rng.integers(2, D + 1, size=T)
    sf = rng.integers(0, F, size=(T, D)).astype(np.int32)
    sb = rng.integers(1, B + 1, size=(T, D)).astype(np.int32)
    for t in range(T):
        sb[t, depths[t]:] = PAD_SPLIT_BIN
    lv = (leaf_scale * rng.normal(size=(T, 1 << D, C))).astype(np.float32)
    borders = np.sort(rng.normal(size=(B, F)).astype(np.float32), axis=0)
    return ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                             jnp.asarray(lv), jnp.asarray(borders),
                             jnp.asarray(np.full((F,), B, np.int32)))

def binarize_calls():
    return sum(v for k, v in registry.call_stats().items()
               if k[0].startswith("binarize"))
"""


def run_sub(body: str, devices: int = 4) -> dict:
    """Run `body` (must print one json line as last stdout line)."""
    prelude = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prelude + body],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_row_sharded_parity_all_layouts():
    """Row-sharded == single-device, bit for bit, on all four layouts,
    for pool and float inputs and for uneven row counts — and the pool
    path never dispatches binarize."""
    res = run_sub(ENSEMBLE_SETUP + """
ens = make_ens(30, 5, 20, 60, 3)
mesh = jax.make_mesh((4,), ("data",), (AxisType.Auto,))
rng = np.random.default_rng(7)
x = rng.normal(size=(136, 20)).astype(np.float32)
out = {}
for layout in ("soa", "depth_major", "depth_grouped", "bitpacked"):
    plan = Predictor.build(ens, strategy="staged", backend="ref",
                           layout=layout)
    pool = plan.quantize(x)
    ref_pool = np.asarray(plan.raw(pool))
    ref_float = np.asarray(plan.raw(x))
    fn = plan.sharded(mesh)
    registry.reset_call_stats()
    got_pool = np.asarray(fn(pool))
    nbin = binarize_calls()
    got_float = np.asarray(fn(x))
    # 131 % 4 != 0: the entry pads to a shardable count and slices back
    got_uneven = np.asarray(fn(pool.slice_rows(0, 131)))
    out[layout] = {
        "pool_exact": bool((got_pool == ref_pool).all()),
        "float_exact": bool((got_float == ref_float).all()),
        "uneven_exact": bool((got_uneven == ref_pool[:131]).all()),
        "uneven_rows": got_uneven.shape[0],
        "binarize_calls": nbin,
    }
print(json.dumps(out))
""")
    for layout, r in res.items():
        assert r["pool_exact"], (layout, r)
        assert r["float_exact"], (layout, r)
        assert r["uneven_exact"], (layout, r)
        assert r["uneven_rows"] == 131, (layout, r)
        assert r["binarize_calls"] == 0, (layout, r)


def test_tree_sharded_psum_parity():
    """Tree-sharded predict psums partial leaf sums over the mesh —
    a reassociated float sum, so parity is to tolerance, not bits."""
    res = run_sub(ENSEMBLE_SETUP + """
ens = make_ens(256, 5, 20, 60, 3, seed=3)
mesh = jax.make_mesh((4,), ("data",), (AxisType.Auto,))
rng = np.random.default_rng(11)
x = rng.normal(size=(64, 20)).astype(np.float32)
plan = Predictor.build(ens, strategy="staged", backend="ref")
pool = plan.quantize(x)
ref = np.asarray(plan.raw(pool))
fn = plan.sharded(mesh, shard_axis="trees")
got = np.asarray(fn(pool))
gotf = np.asarray(fn(x))
scale = float(np.abs(ref).max())
# a mesh BulkScorer takes the shard axis from its config
from repro.scoring.scorer import BulkScorer, ScoreConfig
from repro.scoring.sources import ArraySource
bulk = {axis: np.asarray(BulkScorer(plan, ScoreConfig(
            output="raw", chunk_rows=32, shard_axis=axis), mesh=mesh)
        .score(ArraySource(x)).output) for axis in ("rows", "trees")}
print(json.dumps({
    "err_pool": float(np.abs(got - ref).max()),
    "err_float": float(np.abs(gotf - ref).max()),
    "err_bulk_trees": float(np.abs(bulk["trees"] - ref).max()),
    "bulk_rows_exact": bool(np.array_equal(bulk["rows"], ref)),
    "scale": scale,
}))
""")
    # reassociated sum of 256 trees: 1e-6 relative to the raw scale
    tol = 1e-6 * max(res["scale"], 1.0) * 4
    assert res["err_pool"] <= tol, res
    assert res["err_float"] <= tol, res
    assert res["err_bulk_trees"] <= tol, res
    assert res["bulk_rows_exact"], res


def test_registry_replicas_and_predict_multi():
    """K models x R replicas on one mesh: round-robin routing, merged
    metrics, and quantize-once across every model and replica."""
    res = run_sub(ENSEMBLE_SETUP + """
from repro.serving.engine import GBDTServer, ModelRegistry, ReplicaGroup

import dataclasses
ens_a = make_ens(12, 4, 10, 30, 3, seed=1)
# model b: different trees, *shared* feature schema (same borders) —
# the quantize-once case predict_multi exists for
ens_b = dataclasses.replace(make_ens(12, 4, 10, 30, 3, seed=2),
                            borders=ens_a.borders,
                            n_borders=ens_a.n_borders)
mesh = jax.make_mesh((4,), ("data",), (AxisType.Auto,))
rng = np.random.default_rng(5)
xs = rng.normal(size=(40, 10)).astype(np.float32)

reg = ModelRegistry(mesh=mesh)
ga = reg.register("a", ens_a, replicas=2)
gb = reg.register("b", ens_b, replicas=2)
assert isinstance(ga, ReplicaGroup) and len(ga.servers) == 2
assert all(len(np.asarray(s.mesh.devices).reshape(-1)) == 2
           for s in ga.servers)

# parity vs unsharded single-device plans
want_a = np.asarray(Predictor.build(ens_a).proba(xs))
want_b = np.asarray(Predictor.build(ens_b).proba(xs))
q_cost = []
for g, ens in ((ga, ens_a), (gb, ens_b)):
    registry.reset_call_stats()
    g.quantize(xs)
    q_cost.append(binarize_calls())
registry.reset_call_stats()
out = reg.predict_multi(xs)
multi_bin = binarize_calls()
n_schemas = len({s.schema_fingerprint
                 for s in (ga.servers[0], gb.servers[0])})

# round-robin spreads load across the replicas of a group
for _ in range(4):
    ga.predict_batch(xs)
batches = [s.metrics.snapshot()["batches"] for s in ga.servers]
m = reg.metrics()
reg.close()
print(json.dumps({
    "ok_a": bool(np.allclose(out["a"], want_a, atol=1e-6)),
    "ok_b": bool(np.allclose(out["b"], want_b, atol=1e-6)),
    "multi_binarize": multi_bin,
    "quantize_cost": q_cost,
    "n_schemas": n_schemas,
    "batches": batches,
    "replicas_a": m["a"]["replicas"],
    "requests_a": m["a"]["requests"],
    "layout_a": m["a"]["layout"],
}))
""")
    assert res["ok_a"] and res["ok_b"], res
    # predict_multi quantized once per distinct schema: its binarize
    # bill equals one quantize per schema, no more
    assert res["multi_binarize"] == res["quantize_cost"][0] \
        * res["n_schemas"], res
    assert all(b > 0 for b in res["batches"]), res
    assert res["replicas_a"] == 2, res
    assert res["requests_a"] > 0, res
    assert res["layout_a"] != "mixed", res


# -- single-device pieces (no subprocess needed) ---------------------------

def test_best_shard_axis_cost_model():
    from repro.kernels import tuning

    # serving-sized batches with few trees: rows
    assert tuning.best_shard_axis(16384, 100, 4) == "rows"
    # giant ensemble, tiny batch: trees
    assert tuning.best_shard_axis(2, 4096, 4) == "trees"
    # replicating an enormous leaf table across the mesh is the
    # documented tree-shard trigger
    assert tuning.best_shard_axis(
        16384, 8192, 4, leaf_table_bytes=40 << 20) == "trees"
    # a 1-way mesh never tree-shards
    assert tuning.best_shard_axis(2, 8192, 1) == "rows"


def test_replica_submeshes_validation():
    import jax
    from jax.sharding import AxisType
    from repro.distributed.gbdt import replica_submeshes

    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    subs = replica_submeshes(mesh, 1)
    assert len(subs) == 1 and subs[0].axis_names == ("data",)
    with pytest.raises(ValueError):
        replica_submeshes(mesh, 2)      # 1 device, 2 groups
    with pytest.raises(ValueError):
        replica_submeshes(mesh, 0)


def test_shard_parity_pass_clean():
    """The checker's shard-parity pass over the canonical plans must
    come back clean (no gathering collectives in any sharded entry)."""
    from repro.analysis import passes

    assert passes.shard_parity_findings((8,)) == []


def test_shard_parity_lint_flags_all_gather():
    """Positive control: a sharded entry that all-gathers its panel is
    exactly what the lint exists to flag."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.analysis import passes

    mesh = jax.sharding.AbstractMesh((4,), ("data",))

    def local(x):
        full = jax.lax.all_gather(x, "data", tiled=True)
        return jnp.sum(full)[None] * jnp.ones_like(x[:, 0])

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P("data"), check_vma=False)
    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((8, 4), jnp.float32))
    findings = passes.sharded_entry_findings("ctrl:sharded_raw", closed)
    assert findings, "all_gather inside shard_map must be flagged"
    assert all(f.rule == "shard-parity" for f in findings)


def test_percentile_reservoir_merge():
    from repro.serving.metrics import PercentileReservoir

    a = PercentileReservoir(max_samples=64, seed=1)
    b = PercentileReservoir(max_samples=64, seed=2)
    for v in range(100):
        a.add(float(v))          # stream ~[0, 100)
    for v in range(300):
        b.add(1000.0 + v)        # stream ~[1000, 1300), 3x larger
    a.merge(b)
    assert a.seen == 400
    assert len(a) <= a.max_samples
    # the merged sample leans toward the larger stream and the merged
    # median lands in b's value range
    assert a.percentile(50) > 500.0
    with pytest.raises(TypeError):
        a.merge([1.0, 2.0])


def test_server_metrics_merge():
    from repro.serving.metrics import ServerMetrics

    parts = []
    for i in range(3):
        m = ServerMetrics(f"m/r{i}")
        m.layout = "soa"
        for _ in range(10 * (i + 1)):
            m.note_batch(4, 8, 0.002 * (i + 1))
        parts.append(m)
    merged = ServerMetrics.merge(parts)
    assert merged["replicas"] == 3
    assert merged["requests"] == 4 * (10 + 20 + 30)
    assert merged["batches"] == 60
    assert merged["layout"] == "soa"
    assert merged["pad_overhead"] == pytest.approx(0.5)
    # percentiles come from the merged reservoir: p99 reflects the
    # slowest replica, not an average of per-part p99s
    assert merged["batch_p99_ms"] == pytest.approx(6.0, rel=0.2)
    parts[1].layout = "bitpacked"
    assert ServerMetrics.merge(parts)["layout"] == "mixed"
    with pytest.raises(ValueError):
        ServerMetrics.merge([])


def test_scoring_metrics_merge():
    from repro.scoring.scorer import ScoringMetrics

    parts = []
    for i in range(2):
        m = ScoringMetrics(f"w{i}")
        m.start()
        for _ in range(5):
            m.note_chunk(100, 128, 0.01)
        m.note_quantize(0.05)
        m.stop()
        parts.append(m)
    merged = ScoringMetrics.merge(parts)
    assert merged["rows"] == 1000
    assert merged["chunks"] == 10
    assert merged["quantize_s"] == pytest.approx(0.1)
    assert merged["score_s"] == pytest.approx(0.1)
    # concurrent workers: fleet wall is the slowest part, not the sum
    assert merged["wall_s"] <= sum(p.snapshot()["wall_s"] for p in parts)
    assert merged["chunk_p50_ms"] == pytest.approx(10.0, rel=0.05)
    with pytest.raises(ValueError):
        ScoringMetrics.merge([])
