"""Multi-device tests: run in subprocesses with 8 forced host devices
(XLA locks device count at first init, so the main pytest process stays
single-device; the dry-run spec forbids setting the flag globally)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8) -> dict:
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


def test_flash_decode_matches_reference():
    res = run_sub("""
from repro.distributed.collectives import flash_decode
from repro.models.layers import decode_attention
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
B, S, H, KVH, Dh = 4, 32, 8, 2, 16
q = rng.normal(size=(B, H, Dh)).astype(np.float32)
k = rng.normal(size=(B, S, KVH, Dh)).astype(np.float32)
v = rng.normal(size=(B, S, KVH, Dh)).astype(np.float32)
valid = jnp.asarray(20, jnp.int32)
with mesh:
    got = flash_decode(mesh)(q, k, v, valid)
want = decode_attention(jnp.asarray(q)[:, None], jnp.asarray(k),
                        jnp.asarray(v), valid)[:, 0]
err = float(jnp.max(jnp.abs(got - want)))
print(json.dumps({"err": err}))
""")
    assert res["err"] < 1e-4, res


def test_compressed_allreduce_error_feedback():
    res = run_sub("""
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum_grads
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("data",), (AxisType.Auto,))
rng = np.random.default_rng(1)
g = rng.normal(size=(8, 64)).astype(np.float32)

def local(g, r):
    mean, r2 = compressed_psum_grads({"w": g[0]}, {"w": r[0]}, "data")
    return mean["w"][None], r2["w"][None]

fn = shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
               out_specs=(P("data"), P("data")))
r0 = np.zeros_like(g)
with mesh:
    mean, resid = fn(g, r0)
true_mean = g.mean(0)
err = float(np.max(np.abs(np.asarray(mean)[0] - true_mean)))
scale = float(np.abs(true_mean).max())
# residual bounded by quantization step
rmax = float(np.abs(np.asarray(resid)).max())
gmax = float(np.abs(g).max(axis=1).mean())
print(json.dumps({"err": err, "scale": scale, "rmax": rmax, "gmax": gmax}))
""")
    # int8 quantization: error <= nshards * step/2 / n ~ max/254
    assert res["err"] <= res["scale"] * 0.05 + 0.02, res
    assert res["rmax"] <= res["gmax"] / 100.0, res


def test_ring_allgather_matmul():
    res = run_sub("""
from repro.distributed.collectives import ring_allgather_matmul
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
rng = np.random.default_rng(2)
x = rng.normal(size=(16, 32)).astype(np.float32)
w = rng.normal(size=(32, 8)).astype(np.float32)
with mesh:
    got = ring_allgather_matmul(mesh, axis="model")(x, w)
err = float(np.max(np.abs(np.asarray(got) - x @ w)))
print(json.dumps({"err": err}))
""")
    assert res["err"] < 1e-3, res


def test_sharded_gbdt_predict_psum():
    res = run_sub("""
from repro.core import boosting, losses, predict
from repro.core.boosting import BoostingParams
rng = np.random.default_rng(3)
x = rng.normal(size=(256, 12)).astype(np.float32)
y = (x[:, 0] + x[:, 3] > 0).astype(np.float32)
loss = losses.make_loss("logloss")
ens, _ = boosting.fit(x, y, loss=loss,
                      params=BoostingParams(n_trees=16, depth=3,
                                            learning_rate=0.3))
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
xj = jnp.asarray(x[:64])
want = np.asarray(predict.raw_predict(ens, xj, strategy="staged",
                                      backend="ref"))
with mesh:
    got = np.asarray(predict.predict_sharded(ens, xj, mesh))
err = float(np.max(np.abs(got - want)))
print(json.dumps({"err": err}))
""")
    assert res["err"] < 1e-4, res


def test_elastic_reshard_8_to_4():
    """Checkpoint written under an 8-device mesh restores on 4 devices."""
    res = run_sub("""
import tempfile
from repro import configs
from repro.data.pipeline import TokenSource
from repro.training.trainer import Trainer, TrainerConfig
from repro.launch.mesh import make_local_mesh

cfg = configs.get("glm4-9b", smoke=True)
ts = TokenSource(cfg.vocab_size, 16, 8)
def batches():
    s = 0
    while True:
        yield ts.next_batch(s); s += 1

with tempfile.TemporaryDirectory() as d:
    from jax.sharding import AxisType
    mesh8 = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
    tr = Trainer(cfg, mesh8, d, TrainerConfig(total_steps=4, ckpt_every=2))
    tr.init_or_restore()
    tr.train(batches())
    loss8 = None
    # restore onto a DIFFERENT mesh (2x2 over 4 devices)
    mesh4 = jax.make_mesh((2, 2), ("data", "model"), (AxisType.Auto,) * 2,
                          devices=jax.devices()[:4])
    tr2 = Trainer(cfg, mesh4, d, TrainerConfig(total_steps=6, ckpt_every=2))
    ok = tr2.restore()
    hist = tr2.train(batches())
    print(json.dumps({"restored": ok, "resume_step": 4,
                      "final": tr2.step,
                      "losses_finite": all(np.isfinite(h["loss"])
                                           for h in hist)}))
""")
    assert res["restored"] and res["final"] == 6 and res["losses_finite"]


def test_ring_attention_matches_plain():
    res = run_sub("""
from repro.distributed.collectives import ring_attention
from repro.models.layers import attention
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
rng = np.random.default_rng(4)
B, S, H, KVH, Dh = 2, 32, 6, 2, 8      # 6 heads: does NOT divide 4
q = rng.normal(size=(B, S, H, Dh)).astype(np.float32)
k = rng.normal(size=(B, S, KVH, Dh)).astype(np.float32)
v = rng.normal(size=(B, S, KVH, Dh)).astype(np.float32)
with mesh:
    got = ring_attention(mesh)(q, k, v)
want = attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                 causal=True)
err = float(jnp.max(jnp.abs(got - want)))
print(json.dumps({"err": err}))
""")
    assert res["err"] < 1e-4, res
