"""Sharding rule tests: spec trees match param trees, divisibility holds,
TP/EP/FSDP axes land where designed.  No multi-device compile needed —
specs are pure metadata."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.models import transformer as tf


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    # AbstractMesh carries shape info without real devices
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


@pytest.mark.parametrize("arch", list(configs.ARCHS))
def test_specs_cover_params_and_divide(arch):
    cfg = configs.get(arch)
    mesh = fake_mesh()
    max_pos = 32768
    shapes = tf.param_shapes(cfg, max_positions=max_pos)
    specs = shd.param_specs(cfg, mesh, max_positions=max_pos)
    flat_sh = jax.tree_util.tree_leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    flat_sp = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_sh) == len(flat_sp)
    for (pa, shape), (pb, spec) in zip(flat_sh, flat_sp):
        assert pa == pb
        assert len(spec) <= len(shape), (pa, spec, shape)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert shape[i] % size == 0, (pa, shape, spec)


def test_tp_axes_on_dense_weights():
    cfg = configs.get("internlm2-20b")
    specs = shd.param_specs(cfg, fake_mesh())
    b = specs["blocks"]
    assert b["wq"] == P(None, "data", "model")     # fsdp + TP
    assert b["wo"] == P(None, "model", "data")
    assert b["w_gate"] == P(None, "data", "model")
    assert b["w_out"] == P(None, "model", "data")
    assert specs["embed"] == P(None, "model")


def test_moe_expert_vs_ffn_sharding():
    kimi = shd.param_specs(configs.get("kimi-k2-1t-a32b"), fake_mesh())
    assert kimi["blocks"]["w_gate"] == P(None, "model", "data", None)
    mixtral = shd.param_specs(configs.get("mixtral-8x22b"), fake_mesh())
    # 8 experts < 16-way axis -> TP inside expert ffn
    assert mixtral["blocks"]["w_gate"] == P(None, None, "data", "model")
    assert mixtral["blocks"]["w_out"] == P(None, None, "model", "data")


def test_kv_heads_not_divisible_fall_back():
    cfg = configs.get("glm4-9b")                    # kv=2 < 16
    specs = shd.param_specs(cfg, fake_mesh())
    assert specs["blocks"]["wk"] == P(None, None, None)
    assert specs["blocks"]["wq"] == P(None, None, "model")


def test_uneven_vocab_not_sharded():
    cfg = configs.get("internvl2-1b")               # vocab 151655
    specs = shd.param_specs(cfg, fake_mesh())
    assert specs["lm_head"][-1] is None


def test_batch_and_cache_specs():
    from repro.configs.base import SHAPES
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    cfg = configs.get("internlm2-20b")
    bs = shd.batch_specs(cfg, SHAPES["train_4k"], mesh)
    assert bs["tokens"] == P(("pod", "data"), None)
    bs1 = shd.batch_specs(cfg, SHAPES["long_500k"], mesh)
    assert bs1["tokens"] == P(None, None)           # batch 1: replicated
    cs = shd.cache_specs(cfg, SHAPES["decode_32k"], mesh)
    assert cs["k"][2] == "model"                    # sequence-sharded KV


def test_opt_state_specs_mirror_params():
    cfg = configs.get("glm4-9b")
    mesh = fake_mesh()
    ps = shd.param_specs(cfg, mesh)
    adam = shd.opt_state_specs(ps, "adamw")
    assert adam["m"]["blocks"]["wq"] == ps["blocks"]["wq"]
    fact = shd.opt_state_specs(ps, "adafactor")
    wq = ps["blocks"]["wq"]
    assert fact["vr"]["blocks"]["wq"] == P(*wq[:-1])
    assert fact["vc"]["blocks"]["wq"] == P(*wq[:-2], wq[-1])


def test_collective_bytes_parser():
    import importlib
    dr = importlib.import_module("repro.launch.hlo_analysis")
    hlo = """
  %ar = f32[128,256] all-reduce(f32[128,256] %x), replica_groups={}
  %ag = bf16[64]{0} all-gather(bf16[32]{0} %y), dimensions={0}
  %cp = f32[2,2]{1,0} collective-permute(f32[2,2]{1,0} %z)
  %dot = f32[128,128] dot(f32[128,64] %a, f32[64,128] %b)
"""
    got = dr.collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == 128 * 256 * 4
    assert got["all-gather"] == 64 * 2
    assert got["collective-permute"] == 16
    assert got["total"] == 128 * 256 * 4 + 128 + 16
