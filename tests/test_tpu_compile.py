"""The main-path Pallas kernels compile for a TPU v5e, at the covertype
widths the chip smoke runs (54 features padded to 128, 254 borders,
depth 8, 7 classes, 10,000 trees), with the block shapes the tuner
picks for them.

No chip is needed: the TPU compiler is installed and compiles for a
described, unattached v5e.  Interpret-mode tests check what the kernels
compute; these check that Mosaic accepts them — tiling, casts, VMEM.
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist imports this file in
every worker.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import binarize as bk
from repro.kernels import fused_predict as fk
from repro.kernels import histogram as hk
from repro.kernels import leaf_gather as gk
from repro.kernels import leaf_index as ik
from repro.kernels import node_index as nk
from repro.kernels import ops, tuning

F, FP, B, D, C, T = 54, 128, 254, 8, 7, 10_000
L = 1 << D
CP = ops._round_up(C, ops.CLASS_ALIGN)   # the lowered table's class axis
TRAIN_ROWS = 464_800


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile here is written to a persistent cache but cannot be
    # read back without a chip: keep it out of any configured cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _pad(n, m):
    return -(-n // m) * m


@pytest.mark.parametrize("n_rows", [256, 4096])
def test_fused_predict_u8_10k_trees(one_chip, n_rows):
    """The serving kernel `auto` resolves to on TPU (soa layout: the
    10k-tree one-hot is over the depth-major budget), at the blocks the
    tuner picks for a serving bucket and for a bulk batch."""
    bn, bt = tuning.best_fused_blocks(F, D, L, C, B, n_rows=n_rows,
                                      n_trees=T)
    tp = _pad(T, bt)
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    _compile(lambda x, b, sf, sb, lv: fk.fused_predict(
        x, b, sf, sb, lv, block_n=bn, block_t=bt,
        bins_scratch_dtype=jnp.uint8),
        s((_pad(n_rows, bn), FP), jnp.float32), s((B, FP), jnp.float32),
        s((tp, D), jnp.int32), s((tp, D), jnp.int32),
        s((tp, CP, L), jnp.float32))


def test_staged_chain_u8(one_chip):
    """binarize -> leaf_index -> leaf_gather on the uint8 bin stream at
    the blocks a fused plan's pool path uses (ops defaults, the plan's
    tree block)."""
    _, bt = tuning.best_fused_blocks(F, D, L, C, B, n_rows=256, n_trees=T)
    tp, n = _pad(T, bt), 2048
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731

    def chain(x, b, sf, sb, lv):
        bins = bk.binarize(x, b, block_n=256, block_f=128,
                           out_dtype=jnp.uint8)
        idx = ik.leaf_index(bins, sf, sb, block_n=256, block_t=bt)
        return gk.leaf_gather(idx, lv, block_n=128, block_t=bt)

    text = _compile(chain, s((n, FP), jnp.float32), s((B, FP), jnp.float32),
                    s((tp, D), jnp.int32), s((tp, D), jnp.int32),
                    s((tp, CP, L), jnp.float32))
    assert text.count("tpu_custom_call") >= 3


def test_histogram_u8_deepest_level(one_chip):
    """The training histogram at the deepest level of a depth-8 tree
    (128 leaves x 255 bins, gradients and hessians of 7 classes) over
    the full synthetic covertype pool."""
    n_leaves, n_bins, n_stats = 1 << (D - 1), B + 1, 2 * C
    bf, bn = tuning.best_hist_blocks(F, n_leaves, n_bins, n_stats,
                                     n_rows=TRAIN_ROWS)
    fp, n = _pad(F, bf), _pad(TRAIN_ROWS, bn)
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    _compile(lambda b, leaf, g: hk.histogram(
        b, leaf, g, n_bins=n_bins, n_leaves=n_leaves, block_f=bf,
        block_n=bn),
        s((fp, n), jnp.uint8), s((n,), jnp.int32),
        s((n, n_stats), jnp.float32))


@pytest.mark.parametrize("layout", ["depth_major", "bitpacked"])
def test_fused_layout_variants(one_chip, layout):
    """The `_dm` / `_bp` fused kernels `tuning.best_layout` can pick."""
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    n = 1024
    if layout == "depth_major":
        bn, bt = tuning.best_fused_blocks(F, D, L, C, B, n_rows=n,
                                          n_trees=1024)
        _compile(lambda x, b, oh, sb, p2, lv: fk.fused_predict_dm(
            x, b, oh, sb, p2, lv, block_n=bn, block_t=bt,
            bins_scratch_dtype=jnp.uint8),
            s((n, FP), jnp.float32), s((B, FP), jnp.float32),
            s((1024, D, FP), jnp.float32), s((D, 1024), jnp.int32),
            s((D, 1), jnp.float32), s((1024, CP, L), jnp.float32))
        return
    bn, _ = tuning.best_fused_blocks(F, D, L, C, B, n_rows=n, n_trees=1024,
                                     gather="bitplane")
    _compile(lambda x, b, sf, sb, lv: fk.fused_predict_bp(
        x, b, sf, sb, lv, block_n=bn, bins_scratch_dtype=jnp.uint8),
        s((n, FP), jnp.float32), s((B, FP), jnp.float32),
        s((D, 1024), jnp.int32), s((D, 1024), jnp.int32),
        s((1024, CP, L), jnp.float32))


def _covertype_plan(n_trees, **config):
    """A pallas soa plan over a seeded ensemble at covertype widths."""
    import numpy as np

    from repro.core.predictor import PredictConfig, Predictor
    from repro.core.trees import ObliviousEnsemble

    rng = np.random.default_rng(0)
    ens = ObliviousEnsemble(
        jnp.asarray(rng.integers(0, F, (n_trees, D)).astype(np.int32)),
        jnp.asarray(rng.integers(1, B, (n_trees, D)).astype(np.int32)),
        jnp.asarray(rng.normal(size=(n_trees, L, C)).astype(np.float32)),
        jnp.asarray(np.sort(rng.normal(size=(B, F)), 0).astype(np.float32)),
        jnp.full((F,), B, jnp.int32))
    # the layout the 10,000-tree plan resolves to (few trees would
    # pick depth_major)
    return Predictor.build(ens, PredictConfig(backend="pallas",
                                              layout="soa", **config))


def test_bulk_entries_name_kernels_and_stages(one_chip, monkeypatch):
    """The bulk path's plan entries (quantize, then proba_pool) at
    covertype widths: each kernel's op is named after its benchmark
    trace name, and every kernel and the softmax carry their
    `gbdt/<stage>` scope in their op_name metadata."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    plan = _covertype_plan(64, strategy="staged")
    model = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         plan.lowered)
    ops_seen = {}
    for entry, dtype in (("quantize", jnp.float32),
                         ("proba_pool", jnp.uint8)):
        text = plan._entries[entry].lower(
            model, _sds(one_chip, (256, F), dtype)).compile().as_text()
        for m in re.finditer(r'%(\S+) = \S+ (\S+?)\(.*op_name="([^"]*)"',
                             text):
            ops_seen[m.group(1)] = (m.group(2), m.group(3))
    kernels = {name.split(".")[0]: op_name
               for name, (kind, op_name) in ops_seen.items()
               if kind == "custom-call"}
    assert sorted(kernels) == ["binarize", "leaf_gather", "leaf_index_u8"]
    for name, stage in (("binarize", "binarize"),
                        ("leaf_index_u8", "leaf_index"),
                        ("leaf_gather", "leaf_gather")):
        assert f"/gbdt/{stage}/" in kernels[name], kernels[name]
    assert any("/gbdt/softmax/" in op_name
               for _, op_name in ops_seen.values())


@pytest.mark.parametrize("entry,dtype", [("proba_pool", jnp.uint8),
                                         ("proba", jnp.float32)])
def test_plan_entries_read_the_leaf_table_in_place(one_chip, monkeypatch,
                                                   entry, dtype):
    """The bulk (`proba_pool`) and online (fused `proba`) entries of the
    covertype plan (fused, pallas, soa, blocks 1024 x 64) at 640 trees:
    the class-major (Tp, 8, L) table is read in the layout the device
    stores it in.  A leaf-major (Tp, L, 7) table was relaid out by a
    `copy` of the parameter before every kernel call, into a temp 16x
    the table (83,886,080 B at 640 trees)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    plan = _covertype_plan(640, strategy="fused", block_n=1024,
                           block_t=64)
    assert plan.lowered.leaf_values.shape == (640, CP, L)
    model = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         plan.lowered)
    compiled = plan._entries[entry].lower(
        model, _sds(one_chip, (256, F), dtype)).compile()
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= \S+ copy\(", line)
              and 'op_name="p.leaf_values"' in line]
    assert not copies, copies
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < plan.lowered.leaf_table_bytes() == 5_242_880, temp


def _lossguide_plan(n_trees):
    """A pallas plan over a seeded node table at covertype-lossguide's
    widths: chains of 30 nodes (31 leaves), 7 classes."""
    import numpy as np

    from repro.core.predictor import PredictConfig, Predictor
    from repro.core.trees import NodeEnsemble

    rng = np.random.default_rng(0)
    nodes = np.arange(30)
    left = np.broadcast_to(-1 - nodes, (n_trees, 30))
    right = np.broadcast_to(np.append(nodes[1:], -31), (n_trees, 30))
    ens = NodeEnsemble(
        jnp.asarray(rng.integers(0, F, (n_trees, 30)).astype(np.int32)),
        jnp.asarray(rng.integers(1, B, (n_trees, 30)).astype(np.int32)),
        jnp.asarray(left.astype(np.int32)),
        jnp.asarray(right.astype(np.int32)),
        jnp.asarray(rng.normal(size=(n_trees, 31, C)).astype(np.float32)),
        jnp.asarray(np.sort(rng.normal(size=(B, F)), 0).astype(np.float32)),
        jnp.full((F,), B, jnp.int32))
    return Predictor.build(ens, PredictConfig(backend="pallas"))


def test_node_index_lossguide_widths(one_chip):
    """The non-symmetric trees' leaf index at covertype-lossguide's
    widths (54 features padded to 128, 30 nodes and 31 leaves in 32
    slots, four trees to a 128-lane row, 10,048 trees) on a 256-row
    uint8 chunk, as the bulk path calls it."""
    w, tp = 32, 10_048
    k = 128 // w
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    packed = s((tp // k, 128), jnp.int32)
    _compile(lambda b, nf, nb, p, pl_: nk.node_index(
        b, nf, nb, p, pl_, width=w, block_n=256),
        s((256, FP), jnp.uint8), packed, packed,
        s((tp // k, w, 128), jnp.bfloat16), packed)


def test_lossguide_bulk_entry_names_node_index(one_chip, monkeypatch):
    """The nodes plan's bulk entry (proba_pool) runs binarize-free:
    `node_index` then the shared `leaf_gather`, each named after its
    benchmark trace name and carrying its `gbdt/<stage>` scope; the
    node arrays and path matrices are read in place, never relaid out."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    plan = _lossguide_plan(128)
    assert plan.config.layout == "nodes"
    model = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                         plan.lowered)
    text = plan._entries["proba_pool"].lower(
        model, _sds(one_chip, (256, F), jnp.uint8)).compile().as_text()
    kernels = {m.group(1).split(".")[0]: m.group(2) for m in re.finditer(
        r'%(\S+) = \S+ custom-call\(.*op_name="([^"]*)"', text)}
    assert sorted(kernels) == ["leaf_gather", "node_index"]
    for name in kernels:
        assert f"/gbdt/{name}/" in kernels[name], kernels[name]
    copied = [line for line in text.splitlines()
              if re.search(r"= \S+ copy\(", line)
              and re.search(r'op_name="p\.(node|path)', line)]
    assert not copied, copied
