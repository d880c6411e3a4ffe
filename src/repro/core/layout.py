"""Physical ensemble layouts: the lowering layer between Predictor
plans and kernels.

The paper's biggest wins come from reorganizing how oblivious trees are
laid out for the vector unit — hoisting the per-depth pow2 vector out of
the CalcIndexes loop, grouping trees into blocks
(`CalcTreesBlockedImpl`), and streaming the binarized features in the
order the loop consumes them — not from any single intrinsic.  This
module makes that reorganization a first-class compilation step: a
logical `ObliviousEnsemble` is lowered **once** (at `Predictor.build`
time) into a `LoweredEnsemble`, a registered pytree whose arrays are in
the physical order a kernel family wants, pre-padded to that family's
block contracts.  Kernels consume lowered arrays; plans store them; the
tuner picks among them from the ensemble's shape.

Five layouts:

  soa            today's structure-of-arrays — (T, D) splits, one
                 leaf table — the compatibility default.
  depth_major    splits transposed to (D, T) bit-plane order with the
                 one-hot feature-gather matrix onehot(sf) (T, D, F) and
                 the per-depth pow2 vector precomputed at lower time:
                 leaf_index kernels run a straight matmul instead of
                 rebuilding iota/one-hot per call (the paper's hoisting
                 trick applied to model structure).
  depth_grouped  trees bucketed by *true* depth — a depth-4 tree
                 carries a 16-entry leaf table instead of 2^Dmax —
                 evaluated group-by-group through the soa kernels and
                 summed (the paper's equal-depth tree grouping,
                 CalcTreesBlockedImpl at depth granularity).  Note the
                 per-group summation reassociates the float tree sum
                 (same addends, different order).
  bitpacked      depth-grouped structure with the split arrays
                 transposed to (d, T_d) bit planes in the narrowest
                 integer dtype that holds them: per depth the
                 comparison bins >= sb is ONE bit per doc, 32 docs pack
                 into a uint32 lane word (the paper's vmsgeu mask
                 register) and the `_bp` kernels assemble leaf indexes
                 via integer shift/or — no one-hot, no f32, no MXU
                 until the leaf gather.  For binary-split schemas
                 (<= 1 border per feature) the uint8 pool itself packs
                 into u1 feature planes — `pack_pool_u1` — an 8x pool
                 memory shrink.
  nodes          non-symmetric trees (`trees.NodeEnsemble`): each
                 tree's node table and its per-leaf path matrix, packed
                 lane-dense; `node_index` tests every node and matches
                 whole paths on the MXU, exactly (the only layout a
                 NodeEnsemble takes; an oblivious model is rewritten
                 as a node table when asked for it).

Every layout holds its leaf tables in one packed class-major form,
(Tp/G, Cp, G*Lp) (`ops.class_major`): Cp = C rounded up to
`ops.CLASS_ALIGN` (8), Lp = L rounded up to 8, and G =
`tuning.trees_per_step(L)` trees side by side on the lanes of each row,
tree t in lanes [(t % G) * Lp, (t % G) * Lp + L) of row t // G, with
zero leaves in the padded classes, leaf slots and trees.  A row is whole
(8, 128) tiles with the leaves on lanes, the layout the device stores it
in and the one the leaf-sum kernels read, so no relayout runs per call,
and one MXU contraction sums a row's G trees.  Every tree axis is padded
to whole groups (pallas lowerings to the kernel's tree block as well),
so G = Tp / rows can be read off the shapes; tree shards and
`tree_block` blocks are cut on group boundaries.  The model format
(T, L, C) of `ObliviousEnsemble` and training is unchanged.

Every layout is bit-for-bit the same *math* as the logical model:
identical leaf indices, identical per-tree leaf values.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trees import NodeEnsemble
from repro.kernels import ops
from repro.kernels import node_index as node_k
from repro.kernels import tuning
from repro.kernels.leaf_gather import trees_per_row
from repro.kernels.leaf_index import BP_TREE_BLOCK
from repro.kernels.ops import FEATURE_ALIGN, PAD_SPLIT_BIN

# T-axis alignment of the prepadded staged path (the leaf_index /
# leaf_gather kernels' default tree block).  Canonical definition —
# `core.predictor` re-exports it.
STAGED_TREE_ALIGN = 16


def is_concrete(ensemble) -> bool:
    """Whether the ensemble's structure arrays are concrete (lowerings
    that regroup trees need to *read* split_bins; per-shard plans built
    inside shard_map hold tracers and must stay on "soa")."""
    structure = (ensemble.node_bins if isinstance(ensemble, NodeEnsemble)
                 else ensemble.split_bins)
    return not isinstance(structure, jax.core.Tracer)


# --------------------------------------------------------------------------
# Lowered layouts (registered pytrees; unflatten bypasses __init__ so
# tree ops never re-run lowering-time logic — same scheme as
# `trees.ObliviousEnsemble`)
# --------------------------------------------------------------------------
def _register_lowered(cls, data_fields: tuple[str, ...],
                      meta_fields: tuple[str, ...] = ()):
    def flatten_with_keys(obj):
        children = tuple((jax.tree_util.GetAttrKey(f), getattr(obj, f))
                         for f in data_fields)
        return children, tuple(getattr(obj, f) for f in meta_fields)

    def flatten(obj):
        return (tuple(getattr(obj, f) for f in data_fields),
                tuple(getattr(obj, f) for f in meta_fields))

    def unflatten(aux, children):
        obj = object.__new__(cls)
        for f, c in zip(data_fields, children):
            object.__setattr__(obj, f, c)
        for f, v in zip(meta_fields, aux):
            object.__setattr__(obj, f, v)
        return obj

    jax.tree_util.register_pytree_with_keys(cls, flatten_with_keys,
                                            unflatten, flatten)
    return cls


def _leaf_steps(tables) -> dict[str, Any]:
    """`describe()`'s leaf-sum engagement over (packed table, padded
    tree count) pairs: trees_per_step, G = Tp / rows of each table (one
    int when they agree, else a list in table order), and
    leaf_steps_per_block, the loop steps the leaf sum runs over one row
    block (Tp/G rows a table)."""
    gs = [trees_per_row(n, lv) for lv, n in tables]
    return {"trees_per_step": gs[0] if len(set(gs)) == 1 else gs,
            "leaf_steps_per_block": sum(int(lv.shape[0])
                                        for lv, _ in tables)}


@dataclasses.dataclass(frozen=True)
class SoaLayout:
    """Structure-of-arrays, a single shared depth (today's layout)."""
    layout_name = "soa"
    borders: jax.Array           # (B, Fp) f32
    split_features: jax.Array    # (Tp, D) i32
    split_bins: jax.Array        # (Tp, D) i32
    leaf_values: jax.Array       # (Tp/G, Cp, G*Lp) f32, packed class-major
    # staged tree blocking: per-block (sf, sb, lv) slices, pre-cut and
    # pre-padded at lower time so the per-call loop never touches jnp.pad;
    # the whole-model arrays above are then empty
    tree_blocks: Optional[tuple] = None
    n_outputs: int = 1           # static
    n_model_pads: int = 0        # static: model-side pads spent lowering

    def leaf_sum(self, bins: jax.Array, *, backend: str,
                 block_t: int) -> jax.Array:
        if self.tree_blocks is not None:
            # CalcTreesBlockedImpl with the block slices cut at lower time
            acc = jnp.zeros((bins.shape[0], self.n_outputs), jnp.float32)
            for sf, sb, lv in self.tree_blocks:
                idx = ops.leaf_index_prepadded(bins, sf, sb,
                                               backend=backend,
                                               block_t=block_t)
                acc = acc + ops.leaf_gather_prepadded(
                    idx, lv, n_classes=self.n_outputs, backend=backend,
                    block_t=block_t)
            return acc
        idx = ops.leaf_index_prepadded(bins, self.split_features,
                                       self.split_bins, backend=backend,
                                       block_t=block_t)
        return ops.leaf_gather_prepadded(idx, self.leaf_values,
                                         n_classes=self.n_outputs,
                                         backend=backend, block_t=block_t)

    def fused_raw(self, x: jax.Array, *, backend: str, block_n: int,
                  block_t: int) -> jax.Array:
        return ops.fused_predict_prepadded(
            x, self.borders, self.split_features, self.split_bins,
            self.leaf_values, n_classes=self.n_outputs, backend=backend,
            block_n=block_n, block_t=block_t)

    def _tables(self) -> list:
        """(packed leaf table, padded tree count) of each tree block."""
        blocks = self.tree_blocks or [(self.split_features, None,
                                       self.leaf_values)]
        return [(lv, sf.shape[0]) for sf, _, lv in blocks]

    def leaf_table_bytes(self) -> int:
        return sum(int(np.prod(lv.shape)) * 4 for lv, _ in self._tables())

    def describe(self) -> dict[str, Any]:
        return {"layout": self.layout_name,
                "leaf_table_bytes": self.leaf_table_bytes(),
                "tree_blocks": (len(self.tree_blocks)
                                if self.tree_blocks else 0),
                **_leaf_steps(self._tables())}


@dataclasses.dataclass(frozen=True)
class DepthMajorLayout:
    """Bit-plane order with the feature-gather one-hot precomputed."""
    layout_name = "depth_major"
    borders: jax.Array           # (B, Fp) f32
    onehot: jax.Array            # (Tp, D, Fp) f32 — onehot(sf[t, d])
    split_bins_dm: jax.Array     # (D, Tp) i32 — bit-plane transposed
    pow2: jax.Array              # (D, 1) f32 — hoisted 2^d vector
    leaf_values: jax.Array       # (Tp/G, Cp, G*Lp) f32, packed class-major
    n_outputs: int = 1           # static
    n_model_pads: int = 0        # static

    def leaf_sum(self, bins: jax.Array, *, backend: str,
                 block_t: int) -> jax.Array:
        idx = ops.leaf_index_dm_prepadded(bins, self.onehot,
                                          self.split_bins_dm, self.pow2,
                                          backend=backend, block_t=block_t)
        return ops.leaf_gather_prepadded(idx, self.leaf_values,
                                         n_classes=self.n_outputs,
                                         backend=backend, block_t=block_t)

    def fused_raw(self, x: jax.Array, *, backend: str, block_n: int,
                  block_t: int) -> jax.Array:
        return ops.fused_predict_dm_prepadded(
            x, self.borders, self.onehot, self.split_bins_dm, self.pow2,
            self.leaf_values, n_classes=self.n_outputs, backend=backend,
            block_n=block_n, block_t=block_t)

    def leaf_table_bytes(self) -> int:
        return int(np.prod(self.leaf_values.shape)) * 4

    def onehot_bytes(self) -> int:
        return int(np.prod(self.onehot.shape)) * 4

    def describe(self) -> dict[str, Any]:
        return {"layout": self.layout_name,
                "leaf_table_bytes": self.leaf_table_bytes(),
                "onehot_bytes": self.onehot_bytes(),
                **_leaf_steps([(self.leaf_values,
                                self.onehot.shape[0])])}


@dataclasses.dataclass(frozen=True)
class DepthGroup:
    """All trees of one true depth, sliced to that depth's shapes."""
    depth: int                   # static: true depth d of the group
    split_features: jax.Array    # (Tg_p, d) i32
    split_bins: jax.Array        # (Tg_p, d) i32
    leaf_values: jax.Array       # (Tg_p/G, Cp, G*Lp) f32, packed (L = 2^d)

    @property
    def n_trees(self) -> int:
        return self.split_features.shape[0]


@dataclasses.dataclass(frozen=True)
class DepthGroupedLayout:
    """Trees bucketed by true depth; shallow trees carry small tables."""
    layout_name = "depth_grouped"
    borders: jax.Array           # (B, Fp) f32
    groups: tuple                # tuple[DepthGroup, ...], depth ascending
    n_outputs: int = 1           # static
    n_model_pads: int = 0        # static

    def leaf_sum(self, bins: jax.Array, *, backend: str,
                 block_t: int) -> jax.Array:
        acc = jnp.zeros((bins.shape[0], self.n_outputs), jnp.float32)
        for g in self.groups:
            idx = ops.leaf_index_prepadded(bins, g.split_features,
                                           g.split_bins, backend=backend,
                                           block_t=block_t)
            acc = acc + ops.leaf_gather_prepadded(
                idx, g.leaf_values, n_classes=self.n_outputs,
                backend=backend, block_t=block_t)
        return acc

    def fused_raw(self, x: jax.Array, *, backend: str, block_n: int,
                  block_t: int) -> jax.Array:
        # Running the fused kernel once per group would re-execute its
        # stage 1 (binarize x against every border) G times — exactly
        # the work the grouping is supposed to shrink.  Binarize once
        # and reuse the grouped index+gather loop instead; with more
        # than one group this strictly dominates per-group fusion.
        bins = ops.binarize_prepadded(x, self.borders, backend=backend)
        return self.leaf_sum(bins, backend=backend, block_t=block_t)

    def leaf_table_bytes(self) -> int:
        return sum(int(np.prod(g.leaf_values.shape)) * 4
                   for g in self.groups)

    def describe(self) -> dict[str, Any]:
        return {"layout": self.layout_name,
                "leaf_table_bytes": self.leaf_table_bytes(),
                "groups": {int(g.depth): int(g.n_trees)
                           for g in self.groups},
                **_leaf_steps([(g.leaf_values, g.n_trees)
                               for g in self.groups])}


@dataclasses.dataclass(frozen=True)
class BitpackedGroup:
    """All trees of one true depth, split arrays in bit-plane order."""
    depth: int                   # static: true depth d of the group
    split_features_bp: jax.Array  # (d, Tg_p) i32 — bit-plane transposed
    split_bins_bp: jax.Array     # (d, Tg_p) u8 when thresholds fit, else i32
    leaf_values: jax.Array       # (Tg_p/G, Cp, G*Lp) f32, packed (L = 2^d)

    @property
    def n_trees(self) -> int:
        return self.split_features_bp.shape[1]


@dataclasses.dataclass(frozen=True)
class BitpackedLayout:
    """Depth groups with integer bit-plane splits (the paper's
    word-packed comparison loop): leaf indexes assemble via shift/or,
    32-doc comparison bits pack into uint32 lanes, nothing touches f32
    until the leaf gather."""
    layout_name = "bitpacked"
    borders: jax.Array           # (B, Fp) f32
    groups: tuple                # tuple[BitpackedGroup, ...], depth asc
    n_outputs: int = 1           # static
    n_model_pads: int = 0        # static
    binary_split: bool = False   # static: every feature has <= 1 border
    n_features: int = 0          # static: logical pool width F

    def leaf_sum(self, bins: jax.Array, *, backend: str,
                 block_t: int) -> jax.Array:
        acc = jnp.zeros((bins.shape[0], self.n_outputs), jnp.float32)
        for g in self.groups:
            idx = ops.leaf_index_bp_prepadded(bins, g.split_features_bp,
                                              g.split_bins_bp,
                                              backend=backend,
                                              block_t=block_t)
            acc = acc + ops.leaf_gather_prepadded(
                idx, g.leaf_values, n_classes=self.n_outputs,
                backend=backend, block_t=block_t)
        return acc

    def fused_raw(self, x: jax.Array, *, backend: str, block_n: int,
                  block_t: int) -> jax.Array:
        if len(self.groups) == 1:
            g = self.groups[0]
            return ops.fused_predict_bp_prepadded(
                x, self.borders, g.split_features_bp, g.split_bins_bp,
                g.leaf_values, n_classes=self.n_outputs, backend=backend,
                block_n=block_n, block_t=block_t)
        # multiple groups: binarize once and reuse the grouped
        # index+gather loop (same rationale as DepthGroupedLayout —
        # per-group fusion would re-binarize x against every border
        # once per group)
        bins = ops.binarize_prepadded(x, self.borders, backend=backend)
        return self.leaf_sum(bins, backend=backend, block_t=block_t)

    def leaf_table_bytes(self) -> int:
        return sum(int(np.prod(g.leaf_values.shape)) * 4
                   for g in self.groups)

    def plane_bytes(self) -> int:
        """Bytes held by the split bit planes (both arrays, all groups)."""
        return sum(int(np.prod(g.split_features_bp.shape))
                   * g.split_features_bp.dtype.itemsize
                   + int(np.prod(g.split_bins_bp.shape))
                   * g.split_bins_bp.dtype.itemsize
                   for g in self.groups)

    def pool_row_bytes(self) -> tuple[int, int]:
        """(uint8 bytes, u1-plane bytes) one quantized pool row costs.

        The u1 figure — ceil(F/32) uint32 words — is achievable only
        for binary-split schemas (`binary_split`), where every bin id
        is 0/1 and `pack_pool_u1` packs the pool losslessly: the 8x
        pool-memory shrink of the paper's single-border case.
        """
        f = max(int(self.n_features), 1)
        return f, -(-f // 32) * 4

    def describe(self) -> dict[str, Any]:
        u8, u1 = self.pool_row_bytes()
        return {"layout": self.layout_name,
                "leaf_table_bytes": self.leaf_table_bytes(),
                "plane_bytes": self.plane_bytes(),
                "groups": {int(g.depth): int(g.n_trees)
                           for g in self.groups},
                **_leaf_steps([(g.leaf_values, g.n_trees)
                               for g in self.groups]),
                "binary_split": self.binary_split,
                "pool_row_bytes_u8": u8,
                "pool_row_bytes_u1": u1,
                "pool_shrink_x": (u8 / u1) if self.binary_split else 1.0}


@dataclasses.dataclass(frozen=True)
class NodesLayout:
    """Non-symmetric trees (`trees.NodeEnsemble`) as path matrices.

    W node slots and W leaf slots a tree (a power of two), k = 128 / W
    trees to each 128-lane row of the node arrays, and the trees padded
    to Tp, so that every array is lane-dense (see `kernels.node_index`):

      node_features, node_bins     (Tp/k, k*W) int32, slot j of tree t
      node_left, node_right          at [t // k, (t % k) * W + j]
      paths                        (Tp/k, W, k*W) bfloat16: P[t, l, j]
                                     at [t // k, l, (t % k) * W + j],
                                     +1 / -1 where the path to leaf l
                                     goes right / left at node j
      path_len                     (Tp/k, k*W) int32: the path's length
                                     (`node_index.PAD_PATH_LEN` for an
                                     unreached or padded leaf)
      leaf_values                  (Tp/G, Cp, G*W) float32, packed
                                     class-major (`ops.class_major`)

    The pallas kernel matches paths; the reference walks the node
    arrays from the root.  Both give each row its leaf, which the
    shared `leaf_gather` then sums.
    """
    layout_name = "nodes"
    borders: jax.Array           # (B, Fp) f32
    node_features: jax.Array     # (Tp/k, k*W) i32
    node_bins: jax.Array         # (Tp/k, k*W) i32
    node_left: jax.Array         # (Tp/k, k*W) i32
    node_right: jax.Array        # (Tp/k, k*W) i32
    paths: jax.Array             # (Tp/k, W, k*W) bf16
    path_len: jax.Array          # (Tp/k, k*W) i32
    leaf_values: jax.Array       # (Tp/G, Cp, G*W) f32, packed class-major
    n_outputs: int = 1           # static
    n_model_pads: int = 0        # static
    width: int = 8               # static: W
    nodes_per_tree: int = 0      # static: the most nodes a tree tests
    leaves_per_tree: int = 0     # static: the most leaves a tree has

    def leaf_sum(self, bins: jax.Array, *, backend: str,
                 block_t: int) -> jax.Array:
        idx = ops.node_index_prepadded(
            bins, self.node_features, self.node_bins, self.node_left,
            self.node_right, self.paths, self.path_len, width=self.width,
            backend=backend)
        return ops.leaf_gather_prepadded(idx, self.leaf_values,
                                         n_classes=self.n_outputs,
                                         backend=backend, block_t=block_t)

    def fused_raw(self, x: jax.Array, *, backend: str, block_n: int,
                  block_t: int) -> jax.Array:
        # no fused kernel walks node tables: binarize, then the sum
        bins = ops.binarize_prepadded(x, self.borders, backend=backend)
        return self.leaf_sum(bins, backend=backend, block_t=block_t)

    def leaf_table_bytes(self) -> int:
        return int(np.prod(self.leaf_values.shape)) * 4

    def lowered_bytes(self) -> int:
        """Bytes of every lowered model array but the borders."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (self.node_features, self.node_bins,
                             self.node_left, self.node_right, self.paths,
                             self.path_len, self.leaf_values))

    def describe(self) -> dict[str, Any]:
        return {"layout": self.layout_name,
                "leaf_table_bytes": self.leaf_table_bytes(),
                "lowered_bytes": self.lowered_bytes(),
                "width": self.width,
                "nodes_per_tree": self.nodes_per_tree,
                "leaves_per_tree": self.leaves_per_tree,
                **_leaf_steps([(self.leaf_values,
                                self.node_features.size // self.width)])}


def _pad_tree_axis(a, axis: int, target: int, value=0):
    return ops._pad_dim(a, axis, target, value=value, kind="model")


def _shard_bounds(n_trees: int, n_shards: int, t_align: int, lv):
    """(padded total, per-shard size) for an equal T-axis split where
    every shard stays a multiple of `t_align` and of the group G of the
    packed leaf table `lv` (so shards cut on group boundaries)."""
    align = math.lcm(max(t_align, 1), trees_per_row(n_trees, lv))
    unit = max(n_shards * align, 1)
    total = ops._round_up(max(n_trees, 1), unit)
    return total, total // n_shards


def _shard_table(lv, n_trees: int, total: int, per: int,
                 n_shards: int) -> list:
    """Each tree shard's rows of a packed leaf table of n_trees trees:
    padded with zero rows to `total` trees, cut every `per` trees
    (whole groups)."""
    g = trees_per_row(n_trees, lv)
    lv = _pad_tree_axis(lv, 0, total // g)
    rows = per // g
    return [lv[k * rows:(k + 1) * rows] for k in range(n_shards)]


def shard_trees(lowered: LoweredEnsemble, n_shards: int, *,
                t_align: int = 1) -> list:
    """Split a lowered ensemble's tree axis into `n_shards` equal
    slices for mesh model-parallel evaluation.

    Every shard is the same layout class with identical shapes and
    identical static metadata, so the shards stack into one leading
    mesh axis (`stack_tree_shards`) and flow through `shard_map` with
    `PartitionSpec(model_axis)` on every leaf.  Slices are padded with
    *neutral* trees — split features 0, split bins `PAD_SPLIT_BIN`
    (always-left), all-zero leaf rows — so a padded tree contributes
    exactly 0.0 and

        sum_k shard_k.leaf_sum(bins)  ==  lowered.leaf_sum(bins)

    up to float re-association: the per-shard partial sums reduce in a
    different order than the single-device tree loop, so tree-sharded
    results match at ~1e-6, not bit-for-bit (the row-sharded data path
    keeps exact equality — see docs/distributed.md).

    Grouped layouts (depth_grouped / bitpacked) shard *within* each
    depth group: every shard keeps the full group list (same static
    depths, same jaxpr) with 1/K of each group's trees.
    """
    if n_shards <= 1:
        return [lowered]
    if isinstance(lowered, SoaLayout):
        if lowered.tree_blocks is not None:
            raise ValueError(
                "shard_trees on a tree-blocked soa plan is unsupported: "
                "the block slices were cut for the single-device loop; "
                "lower with tree_block=0 before tree-sharding")
        n = lowered.split_features.shape[0]
        total, per = _shard_bounds(n, n_shards, t_align,
                                   lowered.leaf_values)
        sf = _pad_tree_axis(lowered.split_features, 0, total)
        sb = _pad_tree_axis(lowered.split_bins, 0, total,
                            value=PAD_SPLIT_BIN)
        lv = _shard_table(lowered.leaf_values, n, total, per, n_shards)
        return [SoaLayout(lowered.borders,
                          sf[k * per:(k + 1) * per],
                          sb[k * per:(k + 1) * per],
                          lv[k], None,
                          n_outputs=lowered.n_outputs,
                          n_model_pads=lowered.n_model_pads)
                for k in range(n_shards)]
    if isinstance(lowered, DepthMajorLayout):
        n = lowered.onehot.shape[0]
        total, per = _shard_bounds(n, n_shards, t_align,
                                   lowered.leaf_values)
        oh = _pad_tree_axis(lowered.onehot, 0, total)
        sb = _pad_tree_axis(lowered.split_bins_dm, 1, total,
                            value=PAD_SPLIT_BIN)
        lv = _shard_table(lowered.leaf_values, n, total, per, n_shards)
        return [DepthMajorLayout(lowered.borders,
                                 oh[k * per:(k + 1) * per],
                                 sb[:, k * per:(k + 1) * per],
                                 lowered.pow2,
                                 lv[k],
                                 n_outputs=lowered.n_outputs,
                                 n_model_pads=lowered.n_model_pads)
                for k in range(n_shards)]
    if isinstance(lowered, DepthGroupedLayout):
        shard_groups = [[] for _ in range(n_shards)]
        for g in lowered.groups:
            total, per = _shard_bounds(g.n_trees, n_shards, t_align,
                                       g.leaf_values)
            sf = _pad_tree_axis(g.split_features, 0, total)
            sb = _pad_tree_axis(g.split_bins, 0, total,
                                value=PAD_SPLIT_BIN)
            lv = _shard_table(g.leaf_values, g.n_trees, total, per,
                              n_shards)
            for k in range(n_shards):
                shard_groups[k].append(
                    DepthGroup(g.depth, sf[k * per:(k + 1) * per],
                               sb[k * per:(k + 1) * per], lv[k]))
        return [DepthGroupedLayout(lowered.borders, tuple(gs),
                                   n_outputs=lowered.n_outputs,
                                   n_model_pads=lowered.n_model_pads)
                for gs in shard_groups]
    if isinstance(lowered, BitpackedLayout):
        if all(g.n_trees % BP_TREE_BLOCK == 0 for g in lowered.groups):
            # lowered for the pallas bitplane kernels: every shard keeps
            # whole 128-tree lanes
            t_align = math.lcm(max(t_align, 1), BP_TREE_BLOCK)
        shard_groups = [[] for _ in range(n_shards)]
        for g in lowered.groups:
            total, per = _shard_bounds(g.n_trees, n_shards, t_align,
                                       g.leaf_values)
            sf = _pad_tree_axis(g.split_features_bp, 1, total)
            # uint8 planes can't hold PAD_SPLIT_BIN; pad 0 instead —
            # the padded trees' leaf rows are all-zero, so whichever
            # leaf the always-true comparison selects contributes 0.0
            pad_bin = (0 if g.split_bins_bp.dtype == jnp.uint8
                       else PAD_SPLIT_BIN)
            sb = _pad_tree_axis(g.split_bins_bp, 1, total, value=pad_bin)
            lv = _shard_table(g.leaf_values, g.n_trees, total, per,
                              n_shards)
            for k in range(n_shards):
                shard_groups[k].append(
                    BitpackedGroup(g.depth, sf[:, k * per:(k + 1) * per],
                                   sb[:, k * per:(k + 1) * per], lv[k]))
        return [BitpackedLayout(lowered.borders, tuple(gs),
                                n_outputs=lowered.n_outputs,
                                n_model_pads=lowered.n_model_pads,
                                binary_split=lowered.binary_split,
                                n_features=lowered.n_features)
                for gs in shard_groups]
    raise TypeError(f"shard_trees: unsupported lowered type "
                    f"{type(lowered).__name__}")


def stack_tree_shards(shards: list):
    """Stack per-shard lowered ensembles (from `shard_trees`) into one
    pytree with a leading mesh axis on every array leaf, ready for
    `shard_map` with `in_specs=P(model_axis)`.  The shard body peels
    the unit leading axis back off with `unstack_tree_shard`."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)


def unstack_tree_shard(stacked):
    """Drop the unit leading mesh axis `shard_map` leaves on every
    array of a stacked shard (inverse of `stack_tree_shards` inside
    the mapped body)."""
    return jax.tree_util.tree_map(lambda a: a[0], stacked)


def pack_pool_u1(bins: jax.Array) -> jax.Array:
    """Pack a binary-split quantized pool (N, F) of 0/1 bins into u1
    feature planes -> (N, ceil(F/32)) uint32.

    Only valid when every bin id is 0 or 1 (<= 1 border per feature —
    `BitpackedLayout.binary_split`); ragged feature tails are
    zero-padded lanes.  One row shrinks from F bytes to ceil(F/32)
    words: the paper's 8x pool-memory reduction for binary splits.
    """
    from repro.kernels import ref
    return jnp.transpose(ref.pack_bits(jnp.transpose(bins)))


def unpack_pool_u1(planes: jax.Array, n_features: int) -> jax.Array:
    """Inverse of `pack_pool_u1` -> (N, n_features) int32 bins."""
    from repro.kernels import ref
    return jnp.transpose(ref.unpack_bits(jnp.transpose(planes), n_features))


_register_lowered(SoaLayout,
                  ("borders", "split_features", "split_bins",
                   "leaf_values", "tree_blocks"),
                  ("n_outputs", "n_model_pads"))
_register_lowered(DepthMajorLayout,
                  ("borders", "onehot", "split_bins_dm", "pow2",
                   "leaf_values"),
                  ("n_outputs", "n_model_pads"))
_register_lowered(DepthGroup,
                  ("split_features", "split_bins", "leaf_values"),
                  ("depth",))
_register_lowered(DepthGroupedLayout,
                  ("borders", "groups"),
                  ("n_outputs", "n_model_pads"))
_register_lowered(BitpackedGroup,
                  ("split_features_bp", "split_bins_bp", "leaf_values"),
                  ("depth",))
_register_lowered(BitpackedLayout,
                  ("borders", "groups"),
                  ("n_outputs", "n_model_pads", "binary_split",
                   "n_features"))
_register_lowered(NodesLayout,
                  ("borders", "node_features", "node_bins", "node_left",
                   "node_right", "paths", "path_len", "leaf_values"),
                  ("n_outputs", "n_model_pads", "width", "nodes_per_tree",
                   "leaves_per_tree"))

# The union type plans hold.
LoweredEnsemble = (SoaLayout | DepthMajorLayout | DepthGroupedLayout
                   | BitpackedLayout | NodesLayout)


# --------------------------------------------------------------------------
# Layout registry (capability metadata for tuning / docs / CI)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LayoutSpec:
    name: str
    cls: type
    paper_analog: str            # which paper mechanism it encodes
    claimed_ops: tuple[str, ...]  # kernel ops the layout needs impls for
    memory: str                  # memory-cost note (docs table)
    when: str                    # when the tuner picks it

LAYOUTS: dict[str, LayoutSpec] = {
    "soa": LayoutSpec(
        name="soa", cls=SoaLayout,
        paper_analog="CatBoost SoA model arrays (compatibility default)",
        claimed_ops=("binarize", "leaf_index", "leaf_gather",
                     "fused_predict"),
        memory="T x Cp x 2^Dmax leaf table; (T, D) splits",
        when="uniform shallow models; tracer ensembles (sharded shards)"),
    "depth_major": LayoutSpec(
        name="depth_major", cls=DepthMajorLayout,
        paper_analog="hoisted pow2 / vmsgeu bit-plane loop (CalcIndexes)",
        claimed_ops=("binarize", "leaf_index", "leaf_gather",
                     "fused_predict"),
        memory="soa + T x D x F f32 one-hot gather matrix",
        when="uniform-depth models whose one-hot matrix fits the budget"),
    "depth_grouped": LayoutSpec(
        name="depth_grouped", cls=DepthGroupedLayout,
        paper_analog="equal-depth tree grouping (CalcTreesBlockedImpl)",
        claimed_ops=("binarize", "leaf_index", "leaf_gather",
                     "fused_predict"),
        memory="sum_d T_d x Cp x 2^d leaf tables (< soa when depths mix)",
        when="mixed true depths with enough shallow-tree savings"),
    "bitpacked": LayoutSpec(
        name="bitpacked", cls=BitpackedLayout,
        paper_analog="word-packed comparison loop (vmsgeu mask word + "
                     "integer shift/or index assembly)",
        claimed_ops=("binarize", "leaf_index", "leaf_gather",
                     "fused_predict"),
        memory="grouped leaf tables + 2 x (d, T_d) integer bit planes; "
               "u1 pool planes when binary-split",
        when="mixed depths whose one-hot/f32 working set blows the "
             "VMEM budget"),
    "nodes": LayoutSpec(
        name="nodes", cls=NodesLayout,
        paper_analog="non-symmetric trees (Lossguide / Depthwise): "
                     "per-node tests, then a path match on the MXU",
        claimed_ops=("binarize", "node_index", "leaf_gather"),
        memory="T x Cp x W leaf table; (T, W, W) bf16 path matrices; "
               "four (T, W) int32 node arrays",
        when="every NodeEnsemble (its only layout); oblivious models "
             "only when asked for"),
}

LAYOUT_NAMES = tuple(LAYOUTS)


def format_layout_table() -> str:
    """The layout matrix as a markdown table (docs/layouts.md embeds
    this; `launch.serve --show-kernels` prints it)."""
    cols = ("layout", "paper analog", "memory cost", "when tuning picks it")
    rows = [(s.name, s.paper_analog, s.memory, s.when)
            for s in LAYOUTS.values()]
    widths = [max(len(c), *(len(r[i]) for r in rows))
              for i, c in enumerate(cols)]
    def line(vals):
        return "| " + " | ".join(v.ljust(w)
                                 for v, w in zip(vals, widths)) + " |"
    out = [line(cols), "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
    out += [line(r) for r in rows]
    return "\n".join(out)


# --------------------------------------------------------------------------
# Lowering
# --------------------------------------------------------------------------
def lower(ensemble, layout: str = "soa", *, backend: str = "ref",
          t_align: int = STAGED_TREE_ALIGN,
          tree_block: int = 0) -> LoweredEnsemble:
    """Lower a logical `ObliviousEnsemble` into one physical layout.

    The one-time step `Predictor.build` runs: every model-side pad
    (feature axis to the lane width for pallas, tree axis to the kernel
    block) and every structure-derived array (one-hot gather matrix,
    bit-plane transposes, depth groups) is materialized here, so the
    per-call kernels only ever touch data-side padding.

    `backend` decides the padding contract ("pallas" pads to block
    multiples; anything else keeps exact shapes — the jnp reference
    kernels accept any shape, so padding would only add wasted math).
    `t_align` is the tree-axis block (the fused plan's block_t, or
    `STAGED_TREE_ALIGN`); the pallas bitplane kernels put trees on the
    lane axis, so bitpacked groups pad to whole 128-tree lanes on top.
    `tree_block` enables the staged soa tree-blocked loop (soa layout
    only).
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; known: "
                         f"{LAYOUT_NAMES}")
    if isinstance(ensemble, NodeEnsemble) and layout != "nodes":
        raise ValueError(
            f"a NodeEnsemble (non-symmetric trees) lowers to the 'nodes' "
            f"layout only; {layout!r} holds one split per depth")
    if layout == "bitpacked" and backend == "pallas":
        t_align = math.lcm(max(int(t_align), 1), BP_TREE_BLOCK)
    ctx = _LowerCtx(pallas=backend == "pallas", t_align=t_align)
    if layout == "soa":
        return _lower_soa(ensemble, ctx, tree_block)
    if layout == "depth_major":
        return _lower_depth_major(ensemble, ctx)
    if layout == "bitpacked":
        return _lower_bitpacked(ensemble, ctx)
    if layout == "nodes":
        return _lower_nodes(ensemble, ctx)
    return _lower_depth_grouped(ensemble, ctx)


class _LowerCtx:
    """Pad helper counting model-side pad ops locally (the global
    `ops.pad_stats` counter may tick from other threads concurrently)."""

    def __init__(self, *, pallas: bool, t_align: int):
        self.pallas = pallas
        self.t_align = max(int(t_align), 1)
        self.n_pads = 0

    def pad(self, a, axis, target, value=0):
        out = ops._pad_dim(a, axis, target, value=value, kind="model")
        if out is not a:
            self.n_pads += 1
        return out

    def pad_borders(self, borders):
        if not self.pallas:
            return borders
        fp = ops._round_up(max(borders.shape[1], 1), FEATURE_ALIGN)
        return self.pad(borders, 1, fp, value=np.float32(np.inf))

    def pad_trees(self, sf, sb, lv):
        """Pad the tree axis to whole groups of the packed table (pallas:
        and to the tree block) and lower the (T, L, C) leaf table to the
        packed class-major (Tp/G, Cp, G*Lp) form every layout holds."""
        T, L, _ = lv.shape
        align = tuning.trees_per_step(L)
        if self.pallas:
            align = math.lcm(self.t_align, align)
        tp = ops._round_up(max(T, 1), align)
        self.n_pads += ops.leaf_pads(lv.shape, tp)[0]
        return (self.pad(sf, 0, tp),
                self.pad(sb, 0, tp, value=PAD_SPLIT_BIN),
                ops.class_major(lv, tp))


def _lower_soa(ensemble, ctx: _LowerCtx, tree_block: int) -> SoaLayout:
    borders = ctx.pad_borders(ensemble.borders)
    if tree_block and ensemble.n_trees > tree_block:
        # blocks cut on group boundaries of the packed leaf table
        step = ops._round_up(tree_block, tuning.trees_per_step(
            ensemble.leaf_values.shape[1]))
        blocks = []
        for start in range(0, ensemble.n_trees, step):
            blk = ensemble.slice_trees(
                start, min(start + step, ensemble.n_trees))
            blocks.append(ctx.pad_trees(blk.split_features, blk.split_bins,
                                        blk.leaf_values))
        # the blocked path never reads the whole-ensemble arrays, so they
        # are empty rather than a second copy of the full model
        return SoaLayout(borders, ensemble.split_features[:0],
                         ensemble.split_bins[:0], blocks[0][2][:0],
                         tuple(blocks),
                         n_outputs=ensemble.n_outputs,
                         n_model_pads=ctx.n_pads)
    sf, sb, lv = ctx.pad_trees(ensemble.split_features, ensemble.split_bins,
                               ensemble.leaf_values)
    return SoaLayout(borders, sf, sb, lv, None,
                     n_outputs=ensemble.n_outputs, n_model_pads=ctx.n_pads)


def _lower_depth_major(ensemble, ctx: _LowerCtx) -> DepthMajorLayout:
    borders = ctx.pad_borders(ensemble.borders)
    sf, sb, lv = ctx.pad_trees(ensemble.split_features, ensemble.split_bins,
                               ensemble.leaf_values)
    D = ensemble.depth
    Fp = borders.shape[1]
    # The per-call iota/one-hot the soa kernels rebuild, materialized
    # once: row (t, d) of the gather matrix selects feature sf[t, d].
    f_ids = jnp.arange(Fp, dtype=jnp.int32)[None, None, :]
    onehot = (f_ids == sf[:, :, None]).astype(jnp.float32)   # (Tp, D, Fp)
    pow2 = jnp.asarray((1 << np.arange(D, dtype=np.int64))
                       .astype(np.float32)[:, None])          # (D, 1)
    return DepthMajorLayout(borders, onehot, jnp.transpose(sb), pow2, lv,
                            n_outputs=ensemble.n_outputs,
                            n_model_pads=ctx.n_pads)


def _lower_depth_grouped(ensemble, ctx: _LowerCtx) -> DepthGroupedLayout:
    if not is_concrete(ensemble):
        raise ValueError(
            "depth_grouped lowering reads split_bins to bucket trees by "
            "true depth; the ensemble holds tracers (per-shard plans "
            "inside shard_map must lower to 'soa')")
    borders = ctx.pad_borders(ensemble.borders)
    # Depth-0 trees (every level padded) still need one always-left
    # level so the kernels see D >= 1; their single reachable leaf is
    # index 0 either way.
    depths = np.maximum(np.asarray(ensemble.true_depths), 1)
    sf = np.asarray(ensemble.split_features)
    sb = np.asarray(ensemble.split_bins)
    lv = np.asarray(ensemble.leaf_values)
    groups = []
    for d in sorted(set(int(v) for v in depths)):
        rows = np.flatnonzero(depths == d)
        gsf = jnp.asarray(sf[rows][:, :d])
        gsb = jnp.asarray(sb[rows][:, :d])
        # trailing pad levels always go left, so only the first 2^d
        # leaves are reachable — the shallow tree's actual table
        glv = jnp.asarray(lv[rows][:, :1 << d])
        gsf, gsb, glv = ctx.pad_trees(gsf, gsb, glv)
        groups.append(DepthGroup(d, gsf, gsb, glv))
    return DepthGroupedLayout(borders, tuple(groups),
                              n_outputs=ensemble.n_outputs,
                              n_model_pads=ctx.n_pads)


def _lower_bitpacked(ensemble, ctx: _LowerCtx) -> BitpackedLayout:
    if not is_concrete(ensemble):
        raise ValueError(
            "bitpacked lowering reads split_bins to bucket trees and "
            "narrow threshold planes; the ensemble holds tracers "
            "(per-shard plans inside shard_map must lower to 'soa')")
    borders = ctx.pad_borders(ensemble.borders)
    # Same depth bucketing as depth_grouped (depth-0 trees clamp to one
    # always-left level), then each group's split arrays transpose to
    # (d, Tg_p) bit-plane order.  Threshold planes narrow to uint8 when
    # every value fits — comparing uint8 bins against a uint8 plane
    # never widens the gathered panel — but pallas lowering pads trees
    # with PAD_SPLIT_BIN (2^30), which only int32 can hold.
    depths = np.maximum(np.asarray(ensemble.true_depths), 1)
    sf = np.asarray(ensemble.split_features)
    sb = np.asarray(ensemble.split_bins)
    lv = np.asarray(ensemble.leaf_values)
    groups = []
    for d in sorted(set(int(v) for v in depths)):
        rows = np.flatnonzero(depths == d)
        gsf_np = sf[rows][:, :d]
        gsb_np = sb[rows][:, :d]
        narrow = not ctx.pallas and gsb_np.size and gsb_np.max() <= 255 \
            and gsb_np.min() >= 0
        gsf, gsb, glv = ctx.pad_trees(jnp.asarray(gsf_np),
                                      jnp.asarray(gsb_np),
                                      jnp.asarray(lv[rows][:, :1 << d]))
        gsb_bp = jnp.transpose(gsb)
        if narrow:
            # trees padded to whole groups have all-zero leaves, so any
            # bin will do for them: 0 in place of PAD_SPLIT_BIN
            gsb_bp = jnp.where(gsb_bp == PAD_SPLIT_BIN, 0,
                               gsb_bp).astype(jnp.uint8)
        groups.append(BitpackedGroup(d, jnp.transpose(gsf), gsb_bp, glv))
    n_borders = np.asarray(ensemble.n_borders)
    return BitpackedLayout(borders, tuple(groups),
                           n_outputs=ensemble.n_outputs,
                           n_model_pads=ctx.n_pads,
                           binary_split=bool((n_borders <= 1).all()),
                           n_features=int(ensemble.borders.shape[1]))


def node_width(n_nodes: int, n_leaves: int) -> int:
    """W, the node and leaf slots a tree gets in the `nodes` layout:
    the power of two >= both counts, at least 8 (one sublane tile)."""
    return max(8, 1 << (max(n_nodes, n_leaves, 1) - 1).bit_length())


def _lower_nodes(ensemble, ctx: _LowerCtx) -> NodesLayout:
    """Each tree's path matrix (`NodeEnsemble.path_matrix`), in W slots,
    with k = 128 / W trees packed to each 128-lane row."""
    if not is_concrete(ensemble):
        raise ValueError(
            "nodes lowering reads each tree's structure to build its path "
            "matrix; the ensemble holds tracers")
    if not isinstance(ensemble, NodeEnsemble):
        ensemble = NodeEnsemble.from_oblivious(ensemble)
    borders = ctx.pad_borders(ensemble.borders)
    path, path_len = ensemble.path_matrix()
    T, n_slots = ensemble.node_features.shape
    n_leaves = ensemble.leaf_values.shape[1]
    width = node_width(n_slots, n_leaves)
    if ctx.pallas and width > ops.FEATURE_ALIGN:
        raise ValueError(
            f"the pallas node_index kernel takes trees of at most "
            f"{ops.FEATURE_ALIGN} leaves and {ops.FEATURE_ALIGN - 1} nodes; "
            f"this ensemble needs {width} slots a tree")
    k = max(1, ops.FEATURE_ALIGN // width)
    align = math.lcm(k, tuning.trees_per_step(width))
    if ctx.pallas:
        align = math.lcm(align, ctx.t_align, node_k.tree_block(width))
    tp = ops._round_up(max(T, 1), align)

    def pack(a, fill):      # (T, n) -> padded (tp, width) -> (tp/k, k*W)
        out = np.full((tp, width), fill, np.int32)
        out[:T, :a.shape[1]] = np.asarray(a)
        return jnp.asarray(out.reshape(tp // k, k * width))

    # padded slots and trees: feature 0, never right, both children
    # leaf 0, so a walk through a padded tree ends in its zero leaf
    p = np.zeros((tp, width, width), np.float32)
    p[:T, :n_leaves, :n_slots] = path
    packed_p = (p.reshape(tp // k, k, width, width).transpose(0, 2, 1, 3)
                .reshape(tp // k, width, k * width))
    lv = ops._pad_dim(ensemble.leaf_values, 1, width, kind="model")
    ctx.n_pads += ops.leaf_pads(lv.shape, tp)[0] + (width != n_leaves)
    lv_cm = ops.class_major(lv, tp)
    return NodesLayout(
        borders, pack(ensemble.node_features, 0),
        pack(ensemble.node_bins, PAD_SPLIT_BIN),
        pack(ensemble.node_left, -1), pack(ensemble.node_right, -1),
        jnp.asarray(packed_p, jnp.bfloat16),
        pack(path_len, node_k.PAD_PATH_LEN), lv_cm,
        n_outputs=ensemble.n_outputs, n_model_pads=ctx.n_pads, width=width,
        nodes_per_tree=int((path != 0).any(axis=1).sum(axis=1).max(
            initial=0)),
        leaves_per_tree=int((path_len >= 0).sum(axis=1).max(initial=0)))
