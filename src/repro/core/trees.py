"""Oblivious-tree ensemble: the CatBoost model structure, as a JAX pytree.

Structure-of-arrays layout (exactly what the paper's hotspots consume):
  split_features (T, D) int32 — feature id tested at depth d of tree t
  split_bins     (T, D) int32 — border id; sample goes right iff bin >= split_bin
  leaf_values    (T, 2^D, C) float32
  borders        (B, F) float32 — per-feature bin borders (padded with +inf)
  n_borders      (F,)   int32   — true border count per feature

All trees share a single depth D (CatBoost pads shallower trees the same
way: repeat a split or use an always-false one; we use split_bin = PAD so
the padded levels always go left).

`NodeEnsemble` holds the non-symmetric trees of CatBoost's Lossguide and
Depthwise grow policies as node tables (see its docstring).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Canonical definition lives with the kernels (padding happens there);
# re-exported here because the model layer is where most callers look.
from repro.kernels.ops import PAD_SPLIT_BIN  # noqa: F401


@dataclasses.dataclass(frozen=True)
class ObliviousEnsemble:
    split_features: jax.Array    # (T, D) int32
    split_bins: jax.Array        # (T, D) int32
    leaf_values: jax.Array       # (T, 2^D, C) float32
    borders: jax.Array           # (B, F) float32
    n_borders: jax.Array         # (F,) int32
    base_score: jax.Array = None  # (C,) float32 additive offset

    def __post_init__(self):
        # Default the base score to zeros at *construction* time only.
        # The pytree unflatten below bypasses __init__, so tree_map /
        # tree_unflatten never re-enter this default path — a mapped-to-
        # None leaf stays None instead of crashing on
        # `None.shape` (regression: tests/test_gbdt.py pytree round-trip).
        if self.base_score is None:
            object.__setattr__(
                self, "base_score",
                jnp.zeros((self.leaf_values.shape[2],), jnp.float32))

    @property
    def n_trees(self) -> int:
        return self.split_features.shape[0]

    @property
    def depth(self) -> int:
        return self.split_features.shape[1]

    @property
    def true_depths(self) -> np.ndarray:
        """(T,) int32 — each tree's depth before depth padding.

        A tree shallower than the shared ensemble depth carries trailing
        always-left levels (`split_bins == PAD_SPLIT_BIN`); its true
        depth is the level count with those trailing pads stripped (a
        PAD level *between* real levels still counts — only the trailing
        run is padding, matching the importer's convention).  Model
        structure, so concrete arrays only: reading it on traced arrays
        raises (use `layout.is_concrete` to guard).
        """
        sb = np.asarray(self.split_bins)
        if sb.shape[0] == 0:
            return np.zeros((0,), np.int32)
        trailing_pad = np.cumprod(
            (sb == PAD_SPLIT_BIN)[:, ::-1], axis=1).sum(axis=1)
        return (sb.shape[1] - trailing_pad).astype(np.int32)

    def lower(self, layout: str = "soa", **lower_kw):
        """Lower the logical model into a physical `LoweredEnsemble`
        layout (see `repro.core.layout`): "soa", "depth_major" or
        "depth_grouped"."""
        from repro.core import layout as layout_mod
        return layout_mod.lower(self, layout, **lower_kw)

    @property
    def n_outputs(self) -> int:
        return self.leaf_values.shape[2]

    @property
    def n_features(self) -> int:
        return self.borders.shape[1]

    def slice_trees(self, start: int, stop: int) -> "ObliviousEnsemble":
        """Tree-block view (the paper's CalcTreesBlockedImpl granularity)."""
        if not 0 <= start <= stop <= self.n_trees:
            raise ValueError(
                f"slice_trees({start}, {stop}) out of range for an "
                f"ensemble of {self.n_trees} trees "
                "(need 0 <= start <= stop <= n_trees)")
        return dataclasses.replace(
            self,
            split_features=self.split_features[start:stop],
            split_bins=self.split_bins[start:stop],
            leaf_values=self.leaf_values[start:stop],
        )

    # -- persistence (used by serving + checkpoint tests) ------------------
    def save(self, path: str | pathlib.Path) -> None:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        np.savez(
            path,
            split_features=np.asarray(self.split_features),
            split_bins=np.asarray(self.split_bins),
            leaf_values=np.asarray(self.leaf_values),
            borders=np.asarray(self.borders),
            n_borders=np.asarray(self.n_borders),
            base_score=np.asarray(self.base_score),
        )

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "ObliviousEnsemble":
        with np.load(path) as z:
            return cls(**{k: jnp.asarray(z[k]) for k in z.files})

    def describe(self) -> dict[str, Any]:
        return dict(n_trees=self.n_trees, depth=self.depth,
                    n_outputs=self.n_outputs, n_features=self.n_features,
                    n_leaf_params=int(np.prod(self.leaf_values.shape)))

    def describe_json(self) -> str:
        return json.dumps(self.describe())


# Pytree registration.  Not `jax.tree_util.register_dataclass`: its
# unflatten calls the constructor, which would re-run __post_init__'s
# base_score default on every tree_unflatten — with non-array leaves
# (tree_map to None, tree_transpose, structural unflattens) that path
# dereferences `leaf_values.shape` on whatever leaf happens to be there.
# Unflattening here rebuilds the instance field-by-field without
# __init__, so lowering/mapping an ensemble is a pure structural
# operation and the zeros default exists only on user construction.
_ENSEMBLE_FIELDS = ("split_features", "split_bins", "leaf_values",
                    "borders", "n_borders", "base_score")


def _ensemble_flatten_with_keys(e: "ObliviousEnsemble"):
    children = tuple((jax.tree_util.GetAttrKey(f), getattr(e, f))
                     for f in _ENSEMBLE_FIELDS)
    return children, None


def _ensemble_flatten(e: "ObliviousEnsemble"):
    return tuple(getattr(e, f) for f in _ENSEMBLE_FIELDS), None


def _ensemble_unflatten(_aux, children) -> "ObliviousEnsemble":
    obj = object.__new__(ObliviousEnsemble)
    for f, c in zip(_ENSEMBLE_FIELDS, children):
        object.__setattr__(obj, f, c)
    return obj


jax.tree_util.register_pytree_with_keys(
    ObliviousEnsemble, _ensemble_flatten_with_keys, _ensemble_unflatten,
    _ensemble_flatten)


@dataclasses.dataclass(frozen=True)
class NodeEnsemble:
    """Non-symmetric trees (CatBoost's Lossguide and Depthwise grow
    policies) as a node table, one row of nodes per tree:

      node_features (T, Nmax) int32 — feature id node j of tree t tests
      node_bins     (T, Nmax) int32 — go right iff bin >= node_bins
      node_left     (T, Nmax) int32 — child on the left: a node id >= 0,
      node_right    (T, Nmax) int32   or leaf l written as -1 - l
      leaf_values   (T, Lmax, C) float32
      borders, n_borders, base_score — as `ObliviousEnsemble`

    Node 0 is each tree's root; slots no path from the root reaches
    are padding.  A tree of a single leaf is a root whose two children
    are that leaf.  Every other leaf is reached by exactly one path
    (`path_matrix` checks), so a row's leaf is where the walk from the
    root, `node = right if bin >= node_bins else left`, ends.
    """
    node_features: jax.Array     # (T, Nmax) int32
    node_bins: jax.Array         # (T, Nmax) int32
    node_left: jax.Array         # (T, Nmax) int32
    node_right: jax.Array        # (T, Nmax) int32
    leaf_values: jax.Array       # (T, Lmax, C) float32
    borders: jax.Array           # (B, F) float32
    n_borders: jax.Array         # (F,) int32
    base_score: jax.Array = None  # (C,) float32 additive offset

    def __post_init__(self):
        if self.base_score is None:
            object.__setattr__(
                self, "base_score",
                jnp.zeros((self.leaf_values.shape[2],), jnp.float32))

    @property
    def n_trees(self) -> int:
        return self.node_features.shape[0]

    @property
    def n_outputs(self) -> int:
        return self.leaf_values.shape[2]

    @property
    def n_features(self) -> int:
        return self.borders.shape[1]

    def path_matrix(self) -> tuple[np.ndarray, np.ndarray]:
        """Each leaf's path from its tree's root, as numpy:

          P    (T, Lmax, Nmax) int8 — +1 / -1 where the path to leaf l
               goes right / left at node j, 0 off the path
          plen (T, Lmax) int32 — the path's length; -1 for a leaf no
               path reaches

        A node whose two children are the same leaf tests nothing and
        takes no step.  Model structure, so concrete arrays only;
        computed once per instance.  Raises ValueError on a child out
        of range, a node reached twice (a cycle or a shared subtree) or
        a leaf reached by two paths."""
        cached = self.__dict__.get("_path_matrix")
        if cached is not None:
            return cached
        left = np.asarray(self.node_left).astype(np.int64)
        right = np.asarray(self.node_right).astype(np.int64)
        t, n = left.shape
        n_leaves = self.leaf_values.shape[1]
        if ((left >= n) | (right >= n) | (-1 - left >= n_leaves)
                | (-1 - right >= n_leaves)).any():
            raise ValueError("a child is out of range of the node table "
                             f"({n} nodes, {n_leaves} leaves)")
        trees = np.arange(t)[:, None]
        # reach the nodes from each root, one level a pass; a node with
        # two reached parents (or the root with one) is not a tree
        reached = np.zeros((t, n), bool)
        reached[:, 0] = True
        for _ in range(n):
            parents = np.zeros((t, n), np.int64)
            for kids in (left, right):
                at = reached & (kids >= 0)
                np.add.at(parents, (np.broadcast_to(trees, (t, n))[at],
                                    kids[at]), 1)
            if (parents > 1).any() or (parents[:, 0] > 0).any():
                raise ValueError("a node is reached twice: the table "
                                 "holds a cycle or a shared subtree")
            now = reached | (parents > 0)
            if (now == reached).all():
                break
            reached = now
        # each node's and each leaf's parent, and the side it hangs on
        up = np.full((t, n), -1, np.int64)
        up_side = np.zeros((t, n), np.int8)
        leaf_up = np.full((t, n_leaves), -1, np.int64)
        leaf_side = np.zeros((t, n_leaves), np.int8)
        hits = np.zeros((t, n_leaves), np.int64)
        no_test = reached & (left == right) & (left < 0)
        for kids, direction in ((left, -1), (right, 1)):
            tt, jj = np.nonzero(reached & (kids >= 0))
            up[tt, kids[tt, jj]], up_side[tt, kids[tt, jj]] = jj, direction
            at = reached & (kids < 0) & ~(no_test & (direction == 1))
            tt, jj = np.nonzero(at)
            leaf = -1 - kids[tt, jj]
            np.add.at(hits, (tt, leaf), 1)
            leaf_up[tt, leaf] = jj
            leaf_side[tt, leaf] = np.where(no_test[tt, jj], 0, direction)
        if (hits > 1).any():
            raise ValueError("a leaf is reached by two paths")
        # climb from every leaf to its root, marking each step
        p = np.zeros((t, n_leaves, n), np.int8)
        plen = np.where(hits > 0, 0, -1).astype(np.int32)
        tl = np.broadcast_to(trees, (t, n_leaves))
        ll = np.broadcast_to(np.arange(n_leaves)[None], (t, n_leaves))
        node, side = leaf_up.copy(), leaf_side.copy()
        while (node >= 0).any():
            on = (node >= 0) & (side != 0)
            p[tl[on], ll[on], node[on]] = side[on]
            plen += on
            at = np.maximum(node, 0)
            node, side = (np.where(node >= 0, up[tl, at], -1),
                          np.where(node >= 0, up_side[tl, at], 0))
        object.__setattr__(self, "_path_matrix", (p, plen))
        return p, plen

    @property
    def depth(self) -> int:
        """The longest root-to-leaf path over all trees."""
        return int(max(self.path_matrix()[1].max(initial=0), 0))

    @classmethod
    def from_oblivious(cls, ensemble: "ObliviousEnsemble") -> "NodeEnsemble":
        """The same trees as a node table: depth d of an oblivious tree
        becomes the 2^d nodes of level d, numbered breadth first, and
        leaf ids keep the oblivious index sum_d 2^d right_d.  Padded
        levels (`PAD_SPLIT_BIN`) stay nodes that always go left."""
        sf, sb = np.asarray(ensemble.split_features), \
            np.asarray(ensemble.split_bins)
        t, d = sf.shape
        n = max((1 << d) - 1, 1)
        nf, nb = np.zeros((t, n), np.int32), np.zeros((t, n), np.int32)
        left, right = np.full((t, n), -1, np.int32), \
            np.full((t, n), -1, np.int32)
        for level in range(d):
            for prefix in range(1 << level):
                j = (1 << level) - 1 + prefix
                nf[:, j], nb[:, j] = sf[:, level], sb[:, level]
                kids = (prefix, prefix + (1 << level))
                if level + 1 < d:
                    left[:, j], right[:, j] = ((1 << (level + 1)) - 1
                                               + k for k in kids)
                else:
                    left[:, j], right[:, j] = (-1 - k for k in kids)
        return cls(jnp.asarray(nf), jnp.asarray(nb), jnp.asarray(left),
                   jnp.asarray(right), ensemble.leaf_values,
                   ensemble.borders, ensemble.n_borders,
                   ensemble.base_score)

    def describe(self) -> dict[str, Any]:
        return dict(n_trees=self.n_trees, depth=self.depth,
                    n_outputs=self.n_outputs, n_features=self.n_features,
                    max_nodes=int(self.node_features.shape[1]),
                    max_leaves=int(self.leaf_values.shape[1]),
                    n_leaf_params=int(np.prod(self.leaf_values.shape)))


_NODE_FIELDS = ("node_features", "node_bins", "node_left", "node_right",
                "leaf_values", "borders", "n_borders", "base_score")


def _node_flatten_with_keys(e: NodeEnsemble):
    return tuple((jax.tree_util.GetAttrKey(f), getattr(e, f))
                 for f in _NODE_FIELDS), None


def _node_unflatten(_aux, children) -> NodeEnsemble:
    obj = object.__new__(NodeEnsemble)
    for f, c in zip(_NODE_FIELDS, children):
        object.__setattr__(obj, f, c)
    return obj


jax.tree_util.register_pytree_with_keys(
    NodeEnsemble, _node_flatten_with_keys, _node_unflatten,
    lambda e: (tuple(getattr(e, f) for f in _NODE_FIELDS), None))


def empty_ensemble(n_features: int, depth: int, n_outputs: int,
                   borders: jax.Array, n_borders: jax.Array
                   ) -> ObliviousEnsemble:
    return ObliviousEnsemble(
        split_features=jnp.zeros((0, depth), jnp.int32),
        split_bins=jnp.zeros((0, depth), jnp.int32),
        leaf_values=jnp.zeros((0, 2 ** depth, n_outputs), jnp.float32),
        borders=borders,
        n_borders=n_borders,
    )


def truncate_tree_depths(ensemble: ObliviousEnsemble,
                         depths) -> ObliviousEnsemble:
    """Truncate tree t to `depths[t]` levels via trailing always-left
    pads — the CatBoost shallow-tree convention (`split_bins` =
    `PAD_SPLIT_BIN` beyond the true depth, unreachable leaf values
    zeroed).  The canonical builder of mixed-depth ensembles: the
    layout tests and the layout-sweep benchmark both construct their
    covertype-style mixed-depth models through this, so the convention
    lives in exactly one place.  `depths[t]` may be 0 (a constant tree:
    only leaf 0 reachable) up to `ensemble.depth` (unchanged).
    """
    depths = np.asarray(depths, np.int64)
    if depths.shape != (ensemble.n_trees,):
        raise ValueError(f"need one depth per tree: got shape "
                         f"{depths.shape} for {ensemble.n_trees} trees")
    if depths.size and not (0 <= depths.min()
                            and depths.max() <= ensemble.depth):
        raise ValueError(f"depths must lie in [0, {ensemble.depth}], "
                         f"got [{depths.min()}, {depths.max()}]")
    sb = np.asarray(ensemble.split_bins).copy()
    lv = np.asarray(ensemble.leaf_values).copy()
    for t, d in enumerate(depths):
        sb[t, d:] = PAD_SPLIT_BIN
        lv[t, 1 << d:] = 0.0
    return dataclasses.replace(ensemble, split_bins=jnp.asarray(sb),
                               leaf_values=jnp.asarray(lv))


def concat_ensembles(a: ObliviousEnsemble, b: ObliviousEnsemble
                     ) -> ObliviousEnsemble:
    """Append b's trees to a (a's borders/base_score win).

    Two ensembles are only summable when they agree on tree depth,
    output width and the quantization borders — a mismatch silently
    produces garbage leaf sums, so each is a hard error here.
    """
    if a.depth != b.depth:
        raise ValueError(f"cannot concat ensembles of different depth: "
                         f"{a.depth} vs {b.depth}")
    if a.n_outputs != b.n_outputs:
        raise ValueError(f"cannot concat ensembles with different "
                         f"n_outputs: {a.n_outputs} vs {b.n_outputs}")
    if a.borders.shape != b.borders.shape:
        raise ValueError(f"cannot concat ensembles quantized with "
                         f"different border tables: {a.borders.shape} vs "
                         f"{b.borders.shape}")
    if not (isinstance(a.borders, jax.core.Tracer)
            or isinstance(b.borders, jax.core.Tracer)):
        if not np.array_equal(np.asarray(a.borders), np.asarray(b.borders)):
            raise ValueError(
                "cannot concat ensembles quantized with different border "
                "values: split_bins index into incompatible bin spaces")
    return dataclasses.replace(
        a,
        split_features=jnp.concatenate([a.split_features, b.split_features]),
        split_bins=jnp.concatenate([a.split_bins, b.split_bins]),
        leaf_values=jnp.concatenate([a.leaf_values, b.leaf_values]),
    )
