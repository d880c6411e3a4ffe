"""Capability-matrix enumeration and abstract tracing.

Cells are enumerated from `registry.table()` exactly the way
tests/test_differential.py builds its parametrization — one cell per
(op, impl, layout, bin-dtype) claim — so a new registration (or a new
layout/dtype claim on an existing one) is covered by the contract
checker with zero new code here.

Each cell maps to one or more *call variants*: concrete ShapeDtypeStruct
argument lists for the registered fn at canonical dims, traced with
`jax.make_jaxpr` (never executed, never compiled).  Layout-independent
ops (binarize, l2sq, histogram) produce identical avals across layouts,
so the module-level trace cache collapses them; the checker's
cells/traces counters make the collapse visible.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.analysis import jaxpr_tools

# Canonical dims.  Small on purpose: make_jaxpr cost is shape-blind,
# and the lint rules are dtype/structure properties, not size ones.
N, F, B, T, D, L, C = 64, 7, 9, 6, 4, 16, 2
TP, FP = 16, 128      # padded tree/feature dims the lowered layouts carry
NW = 32               # node/leaf slots a tree of the nodes layout: 4 a row
B_WIDE = 300          # >255 borders: forces the int32 bins scratch path


@dataclasses.dataclass(frozen=True)
class Cell:
    """One capability claim: op × impl × layout × bin-dtype."""
    op: str
    impl: str
    layout: str
    dtype: str

    @property
    def key(self) -> str:
        return f"{self.op}:{self.impl}"

    def __str__(self) -> str:
        return f"{self.key}[{self.layout}/{self.dtype}]"


def enumerate_cells(*, ops_filter=None, impls_filter=None) -> list[Cell]:
    """Every capability-table cell, optionally filtered.  Filters take
    op names / "op:impl" keys respectively."""
    out = []
    for row in registry.table():
        if ops_filter is not None and row["op"] not in ops_filter:
            continue
        if impls_filter is not None \
                and f"{row['op']}:{row['impl']}" not in impls_filter:
            continue
        for lay in row["layouts"].split("/"):
            for dt in row["dtypes"].split("/"):
                out.append(Cell(row["op"], row["impl"], lay, dt))
    return out


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def cell_variants(cell: Cell) -> list[tuple[tuple, dict]]:
    """(args, static kwargs) call variants to trace for one cell.

    Shapes mirror the differential harness's call conventions: soa ops
    take raw (T, D) arrays (the registered wrappers pad), depth-major
    ops take the pre-lowered padded (TP, D, FP) arrays, bitpacked ops
    take the (D, TP) transposed planes.
    """
    f32, i32 = jnp.float32, jnp.int32
    bt = jnp.dtype(cell.dtype) if cell.dtype in ("uint8", "int32") else i32

    if cell.op == "binarize":
        return [((_sds((N, F), f32), _sds((B, F), f32)), {})]

    if cell.op == "l2sq":
        refs = _sds((16, 5), f32)
        return [((_sds((8, 5), f32), refs), {}),    # matrix
                ((_sds((5,), f32), refs), {})]      # rowwise

    if cell.op == "leaf_index":
        if cell.layout in ("soa", "depth_grouped"):
            return [((_sds((N, F), bt), _sds((T, D), i32),
                      _sds((T, D), i32)), {})]
        if cell.layout == "depth_major":
            return [((_sds((N, FP), bt), _sds((TP, D, FP), f32),
                      _sds((D, TP), i32), _sds((D, 1), f32)), {})]
        # bitpacked: the ref path consumes the lowering's planes, which
        # are narrowed to uint8 for u8 pools — trace what production
        # feeds it, or the widening lint would flag the promotion jnp
        # inserts for a mixed uint8-vs-int32 compare that never runs.
        plane = (jnp.uint8 if (cell.dtype == "uint8"
                               and cell.impl.startswith("ref")) else i32)
        return [((_sds((N, F), bt), _sds((D, TP), plane),
                  _sds((D, TP), plane)), {})]

    if cell.op == "node_index":
        # the nodes layout's packed arrays: one kernel tree block of
        # trees, 128 // NW to each 128-lane row
        from repro.kernels.node_index import tree_block
        rows = tree_block(NW) * NW // 128
        packed = _sds((rows, 128), i32)
        return [((_sds((N, FP), bt), packed, packed, packed, packed,
                  _sds((rows, NW, 128), jnp.bfloat16), packed),
                 {"width": NW})]

    if cell.op == "leaf_gather":
        return [((_sds((N, T), i32), _sds((T, L, C), f32)), {})]

    if cell.op == "histogram":
        return [((_sds((F, N), bt), _sds((N,), i32), _sds((N, C), f32)),
                 {"n_bins": B + 1, "n_leaves": 4})]

    assert cell.op == "fused_predict", cell.op
    # dtype here claims the bins-scratch dtype the kernel may pick:
    # uint8 needs <=255 borders, int32 cells trace the >255 path.
    nb = B if cell.dtype == "uint8" else B_WIDE
    if cell.layout in ("soa", "depth_grouped"):
        return [((_sds((N, F), f32), _sds((nb, F), f32),
                  _sds((T, D), i32), _sds((T, D), i32),
                  _sds((T, L, C), f32)), {})]
    if cell.layout == "depth_major":
        return [((_sds((N, FP), f32), _sds((nb, FP), f32),
                  _sds((TP, D, FP), f32), _sds((D, TP), i32),
                  _sds((D, 1), f32), _sds((TP, L, C), f32)), {})]
    # bitpacked
    return [((_sds((N, F), f32), _sds((nb, F), f32),
              _sds((D, TP), i32), _sds((D, TP), i32),
              _sds((TP, L, C), f32)), {})]


# --------------------------------------------------------------------------
# Trace cache
# --------------------------------------------------------------------------
_TRACE_CACHE: dict[tuple, Any] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def _trace_key(cell: Cell, args, kwargs) -> tuple:
    sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
    return (cell.key, sig, tuple(sorted(kwargs.items())))


def trace_cell(cell: Cell) -> list[Any]:
    """ClosedJaxprs for every call variant of the cell, through the
    module-level cache.  Raises whatever the trace raises — the checker
    turns that into a capability finding."""
    impl = registry.get(cell.op, cell.impl)
    out = []
    for args, kwargs in cell_variants(cell):
        key = _trace_key(cell, args, kwargs)
        if key in _TRACE_CACHE:
            _CACHE_STATS["hits"] += 1
        else:
            _TRACE_CACHE[key] = jaxpr_tools.trace_abstract(
                impl.fn, *args, **kwargs)
            _CACHE_STATS["misses"] += 1
        out.append(_TRACE_CACHE[key])
    return out


def cache_stats() -> dict[str, int]:
    return dict(_CACHE_STATS)


def reset_cache() -> None:
    _TRACE_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0


# --------------------------------------------------------------------------
# Canonical ensemble (plan lints + layout-cost audit)
# --------------------------------------------------------------------------
def canonical_ensemble(*, n_features: int = FP, n_trees: int = 64,
                       n_borders: int = B, n_outputs: int = C,
                       depth: int = D, seed: int = 17):
    """Mixed-true-depth ensemble at lowering-friendly dims (features
    already lane-aligned, trees a block multiple) so the layout-cost
    audit compares model vs actual bytes without padding noise."""
    from repro.core import trees
    from repro.core.trees import ObliviousEnsemble

    rng = np.random.default_rng(seed)
    borders = np.sort(rng.normal(size=(n_borders, n_features)), 0) \
        .astype(np.float32)
    sf = rng.integers(0, n_features, (n_trees, depth)).astype(np.int32)
    sb = rng.integers(1, n_borders + 1, (n_trees, depth)).astype(np.int32)
    lv = rng.normal(size=(n_trees, 1 << depth, n_outputs)) \
        .astype(np.float32)
    ens = ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                            jnp.asarray(lv), jnp.asarray(borders),
                            jnp.full((n_features,), n_borders, jnp.int32))
    true_depths = rng.integers(1, depth + 1, n_trees)
    true_depths[0] = depth          # keep dmax = depth
    return trees.truncate_tree_depths(ens, true_depths), true_depths
