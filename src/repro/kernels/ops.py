"""Public kernel ops: registry-dispatched wrappers around the Pallas
kernels and their jnp oracles.

Every op (binarize, leaf_index, leaf_gather, l2sq, fused_predict) has
named implementations registered in `kernels.registry` — "ref" (pure
jnp), "pallas" (real kernels; interpret mode off-TPU), and uint8
bin-stream variants ("ref_u8", "pallas_u8") for the quantized-pool
path.  The implementations here own shape padding (block-size
alignment) and un-padding; the public wrappers are thin shims that map
the legacy `backend="auto"|"ref"|"pallas"` kwarg onto a registry lookup
(`registry.resolve`) and dispatch.  This module is the only one the
rest of the framework imports from `repro.kernels`; pass exact
implementation names (e.g. `backend="pallas_u8"`) to pin a variant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import binarize as _binarize_k
from repro.kernels import fused_predict as _fused_k
from repro.kernels import histogram as _hist_k
from repro.kernels import l2dist as _l2_k
from repro.kernels import leaf_gather as _gather_k
from repro.kernels import leaf_index as _index_k
from repro.kernels import node_index as _node_k
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels import tuning as _tuning

# Legacy alias: a backend value is "auto", a registry backend family
# ("ref" / "pallas"), or an exact implementation name ("pallas_u8").
Backend = str

# Sentinel bin id guaranteeing `bins < PAD_SPLIT_BIN` (padded trees go left).
# Canonical definition — `core.trees` re-exports it.
PAD_SPLIT_BIN = 1 << 30

# Lane width the kernels align the feature axis to (VPU lane / MXU edge).
FEATURE_ALIGN = 128

# Largest border count whose bin ids fit the uint8 quantized-pool
# representation (CatBoost's 255-border cap: ids span [0, B] <= 255).
MAX_U8_BORDERS = 255

# Sublane tile height the lowered leaf table pads its class axis to, so
# a (Cp, L) per-tree table is whole (8, 128) tiles with leaves on lanes.
CLASS_ALIGN = 8


@functools.cache
def default_platform() -> str:
    """`jax.default_backend()`, resolved once per process.

    The platform cannot change mid-process, and querying it inside traced
    code paths (every `auto` dispatch used to) is wasted work on each
    predict call — plan builders and the auto dispatch both read this
    cached value instead.
    """
    return jax.default_backend()


def _on_tpu() -> bool:
    return default_platform() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _lanes(block: int) -> int:
    """A sample-axis block rounded up to whole 128-row lanes: the
    kernels keep samples on the lane axis of the tree-major index, and
    uint8 blocks tile (32, 128), so every row block is a lane multiple."""
    return _round_up(max(int(block), 1), FEATURE_ALIGN)


# Pad-op accounting, split by which side of the problem was padded:
#   model — ensemble arrays (borders / splits / leaf values); a prepared
#           plan must incur these exactly once, at build time
#   data  — per-batch arrays (x / bins / idx); unavoidable per call
# Counters tick only when a pad actually happens (width 0 is free) and
# only when the padding code runs, i.e. once per trace under jit.
_PAD_STATS = {"model": 0, "data": 0}


def pad_stats() -> dict[str, int]:
    return dict(_PAD_STATS)


def reset_pad_stats() -> None:
    for k in _PAD_STATS:
        _PAD_STATS[k] = 0


def _pad_dim(a: jax.Array, axis: int, target: int, value=0,
             kind: str = "data") -> jax.Array:
    pad = target - a.shape[axis]
    if pad == 0:
        return a
    _PAD_STATS[kind] += 1
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    if jnp.issubdtype(a.dtype, jnp.floating):
        # a typed fill keeps integer constants out of float traces
        value = np.asarray(value, a.dtype)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _transpose_pad(leaf_values, n_trees, n_classes):
    T, _, C = leaf_values.shape
    # a typed fill keeps integer constants out of float traces
    return jnp.pad(jnp.transpose(leaf_values, (0, 2, 1)),
                   ((0, n_trees - T), (0, n_classes - C), (0, 0)),
                   constant_values=np.zeros((), leaf_values.dtype))


def class_major(leaf_values: jax.Array,
                n_trees: int | None = None) -> jax.Array:
    """Model-format leaf table (T, L, C) -> the lowered class-major form
    (n_trees, Cp, L), Cp = C rounded up to `CLASS_ALIGN`; padded trees
    (n_trees defaults to T) and classes hold zero leaves.  The leaf-sum
    kernels read only this form.  One jitted pass: the transpose and
    both pads write a single output, with no intermediate copy."""
    T, _, C = leaf_values.shape
    n_trees = T if n_trees is None else n_trees
    n_classes = _round_up(C, CLASS_ALIGN)
    _PAD_STATS["model"] += (n_trees != T) + (n_classes != C)
    return _transpose_pad(leaf_values, n_trees, n_classes)


def leaf_major(leaf_values_cm: jax.Array, n_classes: int) -> jax.Array:
    """Inverse of `class_major`: (T, Cp, L) -> the model format
    (T, L, n_classes), padded classes dropped."""
    return jnp.transpose(leaf_values_cm[:, :n_classes, :], (0, 2, 1))


def pad_features(bins: jax.Array, target_f: int) -> jax.Array:
    """Data-side pad of a bin matrix's feature axis up to `target_f`
    (the prepadded model's aligned width).  Zero bins are what +inf
    padding borders would have produced, so the pad is exact."""
    return _pad_dim(bins, 1, target_f)


def _require_u8_borders(borders: jax.Array) -> None:
    if borders.shape[0] > MAX_U8_BORDERS:
        raise ValueError(
            f"uint8 bins need <= {MAX_U8_BORDERS} borders, got "
            f"{borders.shape[0]} (see quantize.compute_borders's "
            "max_bins cap)")


# Layout capability shorthands (see repro.core.layout): ops that read
# no tree-structure arrays work under every physical layout; soa tree
# kernels also serve depth_grouped, which evaluates group-by-group
# through them.  bitpacked has its own `_bp` structure kernels, so soa
# tree kernels do NOT claim it; the nodes layout's trees are read by
# node_index alone.
ALL_LAYOUTS = ("soa", "depth_major", "depth_grouped", "bitpacked", "nodes")
SOA_LAYOUTS = ("soa", "depth_grouped")


# --------------------------------------------------------------------------
# Registered implementations: binarize
# --------------------------------------------------------------------------
@registry.register("binarize", "ref", dtypes=("int32",),
                   layouts=ALL_LAYOUTS,
                   constraints="any shape; pure-jnp oracle")
def _binarize_ref(x, borders, *, prepadded=False, **_blocks):
    if prepadded:
        x = _pad_dim(x, 1, borders.shape[1])
    return _ref.binarize(x, borders)


@registry.register("binarize", "ref_u8", dtypes=("uint8",),
                   layouts=ALL_LAYOUTS,
                   constraints="<= 255 borders; uint8 bins out")
def _binarize_ref_u8(x, borders, *, prepadded=False, **_blocks):
    if prepadded:
        x = _pad_dim(x, 1, borders.shape[1])
    return _ref.binarize_u8(x, borders)


def _binarize_pallas_impl(x, borders, *, block_n, block_f, prepadded,
                          out_dtype):
    block_n, block_f = _lanes(block_n), _lanes(block_f)
    if prepadded:
        # Borders already F-aligned (+inf pad columns); only the data
        # side is padded per call.  Padded feature columns stay in the
        # output so downstream prepadded stages see an aligned F axis.
        Fp = borders.shape[1]
        xp = _pad_dim(x, 1, Fp)
        N = x.shape[0]
        Np = _round_up(max(N, 1), block_n)
        xp = _pad_dim(xp, 0, Np)
        out = _binarize_k.binarize(xp, borders, block_n=block_n,
                                   block_f=FEATURE_ALIGN,
                                   interpret=_interpret(),
                                   out_dtype=out_dtype)
        return out[:N]
    N, F = x.shape
    Np, Fp = _round_up(max(N, 1), block_n), _round_up(max(F, 1), block_f)
    xp = _pad_dim(_pad_dim(x, 0, Np), 1, Fp)
    bp = _pad_dim(borders, 1, Fp, value=np.float32(np.inf), kind="model")
    out = _binarize_k.binarize(xp, bp, block_n=block_n, block_f=block_f,
                               interpret=_interpret(), out_dtype=out_dtype)
    return out[:N, :F]


@registry.register("binarize", "pallas", dtypes=("int32",),
                   layouts=ALL_LAYOUTS,
                   constraints="pads N/F to block multiples")
def _binarize_pallas(x, borders, *, block_n=256, block_f=128,
                     prepadded=False):
    return _binarize_pallas_impl(x, borders, block_n=block_n,
                                 block_f=block_f, prepadded=prepadded,
                                 out_dtype=jnp.int32)


@registry.register("binarize", "pallas_u8", dtypes=("uint8",),
                   layouts=ALL_LAYOUTS,
                   constraints="<= 255 borders; u8 stores tile (32, 128) "
                               "on real TPUs")
def _binarize_pallas_u8(x, borders, *, block_n=256, block_f=128,
                        prepadded=False):
    _require_u8_borders(borders)
    return _binarize_pallas_impl(x, borders, block_n=block_n,
                                 block_f=block_f, prepadded=prepadded,
                                 out_dtype=jnp.uint8)


# --------------------------------------------------------------------------
# Registered implementations: leaf_index
# --------------------------------------------------------------------------
# Declared widening exception (PR 6's depth_grouped-on-uint8 audit):
# the jnp oracle's `gathered >= split_bins` promotes the gathered uint8
# panel to int32 (XLA type promotion against the int32 split_bins).
# Cost: a transient (N, T, D) int32 comparison panel instead of uint8 —
# acceptable for the clarity-first oracle, where XLA:CPU fuses the
# widening into the compare and no VMEM contract applies.  The
# production uint8 paths (pallas_u8 one-hot contract, ref_bp/pallas_bp
# narrowed-threshold compare) stay unwidened and unsuppressed.
@registry.register("leaf_index", "ref", dtypes=("int32", "uint8"),
                   layouts=SOA_LAYOUTS,
                   constraints="any shape; bins int32 or uint8",
                   suppressions=(
                       "widening: jnp oracle promotes the gathered "
                       "panel to int32 by comparison against int32 "
                       "split_bins; clarity-first oracle, no VMEM "
                       "contract (depth_grouped routes here too)",))
def _leaf_index_ref(bins, sf, sb, *, prepadded=False, **_blocks):
    return _ref.leaf_index(bins, sf, sb)


def _leaf_index_pallas_impl(kernel, bins, sf, sb, *, block_n, block_t,
                            prepadded):
    # The kernels write the index tree-major, (T, N); the registered
    # contract is (N, T).  Inside one jit the transpose cancels against
    # leaf_gather's, so the staged pipeline never materializes it.
    block_n = _lanes(block_n)
    N, F = bins.shape
    T = sf.shape[0]
    binsp = _pad_dim(bins, 0, _round_up(max(N, 1), block_n))
    if prepadded:
        out = kernel(binsp, sf, sb, block_n=block_n, block_t=block_t,
                     interpret=_interpret())
        return out.T[:N]
    Tp = _round_up(T, block_t)
    binsp = _pad_dim(binsp, 1, _round_up(F, FEATURE_ALIGN))
    sfp = _pad_dim(sf, 0, Tp, kind="model")
    sbp = _pad_dim(sb, 0, Tp, value=PAD_SPLIT_BIN, kind="model")
    out = kernel(binsp, sfp, sbp, block_n=block_n, block_t=block_t,
                 interpret=_interpret())
    return out.T[:N, :T]


@registry.register("leaf_index", "pallas", dtypes=("int32",),
                   layouts=SOA_LAYOUTS,
                   constraints="pads N/T to block multiples; block_n "
                               "rounds up to 128-row lanes")
def _leaf_index_pallas(bins, sf, sb, *, block_n=256, block_t=16,
                       prepadded=False):
    return _leaf_index_pallas_impl(_index_k.leaf_index, bins, sf, sb,
                                   block_n=block_n, block_t=block_t,
                                   prepadded=prepadded)


@registry.register("leaf_index", "pallas_u8", dtypes=("uint8",),
                   layouts=SOA_LAYOUTS,
                   constraints="uint8 bins (quantized pool); bf16 "
                               "one-hot gather (exact for ids <= 255)")
def _leaf_index_pallas_u8(bins, sf, sb, *, block_n=256, block_t=16,
                          prepadded=False):
    return _leaf_index_pallas_impl(_index_k.leaf_index_u8, bins, sf, sb,
                                   block_n=block_n, block_t=block_t,
                                   prepadded=prepadded)


# Depth-major layout variants: consume the lowered (onehot, sb_dm, pow2)
# arrays instead of (split_features, split_bins).  The model side is
# always produced pre-padded by `layout.lower`, so only the data side
# is padded here.
@registry.register("leaf_index", "ref_dm", dtypes=("int32", "uint8"),
                   layouts=("depth_major",),
                   constraints="depth-major lowered model; any shape")
def _leaf_index_ref_dm(bins, onehot, sb_dm, pow2, *, prepadded=False,
                       **_blocks):
    return _ref.leaf_index_depth_major(bins, onehot, sb_dm, pow2)


@registry.register("leaf_index", "pallas_dm", dtypes=("int32", "uint8"),
                   layouts=("depth_major",),
                   constraints="depth-major lowered model (T/F pre-padded "
                               "at lower time); pads N per call")
def _leaf_index_pallas_dm(bins, onehot, sb_dm, pow2, *, block_n=256,
                          block_t=16, prepadded=False):
    block_n = _lanes(block_n)
    N = bins.shape[0]
    Np = _round_up(max(N, 1), block_n)
    binsp = _pad_dim(bins, 0, Np)
    out = _index_k.leaf_index_dm(binsp, onehot, sb_dm, pow2,
                                 block_n=block_n, block_t=block_t,
                                 interpret=_interpret())
    return out.T[:N]


# Bitpacked layout variants: consume the bit-plane transposed
# (split_features_bp, split_bins_bp) arrays, both (D, T).  Integer-only
# index assembly — no one-hot, no MXU (see kernels/leaf_index.py).
@registry.register("leaf_index", "ref_bp", dtypes=("int32", "uint8"),
                   layouts=("bitpacked",),
                   constraints="bitpacked bit-plane lowered model; any "
                               "shape; integer-only shift/or assembly")
def _leaf_index_ref_bp(bins, sf_bp, sb_bp, *, prepadded=False, **_blocks):
    return _ref.leaf_index_bitpacked(bins, sf_bp, sb_bp)


def _bp_pad(bins_or_x, sf_bp, sb_bp, block_n):
    """Pad a bitpacked call to the kernel's lane contract: rows to the
    row block, features and trees to whole 128 lanes.  Plans lower the
    model pre-padded (only data-side pads happen per call); direct
    registry dispatch may hand unpadded planes."""
    N, F = bins_or_x.shape
    T = sf_bp.shape[1]
    xp = _pad_dim(_pad_dim(bins_or_x, 0, _round_up(max(N, 1), block_n)),
                  1, _round_up(F, FEATURE_ALIGN))
    Tp = _round_up(max(T, 1), _index_k.BP_TREE_BLOCK)
    sfp = _pad_dim(sf_bp.astype(jnp.int32), 1, Tp, kind="model")
    sbp = _pad_dim(sb_bp.astype(jnp.int32), 1, Tp, value=PAD_SPLIT_BIN,
                   kind="model")
    return xp, sfp, sbp


@registry.register("leaf_index", "pallas_bp", dtypes=("int32", "uint8"),
                   layouts=("bitpacked",),
                   constraints="bitpacked lowered model (T pre-padded to "
                               "128-tree lanes at lower time); pads N per "
                               "call",
                   suppressions=(
                       "widening: the v5e VPU has neither 8-bit compares "
                       "nor 8-bit lane gathers; a uint8 panel widens to "
                       "int32 in registers before the integer gather",))
def _leaf_index_pallas_bp(bins, sf_bp, sb_bp, *, block_n=256, block_t=None,
                          prepadded=False):
    # block_t is accepted for the shared call convention; the bitplane
    # kernel's tree block is always one 128-tree lane
    block_n = _lanes(block_n)
    N, T = bins.shape[0], sf_bp.shape[1]
    binsp, sfp, sbp = _bp_pad(bins, sf_bp, sb_bp, block_n)
    out = _index_k.leaf_index_bp(binsp, sfp, sbp, block_n=block_n,
                                 interpret=_interpret())
    return out.T[:N, :T]


# --------------------------------------------------------------------------
# Registered implementations: node_index (non-symmetric trees)
# --------------------------------------------------------------------------
# Both take the `nodes` layout's packed arrays (`layout.NodesLayout`):
# the reference walks the node arrays, the kernel matches the path
# matrices.  The model side is always lowered pre-padded.
@registry.register("node_index", "ref", dtypes=("int32", "uint8"),
                   layouts=("nodes",),
                   constraints="nodes lowered model; any shape; walks "
                               "each tree from the root")
def _node_index_ref(bins, node_features, node_bins, node_left, node_right,
                    paths, path_len, *, width, **_blocks):
    def tree_rows(a):
        return a.reshape(-1, width)
    return _ref.node_index(bins, tree_rows(node_features),
                           tree_rows(node_bins), tree_rows(node_left),
                           tree_rows(node_right))


@registry.register("node_index", "pallas", dtypes=("int32", "uint8"),
                   layouts=("nodes",),
                   constraints="nodes lowered model (T pre-padded to the "
                               "kernel's tree block); pads N per call; "
                               "bf16 one-hot gather and path match")
def _node_index_pallas(bins, node_features, node_bins, node_left,
                       node_right, paths, path_len, *, width, block_n=256):
    block_n = _lanes(block_n)
    N = bins.shape[0]
    binsp = _pad_dim(_pad_dim(bins, 0, _round_up(max(N, 1), block_n)), 1,
                     _round_up(bins.shape[1], FEATURE_ALIGN))
    out = _node_k.node_index(binsp, node_features, node_bins, paths,
                             path_len, width=width, block_n=block_n,
                             interpret=_interpret())
    return out.T[:N]


# --------------------------------------------------------------------------
# Registered implementations: leaf_gather
# --------------------------------------------------------------------------
# A registered leaf_gather / fused_predict impl takes the model-format
# table (T, L, C), or with `prepadded=True` the lowered class-major
# table (Tp, Cp, L) and the `n_classes` C it holds.  The kernels sum
# (Cp, N); the impls slice the padded classes off before returning.
@registry.register("leaf_gather", "ref", dtypes=("int32",),
                   layouts=ALL_LAYOUTS,
                   constraints="any shape; pure-jnp oracle")
def _leaf_gather_ref(idx, leaf_values, *, prepadded=False, n_classes=None,
                     **_blocks):
    if prepadded:
        leaf_values = leaf_major(leaf_values, n_classes)
    return _ref.leaf_gather(idx, leaf_values)


@registry.register("leaf_gather", "pallas", dtypes=("int32",),
                   layouts=ALL_LAYOUTS,
                   constraints="pads N/T to block multiples")
def _leaf_gather_pallas(idx, leaf_values, *, block_n=128, block_t=16,
                        prepadded=False, n_classes=None):
    # the kernel reads the index tree-major (see _leaf_index_pallas_impl)
    block_n = _lanes(block_n)
    N, T = idx.shape
    if prepadded:
        Tp, lvp = T, leaf_values
    else:
        Tp, n_classes = _round_up(T, block_t), leaf_values.shape[2]
        # zero leaves: padded trees are no-ops
        lvp = class_major(leaf_values, Tp)
    idxp = _pad_dim(_pad_dim(idx, 0, _round_up(max(N, 1), block_n)), 1, Tp)
    out = _gather_k.leaf_gather(idxp.T, lvp, block_n=block_n,
                                block_t=block_t, interpret=_interpret())
    return out.T[:N, :n_classes]


# --------------------------------------------------------------------------
# Registered implementations: l2sq (rank-dispatched rowwise / matrix)
# --------------------------------------------------------------------------
@registry.register("l2sq", "ref", dtypes=("float32",),
                   layouts=ALL_LAYOUTS,
                   constraints="rowwise (K,)x(N,K) or matrix (M,K)x(N,K)")
def _l2sq_ref(a, b, **_blocks):
    return _ref.l2sq_rowwise(a, b) if a.ndim == 1 else _ref.l2sq_matrix(a, b)


@registry.register("l2sq", "pallas", dtypes=("float32",),
                   layouts=ALL_LAYOUTS,
                   constraints="rowwise (K,)x(N,K) or matrix (M,K)x(N,K); "
                               "pads to block multiples")
def _l2sq_pallas(a, b, *, block_m=128, block_n=128, block_k=128):
    if a.ndim == 1:
        N, K = b.shape
        Np, Kp = _round_up(N, block_n), _round_up(K, block_k)
        qp = _pad_dim(a, 0, Kp)
        rp = _pad_dim(_pad_dim(b, 0, Np), 1, Kp)
        out = _l2_k.l2sq_rowwise(qp, rp, block_n=block_n, block_k=block_k,
                                 interpret=_interpret())
        return out[:N]
    M, K = a.shape
    N, _ = b.shape
    Mp, Np_, Kp = (_round_up(M, block_m), _round_up(N, block_n),
                   _round_up(K, block_k))
    ap = _pad_dim(_pad_dim(a, 0, Mp), 1, Kp)
    bp = _pad_dim(_pad_dim(b, 0, Np_), 1, Kp)
    out = _l2_k.l2sq_matrix(ap, bp, block_m=block_m, block_n=block_n,
                            block_k=block_k, interpret=_interpret())
    return out[:M, :N]


# --------------------------------------------------------------------------
# Registered implementations: fused_predict
# --------------------------------------------------------------------------
@registry.register("fused_predict", "ref", dtypes=("int32",),
                   layouts=SOA_LAYOUTS,
                   constraints="any shape; pure-jnp oracle")
def _fused_ref(x, borders, sf, sb, lv, *, prepadded=False, n_classes=None,
               **_blocks):
    if prepadded:
        x = _pad_dim(x, 1, borders.shape[1])
        lv = leaf_major(lv, n_classes)
    return _ref.fused_predict(x, borders, sf, sb, lv)


@registry.register("fused_predict", "pallas", dtypes=("int32", "uint8"),
                   layouts=SOA_LAYOUTS,
                   constraints="pads N/T/F to block multiples; u8 bins "
                               "scratch when <= 255 borders")
def _fused_pallas(x, borders, sf, sb, lv, *, block_n=None, block_t=None,
                  prepadded=False, n_classes=None):
    # uint8 scratch quarters the VMEM the binarized block occupies
    # across tree blocks whenever the bin ids fit a byte — exact either
    # way, so this is not a user-facing choice.
    scratch = (jnp.uint8 if borders.shape[0] <= MAX_U8_BORDERS
               else jnp.int32)
    if prepadded:
        block_n = _lanes(block_n)
        N = x.shape[0]
        Np = _round_up(max(N, 1), block_n)
        xp = _pad_dim(_pad_dim(x, 0, Np), 1, borders.shape[1])
        out = _fused_k.fused_predict(xp, borders, sf, sb, lv,
                                     block_n=block_n, block_t=block_t,
                                     interpret=_interpret(),
                                     bins_scratch_dtype=scratch)
        return out.T[:N, :n_classes]
    N, F = x.shape
    T, D = sf.shape
    _, L, C = lv.shape
    if block_n is None or block_t is None:
        tn, tt = _tuning.best_fused_blocks(
            F, D, L, C, borders.shape[0], n_rows=N, n_trees=T)
        block_n = block_n or tn
        block_t = block_t or tt
    block_n = _lanes(block_n)
    Np = _round_up(N, block_n)
    Tp = _round_up(T, block_t)
    Fp = _round_up(F, FEATURE_ALIGN)
    xp = _pad_dim(_pad_dim(x, 0, Np), 1, Fp)
    bp = _pad_dim(borders, 1, Fp, value=np.float32(np.inf), kind="model")
    sfp = _pad_dim(sf, 0, Tp, kind="model")
    sbp = _pad_dim(sb, 0, Tp, value=PAD_SPLIT_BIN, kind="model")
    lvp = class_major(lv, Tp)
    out = _fused_k.fused_predict(xp, bp, sfp, sbp, lvp, block_n=block_n,
                                 block_t=block_t, interpret=_interpret(),
                                 bins_scratch_dtype=scratch)
    return out.T[:N, :C]


@registry.register("fused_predict", "ref_dm", dtypes=("int32",),
                   layouts=("depth_major",),
                   constraints="depth-major lowered model; any shape")
def _fused_ref_dm(x, borders, onehot, sb_dm, pow2, lv, *, prepadded=False,
                  n_classes=None, **_blocks):
    if prepadded:
        x = _pad_dim(x, 1, borders.shape[1])
        lv = leaf_major(lv, n_classes)
    return _ref.fused_predict_depth_major(x, borders, onehot, sb_dm,
                                          pow2, lv)


@registry.register("fused_predict", "pallas_dm", dtypes=("int32", "uint8"),
                   layouts=("depth_major",),
                   constraints="depth-major lowered model (T/F pre-padded "
                               "at lower time); pads N per call; u8 bins "
                               "scratch when <= 255 borders")
def _fused_pallas_dm(x, borders, onehot, sb_dm, pow2, lv, *,
                     block_n=None, block_t=None, prepadded=False,
                     n_classes=None):
    scratch = (jnp.uint8 if borders.shape[0] <= MAX_U8_BORDERS
               else jnp.int32)
    T, D, F = onehot.shape
    if not prepadded:
        n_classes, lv = lv.shape[2], class_major(lv, T)
    if block_n is None or block_t is None:
        # same autotune fallback as the soa impl (plans always pass
        # concrete blocks; direct registry dispatch may not) — except
        # the model side is already lowered here, so block_t must
        # divide the pre-padded T rather than drive its padding
        L = lv.shape[2]
        tn, tt = _tuning.best_fused_blocks(
            F, D, L, n_classes, borders.shape[0], n_rows=x.shape[0],
            n_trees=T)
        block_n = block_n or tn
        if block_t is None:
            block_t = next(bt for bt in (tt, 64, 32, 16, 8, 4, 2, 1)
                           if T % bt == 0)
    block_n = _lanes(block_n)
    N = x.shape[0]
    Np = _round_up(max(N, 1), block_n)
    xp = _pad_dim(_pad_dim(x, 0, Np), 1, borders.shape[1])
    out = _fused_k.fused_predict_dm(xp, borders, onehot, sb_dm, pow2, lv,
                                    block_n=block_n, block_t=block_t,
                                    interpret=_interpret(),
                                    bins_scratch_dtype=scratch)
    return out.T[:N, :n_classes]


@registry.register("fused_predict", "ref_bp", dtypes=("int32",),
                   layouts=("bitpacked",),
                   constraints="bitpacked lowered model; any shape")
def _fused_ref_bp(x, borders, sf_bp, sb_bp, lv, *, prepadded=False,
                  n_classes=None, **_blocks):
    if prepadded:
        x = _pad_dim(x, 1, borders.shape[1])
        lv = leaf_major(lv, n_classes)
    return _ref.fused_predict_bitpacked(x, borders, sf_bp, sb_bp, lv)


@registry.register("fused_predict", "pallas_bp", dtypes=("int32", "uint8"),
                   layouts=("bitpacked",),
                   constraints="bitpacked lowered model (T pre-padded to "
                               "128-tree lanes at lower time); pads N per "
                               "call; u8 bins scratch when <= 255 borders",
                   suppressions=(
                       "widening: the v5e VPU has neither 8-bit compares "
                       "nor 8-bit lane gathers; the uint8 bins scratch "
                       "widens to int32 in registers before the integer "
                       "gather",))
def _fused_pallas_bp(x, borders, sf_bp, sb_bp, lv, *, block_n=None,
                     block_t=None, prepadded=False, n_classes=None):
    # block_t is accepted for the shared call convention; the bitplane
    # kernel's tree block is always one 128-tree lane
    scratch = (jnp.uint8 if borders.shape[0] <= MAX_U8_BORDERS
               else jnp.int32)
    D, T = sf_bp.shape
    if not prepadded:
        n_classes, lv = lv.shape[2], class_major(lv)
    if block_n is None:
        block_n, _ = _tuning.best_fused_blocks(
            borders.shape[1], D, lv.shape[2], n_classes, borders.shape[0],
            n_rows=x.shape[0], n_trees=T, gather="bitplane")
    block_n = _lanes(block_n)
    N = x.shape[0]
    bp = _pad_dim(borders, 1, _round_up(borders.shape[1], FEATURE_ALIGN),
                  value=np.float32(np.inf), kind="model")
    xp, sfp, sbp = _bp_pad(x, sf_bp, sb_bp, block_n)
    lvp = _pad_dim(lv, 0, sfp.shape[1], kind="model")
    out = _fused_k.fused_predict_bp(xp, bp, sfp, sbp, lvp,
                                    block_n=block_n,
                                    interpret=_interpret(),
                                    bins_scratch_dtype=scratch)
    return out.T[:N, :n_classes]


# --------------------------------------------------------------------------
# Registered implementations: histogram (training-side hot loop)
# --------------------------------------------------------------------------
# Layout-independent like binarize: the inputs carry no lowered model
# structure, only the feature-major bin stream and per-sample stats.
# Declared widening exception: the segment-sum oracle widens pool bins
# to int32 segment ids (`leaf * n_bins + bins`) — the exact shape of
# the PR-7 histogram bug, intentional here because the oracle optimizes
# for clarity over bandwidth (histogram.histogram_ref's docstring).
# The production uint8 path is histogram:pallas_u8, which compares the
# byte stream unwidened and carries no suppression.
@registry.register("histogram", "ref", dtypes=("int32", "uint8"),
                   layouts=ALL_LAYOUTS,
                   constraints="any shape; segment-sum oracle",
                   suppressions=(
                       "widening: segment-sum oracle forms int32 "
                       "segment ids from pool bins; benign oracle "
                       "clarity (production u8 path is pallas_u8)",))
def _histogram_ref(bins_t, leaf, g, *, n_bins, n_leaves, **_blocks):
    return _hist_k.histogram_ref(bins_t, leaf, g, n_bins=n_bins,
                                 n_leaves=n_leaves)


def _histogram_pallas_impl(bins_t, leaf, g, *, n_bins, n_leaves,
                           block_f, block_n):
    F, N = bins_t.shape
    if block_f is None or block_n is None:
        bf, bn = _tuning.best_hist_blocks(
            F, n_leaves, n_bins, g.shape[1], n_rows=N,
            bins_bytes=1 if bins_t.dtype == jnp.uint8 else 4)
        block_f = block_f or bf
        block_n = block_n or bn
    block_n = _lanes(block_n)
    Fp = _round_up(max(F, 1), block_f)
    Np = _round_up(max(N, 1), block_n)
    # padded samples carry g == 0 so they accumulate nothing; padded
    # features land in hist rows [F:] and are sliced off
    binsp = _pad_dim(_pad_dim(bins_t, 0, Fp), 1, Np)
    leafp = _pad_dim(leaf, 0, Np)
    gp = _pad_dim(g, 0, Np)
    out = _hist_k.histogram(binsp, leafp, gp, n_bins=n_bins,
                            n_leaves=n_leaves, block_f=block_f,
                            block_n=block_n, interpret=_interpret())
    return out[:F]


@registry.register("histogram", "pallas", dtypes=("int32",),
                   layouts=ALL_LAYOUTS,
                   constraints="pads F/N to block multiples; padded "
                               "samples get g == 0")
def _histogram_pallas(bins_t, leaf, g, *, n_bins, n_leaves, block_f=None,
                      block_n=None):
    return _histogram_pallas_impl(bins_t, leaf, g, n_bins=n_bins,
                                  n_leaves=n_leaves, block_f=block_f,
                                  block_n=block_n)


@registry.register("histogram", "pallas_u8", dtypes=("uint8",),
                   layouts=ALL_LAYOUTS,
                   constraints="uint8 pool bins; <= 256 bins; one "
                               "(1, block_n) row widened per feature",
                   suppressions=(
                       "widening: the v5e VPU has no 8-bit compare; each "
                       "feature's (1, block_n) uint8 row widens to int32 "
                       "in registers for the bin one-hot, never the panel",))
def _histogram_pallas_u8(bins_t, leaf, g, *, n_bins, n_leaves,
                         block_f=None, block_n=None):
    return _histogram_pallas_impl(bins_t, leaf, g, n_bins=n_bins,
                                  n_leaves=n_leaves, block_f=block_f,
                                  block_n=block_n)


# --------------------------------------------------------------------------
# Public ops — legacy `backend=` kwargs as shims over registry dispatch
# --------------------------------------------------------------------------
def _bins_dtype(bins: jax.Array) -> str:
    return "uint8" if bins.dtype == jnp.uint8 else "int32"


def binarize(x: jax.Array, borders: jax.Array, *, backend: Backend = "auto",
             block_n: int = 256, block_f: int = 128) -> jax.Array:
    """(N, F) f32, (B, F) f32 -> (N, F) int32 bin indices."""
    return registry.dispatch("binarize", backend, x, borders,
                             block_n=block_n, block_f=block_f)


def binarize_u8(x: jax.Array, borders: jax.Array, *,
                backend: Backend = "auto", block_n: int = 256,
                block_f: int = 128) -> jax.Array:
    """(N, F) f32, (B, F) f32 -> (N, F) uint8 bin indices (B <= 255).

    The quantized-pool representation: one byte per (sample, feature),
    exactly the stream the paper's CalcIndexes loop consumes."""
    return registry.dispatch("binarize", backend, x, borders,
                             dtype="uint8", block_n=block_n,
                             block_f=block_f)


def leaf_index(bins: jax.Array, split_features: jax.Array,
               split_bins: jax.Array, *, backend: Backend = "auto",
               block_n: int = 256, block_t: int = 16) -> jax.Array:
    """(N, F) i32|u8, (T, D) i32, (T, D) i32 -> (N, T) int32 leaf ids.

    uint8 bins route to the u8 kernel variant automatically."""
    return registry.dispatch("leaf_index", backend, bins, split_features,
                             split_bins, dtype=_bins_dtype(bins),
                             block_n=block_n, block_t=block_t)


def leaf_gather(idx: jax.Array, leaf_values: jax.Array, *,
                backend: Backend = "auto", block_n: int = 128,
                block_t: int = 16) -> jax.Array:
    """(N, T) i32, (T, L, C) f32 -> (N, C) f32 summed leaf values.

    Takes the model format; the pallas impl lowers it to the kernel's
    class-major form per call (the plans lower it once)."""
    return registry.dispatch("leaf_gather", backend, idx, leaf_values,
                             block_n=block_n, block_t=block_t)


def histogram(bins_t: jax.Array, leaf: jax.Array, g: jax.Array, *,
              n_bins: int, n_leaves: int, backend: Backend = "auto",
              block_f: int | None = None,
              block_n: int | None = None) -> jax.Array:
    """(F, N) i32|u8 feature-major bins, (N,) i32 leaf ids, (N, C) f32
    per-sample stats -> (F, n_leaves*n_bins, C) f32 histogram.

    The training-side hot loop (one call per tree level): stats are
    accumulated per (feature, leaf, bin) cell.  uint8 pool bins route
    to the u8 kernel variant, which never widens the bins panel.
    `g` usually carries gradients and hessians concatenated on the
    stats axis so both histograms cost one pass."""
    return registry.dispatch("histogram", backend, bins_t, leaf, g,
                             dtype=_bins_dtype(bins_t), n_bins=n_bins,
                             n_leaves=n_leaves, block_f=block_f,
                             block_n=block_n)


def l2sq_rowwise(q: jax.Array, refs: jax.Array, *, backend: Backend = "auto",
                 block_n: int = 256, block_k: int = 128) -> jax.Array:
    """(K,), (N, K) -> (N,) squared L2 distances."""
    return registry.dispatch("l2sq", backend, q, refs,
                             block_n=block_n, block_k=block_k)


def l2sq_matrix(a: jax.Array, b: jax.Array, *, backend: Backend = "auto",
                block_m: int = 128, block_n: int = 128,
                block_k: int = 128) -> jax.Array:
    """(M, K), (N, K) -> (M, N) squared L2 distance matrix."""
    return registry.dispatch("l2sq", backend, a, b, block_m=block_m,
                             block_n=block_n, block_k=block_k)


def fused_predict(x: jax.Array, borders: jax.Array, split_features: jax.Array,
                  split_bins: jax.Array, leaf_values: jax.Array, *,
                  backend: Backend = "auto", block_n: int | None = None,
                  block_t: int | None = None) -> jax.Array:
    """Fused binarize+index+gather -> (N, C) f32.

    Inputs need no pre-padding: N/T/F are padded here to the block
    multiples (padded trees get zero leaf values and an impossible
    split bin, so they contribute nothing).  When block_n/block_t are
    None the shapes come from the VMEM footprint model in
    `kernels.tuning` (the RVV-LMUL analog), sized to this ensemble and
    batch instead of a fixed (128, 16).
    """
    return registry.dispatch("fused_predict", backend, x, borders,
                             split_features, split_bins, leaf_values,
                             block_n=block_n, block_t=block_t)


# --------------------------------------------------------------------------
# Prepadded-model fast paths (the compiled-plan Predictor's hot loop)
# --------------------------------------------------------------------------
# These entry points take ensemble arrays that a plan builder
# (`core.predictor.Predictor.build`) has already padded to block
# multiples, so only the data side (x / bins / idx) is padded per call —
# the per-call model `jnp.pad`s the paper hoists out of the loop are gone.
# Invariants the builder guarantees for the pallas backend:
#   borders  F padded to a FEATURE_ALIGN multiple with +inf
#   splits   T padded to a block_t multiple (bins=PAD_SPLIT_BIN: go left)
#   leaves   class-major (Tp, Cp, L), Cp = C rounded up to CLASS_ALIGN,
#            padded trees and classes all zero (they contribute nothing)
# On the ref backend the structure arrays stay unpadded — ref kernels
# accept any shape — and the leaf table is class-major all the same.
# The leaf-summing entries take `n_classes`, the C the table holds, and
# return (N, C): the padded classes never leave them.
# Each entry runs under `jax.named_scope("gbdt/<stage>")`, so every HLO
# op a plan stage emits (the kernel, its pads, slices and transposes)
# carries the stage in its op_name metadata.

@jax.named_scope("gbdt/fused_predict")
def fused_predict_prepadded(x: jax.Array, borders: jax.Array,
                            split_features: jax.Array, split_bins: jax.Array,
                            leaf_values: jax.Array, *, n_classes: int,
                            backend: Backend = "auto",
                            block_n: int = 128,
                            block_t: int = 16) -> jax.Array:
    """Fused predict on a prepadded model (class-major leaf table)
    -> (N, n_classes) f32."""
    return registry.dispatch("fused_predict", backend, x, borders,
                             split_features, split_bins, leaf_values,
                             block_n=block_n, block_t=block_t,
                             prepadded=True, n_classes=n_classes)


@jax.named_scope("gbdt/binarize")
def binarize_prepadded(x: jax.Array, borders: jax.Array, *,
                       backend: Backend = "auto",
                       block_n: int = 256) -> jax.Array:
    """Binarize against prepadded borders -> (N, Fp) int32.

    Keeps the padded feature columns (bins for +inf-border features are
    zero) so the downstream prepadded stages see an aligned F axis.
    """
    return registry.dispatch("binarize", backend, x, borders,
                             block_n=block_n, prepadded=True)


@jax.named_scope("gbdt/binarize")
def binarize_u8_prepadded(x: jax.Array, borders: jax.Array, *,
                          backend: Backend = "auto",
                          block_n: int = 256) -> jax.Array:
    """Binarize against prepadded borders -> (N, Fp) uint8 (B <= 255).

    The plan's quantize entry: same aligned-F contract as
    `binarize_prepadded`, but emitting the one-byte quantized-pool
    stream."""
    return registry.dispatch("binarize", backend, x, borders,
                             dtype="uint8", block_n=block_n,
                             prepadded=True)


@jax.named_scope("gbdt/leaf_index")
def leaf_index_prepadded(bins: jax.Array, split_features: jax.Array,
                         split_bins: jax.Array, *,
                         backend: Backend = "auto", block_n: int = 256,
                         block_t: int = 16) -> jax.Array:
    """Leaf indices on prepadded splits -> (N, Tp) int32 (padded trees
    land in leaf 0, which holds a zero leaf value).  Accepts int32 or
    uint8 bins (the quantized-pool scoring path)."""
    return registry.dispatch("leaf_index", backend, bins, split_features,
                             split_bins, dtype=_bins_dtype(bins),
                             block_n=block_n, block_t=block_t,
                             prepadded=True)


@jax.named_scope("gbdt/leaf_gather")
def leaf_gather_prepadded(idx: jax.Array, leaf_values: jax.Array, *,
                          n_classes: int, backend: Backend = "auto",
                          block_n: int = 128,
                          block_t: int = 16) -> jax.Array:
    """Sum a class-major (Tp, Cp, L) leaf table at idx
    -> (N, n_classes) f32."""
    return registry.dispatch("leaf_gather", backend, idx, leaf_values,
                             block_n=block_n, block_t=block_t,
                             prepadded=True, n_classes=n_classes)


@jax.named_scope("gbdt/node_index")
def node_index_prepadded(bins: jax.Array, node_features: jax.Array,
                         node_bins: jax.Array, node_left: jax.Array,
                         node_right: jax.Array, paths: jax.Array,
                         path_len: jax.Array, *, width: int,
                         backend: Backend = "auto",
                         block_n: int = 256) -> jax.Array:
    """Leaf ids of non-symmetric trees from the `nodes` lowered model
    -> (N, Tp) int32 (padded trees land in leaf 0, which holds a zero
    leaf value).  Accepts int32 or uint8 bins."""
    return registry.dispatch("node_index", backend, bins, node_features,
                             node_bins, node_left, node_right, paths,
                             path_len, dtype=_bins_dtype(bins),
                             layout="nodes", width=width, block_n=block_n)


# --------------------------------------------------------------------------
# Depth-major layout entry points (lowered-model hot loop)
# --------------------------------------------------------------------------
# These take the `DepthMajorLayout` arrays `layout.lower` produced —
# the one-hot gather matrix, bit-plane split bins and the hoisted pow2
# vector — so the kernels never rebuild iota/one-hot per call.  The
# model side is always lowered pre-padded; data is padded per call.

@jax.named_scope("gbdt/leaf_index")
def leaf_index_dm_prepadded(bins: jax.Array, onehot: jax.Array,
                            split_bins_dm: jax.Array, pow2: jax.Array, *,
                            backend: Backend = "auto", block_n: int = 256,
                            block_t: int = 16) -> jax.Array:
    """Leaf indices from a depth-major lowered model -> (N, Tp) int32.
    Accepts int32 or uint8 bins (quantized-pool scoring)."""
    return registry.dispatch("leaf_index", backend, bins, onehot,
                             split_bins_dm, pow2,
                             dtype=_bins_dtype(bins),
                             layout="depth_major",
                             block_n=block_n, block_t=block_t,
                             prepadded=True)


@jax.named_scope("gbdt/fused_predict")
def fused_predict_dm_prepadded(x: jax.Array, borders: jax.Array,
                               onehot: jax.Array, split_bins_dm: jax.Array,
                               pow2: jax.Array, leaf_values: jax.Array, *,
                               n_classes: int, backend: Backend = "auto",
                               block_n: int = 128,
                               block_t: int = 16) -> jax.Array:
    """Fused predict on a depth-major lowered model
    -> (N, n_classes) f32."""
    return registry.dispatch("fused_predict", backend, x, borders, onehot,
                             split_bins_dm, pow2, leaf_values,
                             layout="depth_major",
                             block_n=block_n, block_t=block_t,
                             prepadded=True, n_classes=n_classes)


# --------------------------------------------------------------------------
# Bitpacked layout entry points (lowered-model hot loop)
# --------------------------------------------------------------------------
# These take the `BitpackedLayout` bit-plane arrays `layout.lower`
# produced — (D, T) transposed split features/thresholds — so index
# assembly runs as integer shift/or with no one-hot anywhere.  The
# model side is always lowered pre-padded; data is padded per call.

@jax.named_scope("gbdt/leaf_index")
def leaf_index_bp_prepadded(bins: jax.Array, split_features_bp: jax.Array,
                            split_bins_bp: jax.Array, *,
                            backend: Backend = "auto", block_n: int = 256,
                            block_t: int = 16) -> jax.Array:
    """Leaf indices from a bitpacked lowered model -> (N, Tp) int32.
    Accepts int32 or uint8 bins (quantized-pool scoring)."""
    return registry.dispatch("leaf_index", backend, bins, split_features_bp,
                             split_bins_bp, dtype=_bins_dtype(bins),
                             layout="bitpacked",
                             block_n=block_n, block_t=block_t,
                             prepadded=True)


@jax.named_scope("gbdt/fused_predict")
def fused_predict_bp_prepadded(x: jax.Array, borders: jax.Array,
                               split_features_bp: jax.Array,
                               split_bins_bp: jax.Array,
                               leaf_values: jax.Array, *,
                               n_classes: int, backend: Backend = "auto",
                               block_n: int = 128,
                               block_t: int = 16) -> jax.Array:
    """Fused predict on a bitpacked lowered model
    -> (N, n_classes) f32."""
    return registry.dispatch("fused_predict", backend, x, borders,
                             split_features_bp, split_bins_bp, leaf_values,
                             layout="bitpacked",
                             block_n=block_n, block_t=block_t,
                             prepadded=True, n_classes=n_classes)
