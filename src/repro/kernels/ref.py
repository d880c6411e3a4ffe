"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground-truth implementations of the paper's four hotspots
(BinarizeFloatsNonSse, CalcIndexesBasic, CalculateLeafValues[Multi],
L2SqrDistance) plus the beyond-paper fused predict.  Each Pallas kernel is
validated against the function of the same name here (tests/test_kernels*.py).

Conventions (match CatBoost's oblivious-tree model):
  x              (N, F)  float32   raw feature matrix
  borders        (B, F)  float32   per-feature bin borders, padded with +inf
  bins           (N, F)  int32     binarized features: #borders strictly below x
  split_features (T, D)  int32     feature id used at depth d of tree t
  split_bins     (T, D)  int32     border id; go right iff bins[f] >= split_bin
  leaf_values    (T, 2^D, C) float32
  leaf index     idx[n, t] = sum_d  2^d * [ bins[n, sf[t,d]] >= sb[t,d] ]

The oracles keep this model format.  The Pallas kernels read the leaf
table in the class-major form `layout.lower` builds, (T, Cp, 2^D) with
the classes padded to a multiple of 8; the registered `ref` impls in
`kernels.ops` convert it back before calling these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def binarize(x: jax.Array, borders: jax.Array) -> jax.Array:
    """bins[n, f] = #{b : x[n, f] > borders[b, f]}  (CatBoost: value > border)."""
    # (N, 1, F) > (1, B, F) -> sum over B
    return jnp.sum(x[:, None, :] > borders[None, :, :], axis=1, dtype=jnp.int32)


def binarize_u8(x: jax.Array, borders: jax.Array) -> jax.Array:
    """`binarize` with the paper's actual bin representation: uint8.

    CatBoost caps features at 255 borders precisely so the binarized
    pool fits one byte per (sample, feature); requires B <= 255 (bin
    ids span [0, B], so 255 is the largest id and still fits).

    This is the *pool builder* — the CPU-side counterpart of CatBoost's
    `BinarizeFloats` (which runs `upper_bound` binary search per value),
    so it binarizes by per-column `searchsorted` over the sorted border
    stack: O(N F log B) instead of the O(N F B) all-pairs comparison
    panel `binarize` keeps.  `binarize` itself intentionally stays the
    comparison-sum form: it is the numerics oracle for the Pallas
    bit-plane kernels (the paper's `vmsgeu` loop), which compute
    exactly that panel.  Results are bit-identical: borders columns are
    sorted ascending with +inf padding, so #{b : x > b} ==
    searchsorted(borders, x, 'left'); NaN (which every comparison
    rejects -> bin 0) is masked explicitly since searchsorted would
    sort it past +inf.
    """
    if borders.shape[0] > 255:
        raise ValueError(f"uint8 bins need <= 255 borders, got "
                         f"{borders.shape[0]} (see quantize.compute_borders"
                         " max_bins cap)")

    def col(b, xc):
        idx = jnp.searchsorted(b, xc, side="left")
        return jnp.where(jnp.isnan(xc), 0, idx)

    return jax.vmap(col, in_axes=(1, 1), out_axes=1)(
        borders, x).astype(jnp.uint8)


def leaf_index(bins: jax.Array, split_features: jax.Array,
               split_bins: jax.Array) -> jax.Array:
    """idx[n, t] = sum_d 2^d * [bins[n, sf[t, d]] >= sb[t, d]]  -> (N, T) int32.

    `bins` may be int32 or uint8 (the quantized-pool representation):
    the comparison against int32 `split_bins` promotes, so one oracle
    serves both bin streams."""
    T, D = split_features.shape
    gathered = bins[:, split_features.reshape(-1)].reshape(bins.shape[0], T, D)
    go_right = (gathered >= split_bins[None, :, :]).astype(jnp.int32)
    pow2 = (1 << jnp.arange(D, dtype=jnp.int32))[None, None, :]
    return jnp.sum(go_right * pow2, axis=-1, dtype=jnp.int32)


def leaf_index_depth_major(bins: jax.Array, onehot: jax.Array,
                           split_bins_dm: jax.Array,
                           pow2: jax.Array) -> jax.Array:
    """`leaf_index` over the depth-major lowered layout -> (N, T) int32.

    Consumes what `layout.lower(..., "depth_major")` precomputes: the
    one-hot feature-gather matrix `onehot` (T, D, F) f32 (row (t, d) is
    onehot(sf[t, d])), split bins transposed to bit-plane order
    `split_bins_dm` (D, T) int32, and the hoisted per-depth power-of-two
    vector `pow2` (D, 1) f32.  The feature gather is a straight matmul
    against the precomputed one-hot — no iota / one-hot rebuild per call
    (the paper's pow2 hoisting applied to model structure).  Exact: bin
    ids <= 255 and a one-hot matmul touch only f32-exact integers.
    """
    T, D, F = onehot.shape
    N = bins.shape[0]
    binsf = bins.astype(jnp.float32)
    gathered = jnp.einsum("tdf,nf->ntd", onehot, binsf)        # (N, T, D)
    go_right = gathered >= split_bins_dm.T[None, :, :].astype(jnp.float32)
    return jnp.sum(go_right.astype(jnp.float32)
                   * pow2[:, 0][None, None, :],
                   axis=-1).astype(jnp.int32)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack a 0/1 plane along axis 0 into uint32 lanes -> (ceil(N/32), ...).

    The paper's word-packing: 32 docs' comparison bits become one
    machine word (RVV's `vmsgeu` mask register, LMUL'd into words).
    Ragged tails are zero-padded, so lane bit k of word w is doc
    `32*w + k` and every bit past N is 0.  Bits are disjoint across
    lane positions, so the sum of shifted bits equals their bitwise OR.
    """
    n = bits.shape[0]
    w = -(-max(n, 1) // 32)
    b = jnp.asarray(bits).astype(jnp.uint32)
    pad = [(0, w * 32 - n)] + [(0, 0)] * (b.ndim - 1)
    b = jnp.pad(b, pad).reshape((w, 32) + b.shape[1:])
    shifts = jnp.arange(32, dtype=jnp.uint32).reshape(
        (1, 32) + (1,) * (b.ndim - 2))
    return jnp.sum(b << shifts, axis=1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Inverse of `pack_bits`: uint32 lanes -> the first `n` 0/1 rows (int32)."""
    shifts = jnp.arange(32, dtype=jnp.uint32).reshape(
        (1, 32) + (1,) * (words.ndim - 1))
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    out = bits.reshape((words.shape[0] * 32,) + words.shape[1:])
    return out[:n].astype(jnp.int32)


def leaf_index_bitpacked(bins: jax.Array, split_features_bp: jax.Array,
                         split_bins_bp: jax.Array, *,
                         via_words: bool = False) -> jax.Array:
    """`leaf_index` over the bitpacked lowered layout -> (N, T) int32.

    Consumes the bit-plane transposed model arrays of
    `layout.lower(..., "bitpacked")`: `split_features_bp` (D, T) int32
    and `split_bins_bp` (D, T) in the narrowest dtype that holds the
    thresholds (uint8 when they fit — comparing uint8 bins against a
    uint8 plane never widens the gathered panel).  Depth d's comparison
    result is a single bit per doc; the index register accumulates bit
    d via shift/or on integers — no one-hot, no float arithmetic, no
    MXU.  `via_words=True` additionally routes each depth's comparison
    plane through `pack_bits`/`unpack_bits` (the paper-literal 32-doc
    uint32 lane representation); since pack/unpack is the identity on
    bit planes (property-tested), both paths are equal by construction.
    """
    D, T = split_features_bp.shape
    n = bins.shape[0]
    gathered = bins[:, split_features_bp.reshape(-1)].reshape(n, D, T)
    go = gathered >= split_bins_bp[None, :, :]              # bool (N, D, T)
    idx = jnp.zeros((n, T), jnp.int32)
    for d in range(D):                                      # static unroll
        bit = go[:, d, :]
        if via_words:
            bit = unpack_bits(pack_bits(bit), n)
        idx = idx | (bit.astype(jnp.int32) << d)
    return idx


def _at_or_above(bin_ids: jax.Array, thresholds: jax.Array) -> jax.Array:
    """bin_ids >= thresholds, uint8 bins compared as uint8: a threshold
    above 255 is never reached and one at or below 0 always is."""
    if bin_ids.dtype != jnp.uint8:
        return bin_ids >= thresholds
    narrow = jnp.clip(thresholds, 0, 255).astype(jnp.uint8)
    return (thresholds <= 0) | ((thresholds <= 255) & (bin_ids >= narrow))


def node_index(bins: jax.Array, node_features: jax.Array,
               node_bins: jax.Array, node_left: jax.Array,
               node_right: jax.Array) -> jax.Array:
    """The leaf each row reaches in each non-symmetric tree -> (N, T)
    int32, by walking: from the root (node 0), node = right if
    bins[n, node_features] >= node_bins else left, until a leaf
    (a child < 0 is leaf -1 - child).

    The node arrays are (T, Nmax) (`trees.NodeEnsemble`); `bins` may be
    int32 or uint8."""
    n, t = bins.shape[0], node_features.shape[0]
    trees = jnp.arange(t)[None, :]

    def step(node):
        at = jnp.maximum(node, 0)
        feature = node_features[trees, at]                  # (N, T)
        right = _at_or_above(jnp.take_along_axis(bins, feature, axis=1),
                             node_bins[trees, at])
        child = jnp.where(right, node_right[trees, at],
                          node_left[trees, at])
        return jnp.where(node >= 0, child, node)

    node = jax.lax.while_loop(lambda v: jnp.any(v >= 0), step,
                              jnp.zeros((n, t), jnp.int32))
    return -1 - node


def leaf_gather(idx: jax.Array, leaf_values: jax.Array) -> jax.Array:
    """pred[n, c] = sum_t leaf_values[t, idx[n, t], c]  -> (N, C) float32."""
    N, T = idx.shape
    _, L, C = leaf_values.shape
    taken = jnp.take_along_axis(
        leaf_values[None, :, :, :],                        # (1, T, L, C)
        idx[:, :, None, None].astype(jnp.int32),           # (N, T, 1, 1)
        axis=2,
    )                                                      # (N, T, 1, C)
    return jnp.sum(taken[:, :, 0, :], axis=1)


def l2sq_rowwise(q: jax.Array, refs: jax.Array) -> jax.Array:
    """Paper-faithful L2SqrDistance: one query vs many refs -> (N,) float32."""
    d = refs - q[None, :]
    return jnp.sum(d * d, axis=-1)


def l2sq_matrix(a: jax.Array, b: jax.Array) -> jax.Array:
    """Full pairwise distance matrix (M, N): ||a||^2 + ||b||^2 - 2 a.b^T."""
    a_sq = jnp.sum(a * a, axis=-1)[:, None]
    b_sq = jnp.sum(b * b, axis=-1)[None, :]
    cross = a @ b.T
    return jnp.maximum(a_sq + b_sq - 2.0 * cross, 0.0)


def fused_predict(x: jax.Array, borders: jax.Array, split_features: jax.Array,
                  split_bins: jax.Array, leaf_values: jax.Array) -> jax.Array:
    """binarize -> leaf_index -> leaf_gather in one logical op  -> (N, C)."""
    bins = binarize(x, borders)
    idx = leaf_index(bins, split_features, split_bins)
    return leaf_gather(idx, leaf_values)


def fused_predict_depth_major(x: jax.Array, borders: jax.Array,
                              onehot: jax.Array, split_bins_dm: jax.Array,
                              pow2: jax.Array,
                              leaf_values: jax.Array) -> jax.Array:
    """`fused_predict` over the depth-major lowered layout -> (N, C)."""
    bins = binarize(x, borders)
    idx = leaf_index_depth_major(bins, onehot, split_bins_dm, pow2)
    return leaf_gather(idx, leaf_values)


def fused_predict_bitpacked(x: jax.Array, borders: jax.Array,
                            split_features_bp: jax.Array,
                            split_bins_bp: jax.Array,
                            leaf_values: jax.Array) -> jax.Array:
    """`fused_predict` over the bitpacked lowered layout -> (N, C)."""
    bins = binarize(x, borders)
    idx = leaf_index_bitpacked(bins, split_features_bp, split_bins_bp)
    return leaf_gather(idx, leaf_values)
