"""Kernel block-shape tuning support.

RVV 0.7.1 exposes LMUL (m1/m2/m4/m8) register grouping; the paper notes
picking the best mode "requires experiments".  The TPU analog is the
Pallas BlockSpec shape: it sets the VMEM working set and the MXU/VPU
tile utilization.  This module holds the one VMEM limit every kernel
compiles under, the footprint models that keep the tuner's block
choices inside it, and the candidate grids the tuner ranks.

The footprint models count what Mosaic allocates, not the logical
bytes: every VMEM buffer is padded to whole (sublane, lane) tiles —
the lane dim to 128, the sublane dim to 8 rows of 32-bit words (16
for 2-byte, 32 for 1-byte dtypes) — and every pipelined input/output
block is double-buffered.  A class-major (T, Cp, L) leaf table block
at C=7 therefore costs 8/7 of its logical size, where the 7 classes on
lanes would cost 128/7; a (1, N) uint8 row costs 32 rows.
"""
from __future__ import annotations

import dataclasses
import math

# The VMEM limit each pallas_call passes to the compiler
# (`compiler_params`), and the budget the footprint models plan
# against.  A v5e TensorCore has 128 MiB of VMEM; the compiler's
# default scoped limit is far lower, so kernels state theirs.  Half the
# physical size leaves room for Mosaic's own internal scratch.
VMEM_BUDGET = 64 * 1024 * 1024
LANE = 128                        # VPU lane width / MXU tile edge
SUBLANE = 8                       # rows of 32-bit words per tile
# Sample-axis block candidates: whole lanes, since the staged kernels
# keep samples on the lane axis of the (trees, samples) index block.
ROW_BLOCKS = (128, 256, 512, 1024)
# Tree-axis block candidates of the MXU-gather kernels (trees sit on
# sublanes there, so any multiple of 8 tiles); the bitpacked kernels
# put trees on lanes and always take one full lane of trees.
TREE_BLOCKS = (8, 16, 32, 64)
BITPLANE_TREE_BLOCK = LANE


def compiler_params(*dimension_semantics: str):
    """`pltpu.CompilerParams` every kernel compiles with: the grid's
    dimension semantics and the shared `VMEM_BUDGET` limit."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_BUDGET)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one buffer of `shape`, padded to whole tiles."""
    dims = (1, 1) + tuple(int(d) for d in shape)
    *lead, rows, lanes = dims
    rows_per_tile = SUBLANE * max(1, 4 // itemsize)
    return (math.prod(lead) * _round_up(rows, rows_per_tile)
            * _round_up(lanes, LANE) * itemsize)


def _block(shape, itemsize: int) -> int:
    """A pipelined input/output block: two tile-padded buffers."""
    return 2 * tile_bytes(shape, itemsize)


def binarize_footprint(block_n: int, block_f: int, n_borders: int, *,
                       bins_bytes: int = 4) -> int:
    """`bins_bytes=1` models the uint8 bin stream (quantized pool /
    u8 fused scratch).  The compare-add loop accumulates in int32
    regardless of the stored dtype, so the accumulator and the compare
    mask are counted at 4 bytes."""
    return (_block((block_n, block_f), 4)                # x
            + _block((n_borders, block_f), 4)            # borders
            + _block((block_n, block_f), bins_bytes)     # bins out
            + 2 * tile_bytes((block_n, block_f), 4))     # acc + mask


def _gather_stage_bytes(block_n: int, block_t: int, F: int,
                        bins_bytes: int) -> int:
    """Stage-2 temporaries of the MXU gather: the widened bins panel
    and its matmul operand, one depth's (bt, F) one-hot, the (bt, bn)
    gathered plane and index accumulator, and the (bn, bt) transpose."""
    return (2 * tile_bytes((block_n, F), 4)
            + tile_bytes((block_t, F), 4)
            + 3 * tile_bytes((block_t, block_n), 4)
            + tile_bytes((block_n, block_t), 4))


def _bitplane_stage_bytes(block_n: int, F: int) -> int:
    """Stage-2 temporaries of the bitpacked kernel: the int32 panel it
    gathers from, and per depth the (bn, 128) gathered columns, mask,
    index register and 32-doc words."""
    return (tile_bytes((block_n, F), 4)
            + 4 * tile_bytes((block_n, BITPLANE_TREE_BLOCK), 4))


def _accumulate_stage_bytes(block_n: int, block_t: int, L: int,
                            C: int) -> int:
    """Stage-3 (leaf accumulate) working set: the class-major (bt, C, L)
    leaf block (C sublane-padded to 8, L lane-padded to 128), two live
    (L, bn) one-hots and the (C, bn) sum."""
    return (_block((block_t, C, L), 4)
            + 2 * tile_bytes((L, block_n), 4)
            + tile_bytes((C, block_n), 4))


def leaf_index_footprint(block_n: int, block_t: int, F: int, D: int, *,
                         bins_bytes: int = 4,
                         gather: str = "mxu") -> int:
    """`gather` names the index-assembly pipeline the kernel runs:

      mxu       per depth, a (bt, F) one-hot against the bins panel on
                the MXU; split arrays arrive as (bt, D) blocks
      bitplane  integer lane gather + shift/or (the bitpacked layout):
                split planes arrive as (D, 128) blocks
    """
    bins = _block((block_n, F), bins_bytes)
    out = _block((block_t, block_n), 4)
    if gather == "bitplane":
        planes = 2 * _block((D, block_t), 4)
        return bins + planes + out + _bitplane_stage_bytes(block_n, F)
    splits = 2 * _block((block_t, D), 4)
    return (bins + splits + out
            + _gather_stage_bytes(block_n, block_t, F, bins_bytes))


def node_index_footprint(block_n: int, block_t: int, F: int, W: int, *,
                         bins_bytes: int = 4) -> int:
    """One grid step of `kernels.node_index`: block_t trees of W node
    slots, R = block_t * W node rows.  Blocks: the bins panel, three
    packed (R / 128, 128) int32 node arrays, the (R / 128, W, 128) bf16
    path rows, the (bt, bn) index.  Temporaries: the widened bins
    operand, a column's (R, 128) spread, the (R, F) one-hot, the
    gathered, sign, score and match planes (R, bn), the group's
    block-diagonal path matrices and the (bt, R) leaf weights."""
    rows = block_t * W
    groups = max(rows // LANE, 1)
    blocks = (_block((block_n, F), bins_bytes)
              + 3 * _block((groups, LANE), 4)
              + _block((groups, W, LANE), 2)
              + _block((block_t, block_n), 4))
    temps = (2 * tile_bytes((block_n, F), 4)
             + 2 * tile_bytes((rows, LANE), 4)
             + tile_bytes((rows, F), 4)
             + 4 * tile_bytes((rows, block_n), 4)
             + tile_bytes((groups, LANE, LANE), 2)
             + tile_bytes((block_t, rows), 2))
    return blocks + temps


def leaf_gather_footprint(block_n: int, block_t: int, L: int, C: int) -> int:
    return (_block((block_t, block_n), 4)                # idx (trees, rows)
            + _block((C, block_n), 4)                    # out (classes, rows)
            + tile_bytes((block_n, block_t), 4)          # idx transposed
            + _accumulate_stage_bytes(block_n, block_t, L, C))


def fused_footprint(block_n: int, block_t: int, F: int, D: int, L: int,
                    C: int, n_borders: int, *, bins_bytes: int = 4,
                    gather: str = "mxu") -> int:
    """Working set of one fused grid step.  `bins_bytes=1` models the
    u8 bins scratch the fused kernel uses when the ensemble fits 255
    borders (ops.py picks it automatically); `gather="bitplane"` models
    the bitpacked kernel (see `leaf_index_footprint`)."""
    stage1 = (_block((block_n, F), 4)                    # x
              + _block((n_borders, F), 4)                # borders
              + tile_bytes((block_n, F), bins_bytes)     # bins scratch
              + 2 * tile_bytes((block_n, F), 4))         # acc + mask
    if gather == "bitplane":
        stage2 = (2 * _block((D, block_t), 4)
                  + _bitplane_stage_bytes(block_n, F))
    else:
        stage2 = (2 * _block((block_t, D), 4)
                  + _gather_stage_bytes(block_n, block_t, F, bins_bytes))
    return (stage1 + stage2 + _block((C, block_n), 4)
            + _accumulate_stage_bytes(block_n, block_t, L, C))


@dataclasses.dataclass
class Candidate:
    block_n: int
    block_t: int
    footprint: int
    score: float


def _pad_utilization(n: int, block: int) -> float:
    """Fraction of padded work that is real when n is rounded up to a
    multiple of block (1.0 when block divides n or n unknown)."""
    padded = block * ((n + block - 1) // block) if n > 0 else block
    return n / padded if n > 0 else 1.0


def candidates_fused(F: int, D: int, L: int, C: int, n_borders: int,
                     budget: int = VMEM_BUDGET, *,
                     n_rows: int | None = None,
                     n_trees: int | None = None,
                     gather: str = "mxu") -> list[Candidate]:
    """Candidate (block_n, block_t) grid, best first.

    When the workload shape (n_rows, n_trees) is known — the serving path
    always knows it — candidates that force heavy zero-padding are
    penalized by the fraction of padded work that is real, so a 150-row
    bucket is not handed a 1024-row block.  `F` is the logical feature
    count; the kernels see it padded to whole lanes.
    """
    Fp = _round_up(max(F, 1), LANE)
    bins_bytes = 1 if n_borders <= 255 else 4
    tree_blocks = ((BITPLANE_TREE_BLOCK,) if gather == "bitplane"
                   else TREE_BLOCKS)
    out = []
    for bn in ROW_BLOCKS:
        for bt in tree_blocks:
            fp = fused_footprint(bn, bt, Fp, D, L, C, n_borders,
                                 bins_bytes=bins_bytes, gather=gather)
            if fp > budget:
                continue
            # prefer larger tiles (fewer grid steps) once they fit
            score = min(1.0, fp / budget + 0.2) * (bn * bt) ** 0.25
            if n_rows is not None:
                score *= _pad_utilization(n_rows, bn)
            if n_trees is not None:
                score *= _pad_utilization(n_trees, bt)
            out.append(Candidate(bn, bt, fp, score))
    return sorted(out, key=lambda c: -c.score)


def best_fused_blocks(F: int, D: int, L: int, C: int,
                      n_borders: int, *,
                      n_rows: int | None = None,
                      n_trees: int | None = None,
                      gather: str = "mxu") -> tuple[int, int]:
    cands = candidates_fused(F, D, L, C, n_borders, n_rows=n_rows,
                             n_trees=n_trees, gather=gather)
    if not cands:
        smallest = fused_footprint(ROW_BLOCKS[0], TREE_BLOCKS[0],
                                   _round_up(max(F, 1), LANE), D, L, C,
                                   n_borders)
        raise ValueError(
            f"no fused block shape fits VMEM_BUDGET={VMEM_BUDGET} B for "
            f"F={F} D={D} L={L} C={C} borders={n_borders}: the smallest "
            f"candidate ({ROW_BLOCKS[0]}, {TREE_BLOCKS[0]}) needs "
            f"{smallest} B")
    return cands[0].block_n, cands[0].block_t


# --------------------------------------------------------------------------
# Training histogram block planning (see repro.kernels.histogram)
# --------------------------------------------------------------------------
def hist_footprint(block_f: int, block_n: int, n_leaves: int,
                   n_bins: int, n_stats: int, *,
                   bins_bytes: int = 1) -> int:
    """VMEM working set of one histogram grid step.

    The kernel contracts a per-feature (n_bins, bn) bin one-hot with a
    (bn, n_leaves * n_stats) leaf-expanded stats panel, so the
    accumulator block is (block_f, n_bins, n_leaves * n_stats): stats
    share the lane axis with the leaves instead of owning it.  Counted:
    the bins rows (one (1, bn) tile each), the leaf column and stats
    blocks, the two constant expansion matrices, the accumulator, and
    the temporaries (leaf one-hot, expanded stats and its two factors,
    bin one-hot, one feature's product)."""
    bp = _round_up(max(n_bins, 1), SUBLANE)
    lc = n_leaves * n_stats
    return (block_f * _block((1, block_n), bins_bytes)
            + _block((block_n, 1), 4)                    # leaf column
            + _block((block_n, n_stats), 4)              # g/h stats
            + _block((n_leaves, lc), 4)                  # leaf expansion
            + _block((n_stats, lc), 4)                   # stats expansion
            + block_f * _block((bp, lc), 4)              # accumulator
            + tile_bytes((block_n, n_leaves), 4)
            + 3 * tile_bytes((block_n, lc), 4)
            + tile_bytes((bp, block_n), 4)
            + tile_bytes((bp, lc), 4))


@dataclasses.dataclass
class HistCandidate:
    block_f: int
    block_n: int
    footprint: int
    score: float


def candidates_hist(F: int, n_leaves: int, n_bins: int, n_stats: int,
                    budget: int = VMEM_BUDGET, *,
                    n_rows: int | None = None,
                    bins_bytes: int = 1) -> list[HistCandidate]:
    """Candidate (block_f, block_n) grid for the histogram kernel, best
    first.  Scored like `candidates_fused`: prefer larger tiles once
    they fit, penalize candidates whose padding (features to block_f,
    rows to block_n) is mostly zeros."""
    out = []
    for bf in (1, 2, 4, 8):
        for bn in ROW_BLOCKS:
            fp = hist_footprint(bf, bn, n_leaves, n_bins, n_stats,
                                bins_bytes=bins_bytes)
            if fp > budget:
                continue
            score = min(1.0, fp / budget + 0.2) * (bf * bn) ** 0.25
            if n_rows is not None:
                score *= _pad_utilization(n_rows, bn)
            score *= _pad_utilization(F, bf)
            out.append(HistCandidate(bf, bn, fp, score))
    return sorted(out, key=lambda c: -c.score)


def best_hist_blocks(F: int, n_leaves: int, n_bins: int, n_stats: int, *,
                     n_rows: int | None = None,
                     bins_bytes: int = 1) -> tuple[int, int]:
    cands = candidates_hist(F, n_leaves, n_bins, n_stats,
                            n_rows=n_rows, bins_bytes=bins_bytes)
    if not cands:
        raise ValueError(
            f"no histogram block shape fits VMEM_BUDGET={VMEM_BUDGET} B "
            f"for n_leaves={n_leaves} n_bins={n_bins} n_stats={n_stats}")
    return cands[0].block_f, cands[0].block_n


# --------------------------------------------------------------------------
# Bulk-scoring chunk planning (see repro.scoring.scorer)
# --------------------------------------------------------------------------
# Working-set budget per in-flight scoring chunk.  The binding
# constraint on CPU (the measured backend in this container) is not
# host RAM but the cache footprint of the staged kernels' per-chunk
# intermediates — the (N, F, B) binarize comparison panel and the
# (N, T, L) gather one-hot.  Chunks past the budget fall off a cache
# cliff (measured: the float path's us/row triples from N=2048 to
# N=4096 on a 100-tree covertype model); chunks far below it waste
# dispatch overhead.  32 MiB lands the planner on the measured sweet
# spot for paper-scale models while keeping a depth-2 prefetch
# pipeline comfortably in memory.
CHUNK_BUDGET_BYTES = 32 * 1024 * 1024
MIN_CHUNK_ROWS = 256
MAX_CHUNK_ROWS = 1 << 17          # dispatch overhead is long amortized


def chunk_row_bytes(n_features: int, n_outputs: int, *,
                    n_borders: int = 0, n_trees: int = 0,
                    n_leaves: int = 0) -> int:
    """Per-row working set of one scoring chunk.

    Always counted: the float32 copy sliced from the source, its uint8
    bins (the quantized pool), and the float32 output panel.  When the
    model dims are known the staged-kernel intermediates dominate and
    are added: the (F, B) binarize comparison panel and the (T, L)
    leaf-gather one-hot, both float32 per row."""
    base = 4 * n_features + n_features + 4 * max(n_outputs, 2)
    base += 4 * n_features * n_borders       # binarize comparisons
    base += 4 * n_trees * n_leaves           # gather one-hot
    return base


def best_chunk_rows(n_features: int, n_outputs: int, *,
                    n_borders: int = 0, n_trees: int = 0,
                    n_leaves: int = 0,
                    budget_bytes: int = CHUNK_BUDGET_BYTES,
                    n_rows: int | None = None) -> int:
    """Pick the bulk scorer's fixed chunk shape, the way
    `best_fused_blocks` picks block shapes: largest power-of-two row
    count whose per-chunk working set fits the budget (pow2 so the
    tail bucket ladder and the kernel block shapes divide it evenly),
    clamped to [MIN_CHUNK_ROWS, MAX_CHUNK_ROWS].  A known small
    `n_rows` caps the chunk at the first pow2 that covers the whole
    dataset — no point compiling a shape 60x the data."""
    per_row = chunk_row_bytes(n_features, n_outputs, n_borders=n_borders,
                              n_trees=n_trees, n_leaves=n_leaves)
    rows = MIN_CHUNK_ROWS
    while rows * 2 <= MAX_CHUNK_ROWS and rows * 2 * per_row <= budget_bytes:
        rows *= 2
    if n_rows is not None and n_rows > 0:
        cover = MIN_CHUNK_ROWS
        while cover < n_rows:
            cover *= 2
        rows = min(rows, cover)
    return rows


# --------------------------------------------------------------------------
# Physical-layout selection (see repro.core.layout)
# --------------------------------------------------------------------------
# depth_grouped pays per-group kernel dispatches to shrink leaf tables;
# only worth it once the shallow trees save a real fraction of the
# padded-to-Dmax table (and more than one group exists).
GROUPED_MIN_SAVINGS = 0.30
# depth_major trades a (T, D, F) f32 one-hot gather matrix for never
# rebuilding iota/one-hot in the leaf_index hot loop; past this size the
# matrix stops being a free win (HBM traffic per tree block grows).
DEPTH_MAJOR_MAX_ONEHOT_BYTES = 8 * 1024 * 1024


def layout_costs(true_depths, n_outputs: int, n_features: int
                 ) -> dict[str, int]:
    """Leaf-table / lowered-array byte costs per layout for an ensemble
    with the given per-tree true depths (the inputs `best_layout` ranks
    on; exposed for the bench and docs).  Leaf tables are counted as
    lowered: classes padded to a sublane tile."""
    import numpy as np
    d = np.asarray(true_depths, np.int64)
    dmax = int(d.max()) if d.size else 1
    cp = _round_up(max(n_outputs, 1), SUBLANE)
    soa_leaf = int(d.size) * (1 << dmax) * cp * 4
    grouped_leaf = int(((1 << np.maximum(d, 1)) * cp * 4).sum())
    onehot = int(d.size) * dmax * n_features * 4
    # bitpacked shares depth_grouped's leaf tables; its extra state is
    # two (d, T_d) integer bit planes per group — int32 worst case
    plane = int((2 * np.maximum(d, 1) * 4).sum())
    return {"soa_leaf_bytes": soa_leaf,
            "depth_grouped_leaf_bytes": grouped_leaf,
            "depth_major_onehot_bytes": onehot,
            "bitpacked_leaf_bytes": grouped_leaf,
            "bitpacked_plane_bytes": plane}


def best_layout(true_depths, n_outputs: int, n_features: int, *,
                backend: str = "ref") -> str:
    """Pick a physical layout from the ensemble's shape, the same way
    `best_fused_blocks` picks block shapes: from the depth histogram,
    tree count, the leaf-table bytes each layout would carry, and the
    kernel family that will consume it.

      bitpacked      mixed depths with grouped savings whose one-hot /
                     f32 working set (the (T, Dmax, F) gather panel an
                     MXU-family index kernel would stream) blows the
                     VMEM budget — the integer bit-plane pipeline
                     carries no one-hot at all, so its working set is
                     the grouped leaf tables plus two thin planes
      depth_grouped  when true depths mix and the per-depth leaf tables
                     save >= GROUPED_MIN_SAVINGS of the soa table
                     (less index+gather work on any backend)
      depth_major    pallas-family kernels on (near-)uniform depths
                     when the precomputed one-hot gather matrix stays
                     small enough — it removes the per-call iota /
                     one-hot build from the kernel body; the jnp
                     reference gathers cheaper than it matmuls, so ref
                     stays on soa
      soa            everything else (and the safe fallback: tracer
                     ensembles never reach here — the plan resolver
                     pins them to soa)
    """
    import numpy as np
    d = np.asarray(true_depths, np.int64)
    if d.size == 0:
        return "soa"
    costs = layout_costs(d, n_outputs, n_features)
    if len(set(d.tolist())) > 1:
        savings = 1.0 - (costs["depth_grouped_leaf_bytes"]
                         / max(costs["soa_leaf_bytes"], 1))
        if savings >= GROUPED_MIN_SAVINGS:
            if costs["depth_major_onehot_bytes"] > VMEM_BUDGET:
                return "bitpacked"
            return "depth_grouped"
    if backend.startswith("pallas") and \
            costs["depth_major_onehot_bytes"] <= DEPTH_MAJOR_MAX_ONEHOT_BYTES:
        return "depth_major"
    return "soa"


# --------------------------------------------------------------------------
# Mesh shard-axis selection (see Predictor.sharded / docs/distributed.md)
# --------------------------------------------------------------------------
# Tree-sharding exists for giant ensembles (the 1k-10k tree regime);
# below this the psum combine and the reassociated float sum buy
# nothing a row shard doesn't already give exactly.
TREE_SHARD_MIN_TREES = 1024
# Row-sharding replicates the whole lowered model on every shard; past
# this many replicated bytes the model, not the batch, is the memory
# problem and the tree split pays for its psum.
TREE_REPLICATION_BUDGET_BYTES = 64 * 1024 * 1024


def shard_count(mesh) -> int:
    """Total shards a mesh (or plain int) fans out to."""
    if isinstance(mesh, int):
        return max(mesh, 1)
    out = 1
    for size in dict(mesh.shape).values():
        out *= int(size)
    return max(out, 1)


def best_shard_axis(n_rows: int, n_trees: int, mesh, *,
                    n_outputs: int = 1,
                    leaf_table_bytes: int = 0) -> str:
    """Pick row- vs tree-sharding for a K-way mesh, the same way
    `best_layout` / `best_chunk_rows` pick from shape arithmetic.

    The per-shard traversal work is symmetric — ceil(N/K) x T rows-wise
    vs N x ceil(T/K) trees-wise — so the bulk product never decides.
    What does:

      rows   exact parity (same addend order per row), no combine;
             hidden cost is K-fold replication of the lowered model
      trees  a psum of the (N, C) partial sums, a reassociated float
             tree sum (~1e-6, not bit-for-bit), and the model split
             K ways instead of replicated

    So: rows unless the ensemble is in the giant-tree regime
    (`TREE_SHARD_MIN_TREES`) AND either the replicated leaf tables
    blow `TREE_REPLICATION_BUDGET_BYTES` or the batch is too ragged to
    row-shard efficiently (padding utilization below the tree axis's —
    the N < K serving-batch case).  `mesh` may be a Mesh/AbstractMesh
    or a plain shard count.
    """
    k = shard_count(mesh)
    if k <= 1:
        return "rows"
    if n_trees < TREE_SHARD_MIN_TREES or n_trees < k:
        return "rows"
    if leaf_table_bytes * (k - 1) > TREE_REPLICATION_BUDGET_BYTES:
        return "trees"
    if _pad_utilization(max(n_rows, 1), k) < _pad_utilization(n_trees, k):
        return "trees"
    return "rows"
