"""The lint passes: rules applied to abstract traces.

Every pass takes a `matrix.Cell` (or a plan-entry name) plus a
ClosedJaxpr from `jax.make_jaxpr` and returns `report.Finding`s.
Nothing here executes traced code.

The paper's core observation is that the C++ compiler performs *no
automatic vectorization* of the CatBoost scalar loop — the win had to
be engineered by hand and can silently rot.  These passes are the
JAX-side analog of that discipline: the uint8 bin stream, the integer
bit-plane pipeline and the VMEM working set are engineered contracts,
and XLA will happily trace a widened/promoted version that still
returns correct values while quietly quadrupling the panel the kernel
streams.  A lint at the jaxpr level catches the rot before a benchmark
has to.
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np
from jax._src import core as jax_core

from repro.analysis import jaxpr_tools as jt
from repro.analysis.matrix import Cell
from repro.analysis.report import Finding

# Sinks allowed to consume a widened uint8 panel: the MXU contract.
# dot_general — the one-hot gather matmul requires f32 operands (exact
# for bin ids <= 255); gather — but only as the *index* operand: a
# gather indexed by a widened value never materializes a widened panel
# per element, while gathering FROM a widened data panel means that
# panel is resident wide (the operand-position check below).
SANCTIONED_SINKS = frozenset({"dot_general", "gather"})


def _sanctioned(eqn: Any, var: Any) -> bool:
    name = eqn.primitive.name
    if name == "dot_general":
        return True
    if name == "gather":
        # invars[0] is the data operand; widened data panels are the
        # violation, widened indices are fine
        return var is not eqn.invars[0]
    return False

# Working-set estimate vs footprint model tolerance.  The estimate is
# a pessimistic liveness bound (XLA may fuse intermediates away); the
# models deliberately count only the structural panels.  1.5x absorbs
# bookkeeping values (iota, masks) without absorbing a dtype widening,
# which is >= 2x on the dominant panel by construction.
VMEM_SLACK = 1.5

# Plan-entry buffers above this that are not donated get flagged: at
# serving batch sizes nothing legitimate is this large except the
# input panel itself, which the plan donates.
LARGE_BUFFER_BYTES = 64 * 1024 * 1024


def _finding(cell: Cell, rule: str, msg: str) -> Finding:
    return Finding(rule=rule, op=cell.op, impl=cell.impl,
                   layout=cell.layout, dtype=cell.dtype, message=msg)


# --------------------------------------------------------------------------
# Pass 1a: uint8 widening discipline
# --------------------------------------------------------------------------
def widening_lint(cell: Cell, closed: Any) -> list[Finding]:
    """Flag uint8 panels promoted to wide dtypes outside the MXU/gather
    contract.

    For every `convert_element_type` whose operand is uint8 and whose
    target itemsize exceeds 1, the widened value's terminal consumers
    (through transpose/reshape/... moves) must all be sanctioned sinks.
    Any other consumer — a compare, an add, a store — means a widened
    panel is live element-wise, which is exactly the PR-7 histogram bug
    (uint8 pool bins promoted to an int32 segment-id panel) and the
    4x-VMEM failure mode the uint8 stream exists to avoid.

    The walk follows the widened value into call-like sub-jaxprs (jnp
    wraps `take`/`einsum` bodies in named pjits); loop/branch eqns and
    values escaping through scope outvars are boundaries, not
    violations — each (sub)jaxpr is also linted as its own scope.
    """
    if cell.dtype != "uint8":
        return []
    out: list[Finding] = []
    for jaxpr in jt.iter_jaxprs(closed.jaxpr):
        consumers = jt.consumers_map(jaxpr)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            operand = eqn.invars[0]
            src = jt.unwrap_aval(getattr(operand, "aval", None))
            dst = eqn.params.get("new_dtype")
            if src is None or dst is None:
                continue
            if np.dtype(src.dtype) != np.dtype(np.uint8):
                continue
            if np.dtype(dst).itemsize <= 1:
                continue
            outvar = eqn.outvars[0]
            bad = [(t, v) for t, v in jt.terminal_consumers(
                       jaxpr, outvar, consumers)
                   if not jt.eqn_subjaxprs(t) and not _sanctioned(t, v)]
            if bad:
                sinks = sorted({t.primitive.name for t, _ in bad})
                out.append(_finding(
                    cell, "widening",
                    f"uint8 {jt.aval_short(src)} widened to "
                    f"{np.dtype(dst).name} and consumed by "
                    f"{'/'.join(sinks)} (sanctioned sinks: "
                    f"{'/'.join(sorted(SANCTIONED_SINKS))})"))
    return out


# --------------------------------------------------------------------------
# Pass 1b: bitpacked integer-pipeline discipline
# --------------------------------------------------------------------------
def integer_pipeline_lint(cell: Cell, closed: Any) -> list[Finding]:
    """The bitpacked layout's reason to exist is an index pipeline with
    no float excursion (the paper's vmsgeu/bit-plane loop): flag any
    integer->float conversion in a bitpacked leaf_index/fused trace.
    bool->float is allowed — that is the leaf-gather one-hot being
    built from a comparison mask, downstream of index assembly."""
    if cell.layout != "bitpacked" \
            or cell.op not in ("leaf_index", "fused_predict"):
        return []
    out: list[Finding] = []
    for jaxpr in jt.iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = jt.unwrap_aval(getattr(eqn.invars[0], "aval", None))
            dst = eqn.params.get("new_dtype")
            if src is None or dst is None:
                continue
            sd, dd = np.dtype(src.dtype), np.dtype(dst)
            if sd.kind in "iu" and dd.kind == "f":
                out.append(_finding(
                    cell, "int-pipeline",
                    f"{sd.name} {jt.aval_short(src)} converted to "
                    f"{dd.name} inside the bitpacked pipeline"))
    return out


# --------------------------------------------------------------------------
# Pass 2: VMEM working-set audit
# --------------------------------------------------------------------------
def _model_bytes(cell: Cell, refs: list[Any]) -> Optional[int]:
    """The kernels.tuning footprint model for this kernel, with dims
    recovered from the kernel body's BLOCK-shaped ref avals.  None for
    kernels without a model (l2sq) — those get the budget check only."""
    from repro.kernels import tuning

    def ib(a):  # itemsize
        return np.dtype(a.dtype).itemsize

    if cell.op == "binarize":
        x, borders, out = refs
        bn, bf = x.shape
        return tuning.binarize_footprint(bn, bf, borders.shape[0],
                                         bins_bytes=ib(out))
    if cell.op == "leaf_index":
        bins, model, out = refs[0], refs[1], refs[-1]
        bt, bn = out.shape                 # tree-major index block
        if cell.layout == "depth_major":
            _, d, f = model.shape
            return tuning.leaf_index_footprint(bn, bt, f, d,
                                               bins_bytes=ib(bins))
        if cell.layout == "bitpacked":
            return tuning.leaf_index_footprint(
                bn, bt, bins.shape[1], model.shape[0],
                bins_bytes=ib(bins), gather="bitplane")
        return tuning.leaf_index_footprint(bn, bt, bins.shape[1],
                                           model.shape[1],
                                           bins_bytes=ib(bins))
    if cell.op == "node_index":
        bins, _nf, _nb, paths, _plen, out = refs
        bt, bn = out.shape                 # tree-major index block
        return tuning.node_index_footprint(bn, bt, bins.shape[1],
                                           paths.shape[1],
                                           bins_bytes=ib(bins))
    if cell.op == "leaf_gather":
        idx, lv, _out = refs
        bt, bn = idx.shape
        _, c, l = lv.shape                 # class-major leaf block
        return tuning.leaf_gather_footprint(bn, bt, l, c)
    if cell.op == "fused_predict":
        # x, borders, <layout's model arrays>, lv, out, idx, bins scratch
        x, borders, model = refs[0], refs[1], refs[2]
        lv, idx, scratch = refs[-4], refs[-2], refs[-1]
        bt = idx.shape[0]
        if cell.layout == "depth_major":
            d = model.shape[1]
        elif cell.layout == "bitpacked":
            d = model.shape[0]
        else:
            d = model.shape[1]
        gather = "bitplane" if cell.layout == "bitpacked" else "mxu"
        _, c, l = lv.shape                 # class-major leaf block
        return tuning.fused_footprint(x.shape[0], bt, x.shape[1], d, l, c,
                                      borders.shape[0],
                                      bins_bytes=ib(scratch),
                                      gather=gather)
    if cell.op == "histogram":
        bins, _leaf, g, e_leaf, _e_stat, out = refs
        bf, _, bn = bins.shape
        return tuning.hist_footprint(bf, bn, e_leaf.shape[0],
                                     out.shape[1], g.shape[1],
                                     bins_bytes=ib(bins))
    return None  # l2sq: no footprint model — budget check only


def vmem_audit(cell: Cell, closed: Any) -> tuple[list[Finding], int]:
    """Per-pallas-kernel working-set estimate (resident ref blocks +
    peak live interior values, each priced tile-padded as Mosaic
    allocates it) vs the VMEM budget and the op's tuning footprint
    model, which counts the same padding.  Returns (findings,
    kernels_audited)."""
    from repro.kernels import tuning

    out: list[Finding] = []
    calls = jt.find_pallas_calls(closed.jaxpr)
    for eqn in calls:
        refs = jt.pallas_ref_avals(eqn)
        body = jt.pallas_kernel_jaxpr(eqn)
        est = sum(jt.vmem_aval_bytes(a) for a in refs) \
            + jt.peak_live_bytes(body, include_invars=False,
                                 size=jt.vmem_aval_bytes)
        if est > tuning.VMEM_BUDGET:
            out.append(_finding(
                cell, "vmem-budget",
                f"estimated working set {est} B exceeds VMEM_BUDGET "
                f"{tuning.VMEM_BUDGET} B"))
        try:
            model = _model_bytes(cell, refs)
        except (ValueError, IndexError) as e:
            out.append(_finding(
                cell, "trace-error",
                f"footprint-model dim recovery failed on refs "
                f"{[jt.aval_short(a) for a in refs]}: {e}"))
            continue
        if model is not None and est > VMEM_SLACK * model:
            out.append(_finding(
                cell, "vmem-model",
                f"estimated working set {est} B is "
                f"{est / model:.2f}x the tuning footprint model "
                f"({model} B; slack {VMEM_SLACK}x) — the block tuner "
                "would mis-plan this kernel"))
    return out, len(calls)


# --------------------------------------------------------------------------
# Pass 3: plan-entry transfer/retrace lints
# --------------------------------------------------------------------------
def entry_findings(name: str, closed: Any) -> list[Finding]:
    """Lint one Predictor plan entry's abstract trace.

    transfer: explicit `device_put` staging inside a jitted entry, or a
    large buffer entering a pjit region with donation disabled.
    retrace: weakly-typed or x64 avals at the entry boundary — shapes
    the ≤2-shapes compile contract does not cover, so every call with a
    fresh Python scalar would silently retrace."""
    cell = Cell("plan", name, "", "")
    out: list[Finding] = []
    for aval in list(closed.in_avals) + [v.aval for v in
                                         closed.jaxpr.constvars]:
        if getattr(aval, "weak_type", False):
            out.append(_finding(
                cell, "retrace",
                f"weakly-typed boundary aval {jt.aval_short(aval)} — "
                "each distinct Python scalar retraces"))
        dt = getattr(jt.unwrap_aval(aval), "dtype", None)
        if dt is not None and np.dtype(dt).itemsize == 8:
            out.append(_finding(
                cell, "retrace",
                f"x64 boundary aval {jt.aval_short(aval)} leaks into "
                "the plan (the serve path pins float32/int32)"))
    for jaxpr in jt.iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "device_put":
                out.append(_finding(
                    cell, "transfer",
                    "device_put staged inside the traced entry — "
                    "host->device transfer on every call"))
            elif eqn.primitive.name in jt.JIT_PRIMS:
                donated = eqn.params.get("donated_invars", ())
                for v, don in zip(eqn.invars, donated):
                    nbytes = jt.aval_bytes(getattr(v, "aval", None))
                    if nbytes > LARGE_BUFFER_BYTES and not don:
                        out.append(_finding(
                            cell, "transfer",
                            f"{nbytes} B buffer enters jitted region "
                            "without donation — doubles peak residency"))
    return out


# --------------------------------------------------------------------------
# Pass 3b: sharded-entry collective discipline (shard-parity)
# --------------------------------------------------------------------------
# Collectives that re-materialize a sharded value on every shard.  The
# row-sharded predict path owes its weak scaling to each shard touching
# only its own (N/K, F) panel; an all_gather in the jaxpr means some
# operation pulled the full panel back — O(N) bytes and O(N) work per
# shard, i.e. no scaling at all.  `psum` is *expected* (tree-sharded
# leaf-sum reduction) and reduces, never gathers, so it is not listed.
GATHERING_COLLECTIVES = frozenset({
    "all_gather", "all_gather_invariant", "all_to_all", "pgather"})


def sharded_entry_findings(name: str, closed: Any) -> list[Finding]:
    """Lint one sharded plan entry's abstract trace: no gathering
    collective may appear anywhere in it (sub-jaxprs included — the
    shard_map body is a sub-jaxpr of the traced entry)."""
    cell = Cell("plan", name, "", "")
    out: list[Finding] = []
    for jaxpr in jt.iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in GATHERING_COLLECTIVES:
                out.append(_finding(
                    cell, "shard-parity",
                    f"{eqn.primitive.name} of "
                    f"{jt.aval_short(getattr(eqn.invars[0], 'aval', None))}"
                    " inside a row-sharded entry — the bins panel must "
                    "stay shard-local"))
    return out


def shard_parity_findings(batch_sizes: Any = (8,)) -> list[Finding]:
    """Abstract-trace the sharded entry points of one plan per layout
    over a device-free `AbstractMesh` and lint each trace for gathering
    collectives.  Also re-asserts the no-compile contract: the walk
    must not tick the plans' trace counters (an AbstractMesh cannot be
    compiled against, so a tick means a sharded entry escaped the
    abstract path)."""
    import jax
    from repro.core.predictor import Predictor
    from repro.analysis.matrix import canonical_ensemble

    mesh = jax.sharding.AbstractMesh((4,), ("data",))
    sizes = [n for n in batch_sizes if n % 4 == 0] or [8]
    ens, _ = canonical_ensemble()
    out: list[Finding] = []
    for lay in ("soa", "depth_major", "depth_grouped", "bitpacked"):
        plan = Predictor.build(ens, strategy="staged", layout=lay)
        entries = plan.trace_entries(
            batch_sizes=sizes, mesh=mesh,
            entries=("sharded_raw", "sharded_raw_pool"))
        for label, closed in entries.items():
            out += sharded_entry_findings(f"{lay}:{label}", closed)
        if plan.stats["total_traces"]:
            out.append(Finding(
                rule="trace-error", op="plan", impl=f"{lay}:sharded",
                message="sharded trace walk compiled — it must stay "
                        "abstract (AbstractMesh)"))
    return out


# --------------------------------------------------------------------------
# Pass 4: tuning-model consistency (chunk planner, layout selector)
# --------------------------------------------------------------------------
def chunk_model_findings() -> list[Finding]:
    """`best_chunk_rows` must honor its own documented contract for
    representative model shapes: pow2 rows in [MIN, MAX], working set
    within budget unless pinned at the MIN floor, small datasets capped
    at the first covering pow2."""
    from repro.kernels import tuning

    cell = Cell("tuning", "best_chunk_rows", "", "")
    out: list[Finding] = []
    shapes = [  # (n_features, n_outputs, kwargs)
        (10, 1, {}),
        (54, 7, dict(n_borders=254, n_trees=100, n_leaves=64)),
        (784, 10, dict(n_borders=255, n_trees=500, n_leaves=64)),
        (2000, 1, dict(n_borders=255, n_trees=1000, n_leaves=64)),
    ]
    for f, c, kw in shapes:
        rows = tuning.best_chunk_rows(f, c, **kw)
        per_row = tuning.chunk_row_bytes(f, c, **kw)
        desc = f"F={f} C={c} {kw or ''}".strip()
        if rows & (rows - 1) or not (tuning.MIN_CHUNK_ROWS <= rows
                                     <= tuning.MAX_CHUNK_ROWS):
            out.append(_finding(
                cell, "chunk-model",
                f"{desc}: rows={rows} not a pow2 in "
                f"[{tuning.MIN_CHUNK_ROWS}, {tuning.MAX_CHUNK_ROWS}]"))
        elif rows * per_row > tuning.CHUNK_BUDGET_BYTES \
                and rows > tuning.MIN_CHUNK_ROWS:
            out.append(_finding(
                cell, "chunk-model",
                f"{desc}: rows={rows} x {per_row} B/row = "
                f"{rows * per_row} B exceeds CHUNK_BUDGET_BYTES "
                f"{tuning.CHUNK_BUDGET_BYTES} above the MIN floor"))
        capped = tuning.best_chunk_rows(f, c, n_rows=1000, **kw)
        cover = tuning.MIN_CHUNK_ROWS
        while cover < 1000:
            cover *= 2
        if capped > max(cover, tuning.MIN_CHUNK_ROWS):
            out.append(_finding(
                cell, "chunk-model",
                f"{desc}: n_rows=1000 cap ignored (rows={capped})"))
    return out


def layout_cost_findings() -> list[Finding]:
    """`tuning.layout_costs` (what `best_layout` ranks on) vs the bytes
    each layout actually lowers for a canonical mixed-depth ensemble at
    lane-aligned dims.  Loose bounds: the model is pre-padding, the
    lowering pads groups/trees to block multiples and may narrow
    bitpacked planes to uint8 — a model off by more than 4x either way
    would mis-rank layouts."""
    from repro.core import layout as layout_mod
    from repro.kernels import tuning
    from repro.analysis.matrix import canonical_ensemble

    cell = Cell("tuning", "layout_costs", "", "")
    ens, true_depths = canonical_ensemble()
    costs = tuning.layout_costs(true_depths, ens.n_outputs,
                                ens.n_features)
    lowered = {lay: layout_mod.lower(ens, lay, backend="ref")
               for lay in ("soa", "depth_grouped", "depth_major",
                           "bitpacked")}
    actual = {
        "soa_leaf_bytes": lowered["soa"].leaf_table_bytes(),
        "depth_grouped_leaf_bytes":
            lowered["depth_grouped"].leaf_table_bytes(),
        "depth_major_onehot_bytes": lowered["depth_major"].onehot_bytes(),
        "bitpacked_leaf_bytes": lowered["bitpacked"].leaf_table_bytes(),
        "bitpacked_plane_bytes": lowered["bitpacked"].plane_bytes(),
    }
    out: list[Finding] = []
    for key, model in costs.items():
        got = actual[key]
        if not (model / 4 <= got <= model * 4 + 65536):
            out.append(_finding(
                cell, "layout-cost",
                f"{key}: model {model} B vs lowered {got} B — "
                "outside the 4x mis-rank bound"))
    return out
