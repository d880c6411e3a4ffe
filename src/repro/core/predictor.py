"""Compiled-plan GBDT evaluation: prepare the model once, predict many.

The paper's speedups come from hoisting everything that does not depend
on the incoming batch out of the hot loop: CatBoost's evaluator prepares
quantized borders and a blocked tree layout once, then runs a tight
vectorized loop per batch.  The kwarg-threaded `core.predict.raw_predict`
path re-did that preparation on every call — re-resolving `auto`
strategy/backend, re-running the block tuner, and re-padding the *model*
arrays (borders, splits, leaf values) to block multiples inside each
predict.  This module is the prepare-once counterpart:

  config = PredictConfig(strategy="fused", backend="pallas")
  plan   = Predictor.build(ensemble, config)   # resolve + pad ONCE
  plan.raw(x)       # (N, C) raw scores — jitted, cached per batch shape
  plan.proba(x)     # class probabilities
  plan.classify(x)  # argmax / threshold
  plan.sharded(mesh)(x)   # mesh-distributed raw scores

Quantized-first evaluation (the paper's actual data flow — its
evaluators binarize once and run CalcIndexes over uint8 bins, never
re-touching float features):

  pool = plan.quantize(x)      # binarize ONCE -> uint8 QuantizedPool
  plan.raw(pool)               # skips binarize entirely
  plan.proba(pool); plan.classify(pool)

A pool is schema-stamped (`quantize.borders_fingerprint`): scoring it
through a plan quantized with different borders raises `ValueError`
instead of silently indexing the wrong bin space.  Models sharing a
schema share pools — the multi-model registry serving win.

`Predictor.build` resolves `auto` choices to concrete ones (backend via
the kernel registry's platform default, fused block shapes from
`kernels.tuning`), pads the model arrays to block multiples exactly
once, and caches jitted entry points; with bucketed serving batches the
number of XLA compiles is bounded by (entry points x batch buckets).
The kwarg API in `core.predict` remains as a thin one-shot shim over
this class.

`from_catboost_json` ingests CatBoost's exported oblivious-tree JSON
(`model.save_model(f, format="json")`): per-feature borders, split
feature/border per depth, flat leaf values — the real-model workload the
paper benchmarks.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
import threading
from typing import Any, Callable, Literal, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import layout as layout_mod
from repro.core.layout import LoweredEnsemble, STAGED_TREE_ALIGN
from repro.core.quantize import (QuantizedPool, borders_fingerprint,
                                 MAX_BINS)
from repro.core.trees import NodeEnsemble, ObliviousEnsemble
from repro.kernels import ops
from repro.kernels import registry
from repro.kernels import tuning
from repro.kernels.ops import PAD_SPLIT_BIN
from repro.obs.trace import get_tracer

_TRACER = get_tracer()

Strategy = Literal["auto", "staged", "fused"]
Backend = str   # "auto" or a kernel-registry backend family

_STRATEGIES = ("auto", "staged", "fused")


@dataclasses.dataclass(frozen=True)
class PredictConfig:
    """Validated prediction-plan configuration.

    `auto` fields are placeholders resolved at plan-build time by
    `resolve()`; a built `Predictor` only ever holds concrete values, so
    nothing downstream re-queries the platform or the tuner per call.

      strategy   staged (paper three-pass) | fused (single Pallas pass)
      backend    a kernel-registry backend: pallas (real kernels;
                 interpret on CPU) | ref (pure jnp) — validated against
                 `kernels.registry.known_backends()`.  Note a third
                 registered family would pass validation but currently
                 gets the ref (unpadded) model layout: `layout.lower`
                 only knows how to pre-pad for the pallas kernels'
                 block contracts
      layout     physical model layout the plan lowers to (see
                 `repro.core.layout`): soa | depth_major |
                 depth_grouped | bitpacked | nodes; auto picks nodes
                 for a `NodeEnsemble` (non-symmetric trees), else from
                 the ensemble's depth histogram / leaf-table bytes via
                 `kernels.tuning.best_layout`
      tree_block staged-path tree blocking (CalcTreesBlockedImpl); 0 = off
                 (soa layout only — an auto layout resolves to soa when
                 tree blocking is requested)
      block_n/t  fused-kernel Pallas block shapes; None = autotuned
    """
    strategy: Strategy = "auto"
    backend: Backend = "auto"
    layout: str = "auto"
    tree_block: int = 0
    block_n: Optional[int] = None
    block_t: Optional[int] = None

    def __post_init__(self):
        if self.strategy not in _STRATEGIES:
            raise ValueError(f"strategy must be one of {_STRATEGIES}, "
                             f"got {self.strategy!r}")
        backends = ("auto",) + registry.known_backends()
        if self.backend not in backends:
            raise ValueError(f"backend must be one of {backends}, "
                             f"got {self.backend!r}")
        layouts = ("auto",) + layout_mod.LAYOUT_NAMES
        if self.layout not in layouts:
            raise ValueError(f"layout must be one of {layouts}, "
                             f"got {self.layout!r}")
        if not isinstance(self.tree_block, int) or self.tree_block < 0:
            raise ValueError(f"tree_block must be an int >= 0, "
                             f"got {self.tree_block!r}")
        if self.tree_block and self.layout not in ("auto", "soa"):
            raise ValueError(
                f"tree_block is a soa-layout feature (the depth layouts "
                f"block by structure instead); got tree_block="
                f"{self.tree_block} with layout={self.layout!r}")
        for name in ("block_n", "block_t"):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{name} must be a positive int or None, "
                                 f"got {v!r}")

    @property
    def is_resolved(self) -> bool:
        return (self.strategy != "auto" and self.backend != "auto"
                and self.layout != "auto"
                and (self.strategy != "fused"
                     or (self.block_n is not None
                         and self.block_t is not None)))

    def resolve(self, ensemble: ObliviousEnsemble | NodeEnsemble, *,
                n_rows: Optional[int] = None) -> "PredictConfig":
        """Concretize every `auto` choice for one ensemble.

        The `auto` backend resolves through the kernel registry
        (`registry.default_backend()`, reading the once-per-process
        platform); fused block shapes come from the VMEM footprint
        model in `kernels.tuning`, sized to this ensemble (and
        `n_rows`, the expected batch size, when known); the `auto`
        layout comes from `tuning.best_layout` on the ensemble's depth
        histogram (tracer ensembles — per-shard plans built inside
        shard_map — pin to soa: grouping needs to read split_bins).  A
        `NodeEnsemble` has one layout, nodes; the oblivious layouts
        refuse it.
        """
        nodes = isinstance(ensemble, NodeEnsemble)
        if nodes and self.layout not in ("auto", "nodes"):
            raise ValueError(
                f"layout {self.layout!r} holds one split per depth and "
                "cannot score a NodeEnsemble (non-symmetric trees); use "
                "layout='nodes' or 'auto'")
        if nodes and self.tree_block:
            raise ValueError("tree_block is a soa-layout feature; a "
                             "NodeEnsemble lowers to the nodes layout")
        strategy, backend = self.strategy, self.backend
        if strategy == "auto":
            strategy = "fused" if ops.default_platform() == "tpu" \
                else "staged"
        if backend == "auto":
            backend = registry.default_backend()
        layout = self.layout
        if layout == "auto" and nodes:
            layout = "nodes"
        elif layout == "auto":
            if self.tree_block or not layout_mod.is_concrete(ensemble):
                layout = "soa"
            else:
                layout = tuning.best_layout(ensemble.true_depths,
                                            ensemble.n_outputs,
                                            ensemble.n_features,
                                            backend=backend)
        block_n, block_t = self.block_n, self.block_t
        if strategy == "fused" and (block_n is None or block_t is None):
            tn, tt = tuning.best_fused_blocks(
                ensemble.n_features, ensemble.depth,
                ensemble.leaf_values.shape[1], ensemble.n_outputs,
                ensemble.borders.shape[0], n_rows=n_rows,
                n_trees=ensemble.n_trees)
            block_n = block_n or tn
            block_t = block_t or tt
        return dataclasses.replace(self, strategy=strategy, backend=backend,
                                   layout=layout, block_n=block_n,
                                   block_t=block_t)


def proba_from_raw(raw: jax.Array, n_outputs: int) -> jax.Array:
    """Raw scores -> class probabilities: two-column sigmoid for binary
    models, softmax otherwise.  The single definition every predict
    surface (plan entries, kwarg shims, mesh serving) shares."""
    with jax.named_scope("gbdt/softmax"):
        if n_outputs == 1:
            p = jax.nn.sigmoid(raw[:, 0])
            return jnp.stack([1.0 - p, p], axis=1)
        return jax.nn.softmax(raw, axis=-1)


def classify_from_raw(raw: jax.Array, n_outputs: int) -> jax.Array:
    """Raw scores -> int32 class ids: zero threshold for binary models,
    argmax otherwise (single definition, like `proba_from_raw`)."""
    if n_outputs == 1:
        return (raw[:, 0] > 0.0).astype(jnp.int32)
    return jnp.argmax(raw, axis=-1).astype(jnp.int32)


def _lower_model(ensemble: ObliviousEnsemble, cfg: PredictConfig
                 ) -> tuple[LoweredEnsemble, int, float]:
    """The one-time model lowering `Predictor.build` hoists.

    Returns the lowered model, the number of model pad ops spent, and
    the wall-clock lowering seconds (surfaced in `Predictor.stats` so
    serving dashboards can see what one-time cost shipped).
    """
    import time
    t_align = cfg.block_t if cfg.strategy == "fused" else STAGED_TREE_ALIGN
    tree_block = cfg.tree_block if cfg.strategy == "staged" else 0
    t0 = time.perf_counter()
    lowered = layout_mod.lower(ensemble, cfg.layout, backend=cfg.backend,
                               t_align=t_align, tree_block=tree_block)
    return lowered, lowered.n_model_pads, time.perf_counter() - t0


class Predictor:
    """A compiled prediction plan for one ensemble.

    Construct with `Predictor.build(...)` (or `from_catboost_json`).
    The plan owns:
      * a fully resolved `PredictConfig` (no `auto` left)
      * the model lowered ONCE into its physical layout (see
        `repro.core.layout`): arrays reordered / precomputed / padded
        to block multiples at build time
      * jitted `raw` / `proba` / `classify` entry points whose compile
        cache is keyed by batch shape — with bucketed serving batches,
        compiles are bounded by (entries used x buckets)
    The plan is immutable: if the underlying ensemble changes, build a
    new `Predictor` (see `serving.engine.ModelRegistry.register`).
    """

    def __init__(self, ensemble: ObliviousEnsemble, config: PredictConfig,
                 lowered: Optional[LoweredEnsemble], *,
                 on_trace: Optional[Callable[[], None]] = None,
                 build_model_pads: int = 0,
                 lower_time_s: float = 0.0):
        if not config.is_resolved:
            raise ValueError("Predictor requires a resolved PredictConfig; "
                             "use Predictor.build()")
        self.ensemble = ensemble
        self.config = config
        self._lowered = lowered
        self._on_trace = on_trace
        self._build_model_pads = build_model_pads
        self._lower_time_s = lower_time_s
        self._lock = threading.Lock()
        self._traces: dict[str, int] = {}
        self._entry_shapes: set[tuple] = set()
        self._sharded_cache: dict[tuple, Callable] = {}
        # Schema fingerprint: which QuantizedPools this plan may score.
        # Computed lazily — the per-shard plans `sharded()` builds inside
        # shard_map hold tracer borders, which cannot be hashed (and
        # never score pools).
        self._schema_fingerprint: Optional[str] = None
        # Abstract (make_jaxpr) traces per (entry, shape, dtype, schema
        # fingerprint) — the contract checker walks every plan entry,
        # and walking must never compile (the jitted entries each tick
        # an XLA compile) nor re-trace an entry it already walked.
        self._abstract_traces: dict[tuple, Any] = {}
        self._abstract_trace_misses = 0
        self._entries = {
            "raw": self._make_entry("raw", self._raw_impl),
            "proba": self._make_entry("proba", self._proba_impl),
            "classify": self._make_entry("classify", self._classify_impl),
            # quantized-pool entries: same surface, bins in, no binarize
            "raw_pool": self._make_entry("raw_pool", self._pool_raw_impl),
            "proba_pool": self._make_entry("proba_pool",
                                           self._pool_proba_impl),
            "classify_pool": self._make_entry("classify_pool",
                                              self._pool_classify_impl),
            "quantize": self._make_entry("quantize", self._quantize_impl),
        }

    # -- construction ------------------------------------------------------
    @classmethod
    def build(cls, ensemble: ObliviousEnsemble | NodeEnsemble,
              config: Optional[PredictConfig] = None, *,
              expected_batch: Optional[int] = None,
              on_trace: Optional[Callable[[], None]] = None,
              prepare: bool = True,
              **config_kw: Any) -> "Predictor":
        """Resolve the config and prepare the model — the only place any
        per-ensemble preparation happens.

        `expected_batch` feeds the fused block tuner's padding-waste
        penalty (serving passes its largest bucket).  `config_kw` is a
        convenience for `Predictor.build(ens, strategy="fused")` style
        calls; it cannot be combined with an explicit `config`.
        `prepare=False` defers the model lowering to the first local
        predict — for plans used only through `sharded(mesh)`, which
        lowers per tree shard and would never read the local copy.
        """
        if config is None:
            config = PredictConfig(**config_kw)
        elif config_kw:
            raise TypeError("pass either a PredictConfig or config kwargs, "
                            f"not both: {sorted(config_kw)}")
        resolved = config.resolve(ensemble, n_rows=expected_batch)
        lowered, pads, secs = (_lower_model(ensemble, resolved) if prepare
                               else (None, 0, 0.0))
        return cls(ensemble, resolved, lowered, on_trace=on_trace,
                   build_model_pads=pads, lower_time_s=secs)

    @classmethod
    def from_catboost_json(cls, path: str | pathlib.Path,
                           config: Optional[PredictConfig] = None,
                           **build_kw: Any) -> "Predictor":
        """Build a plan straight from a CatBoost JSON model export."""
        return cls.build(load_catboost_json(path), config, **build_kw)

    # -- plan internals ----------------------------------------------------
    @property
    def lowered(self) -> LoweredEnsemble:
        """The physical `LoweredEnsemble` this plan scores through
        (lowering it first for deferred-prepare plans)."""
        return self._ensure_prepared()

    @property
    def schema_fingerprint(self) -> str:
        """Fingerprint of this plan's quantization schema: pools are
        scoreable iff their fingerprint matches."""
        if self._schema_fingerprint is None:
            self._schema_fingerprint = borders_fingerprint(
                self.ensemble.borders)
        return self._schema_fingerprint

    def _note_trace(self, name: str) -> None:
        with self._lock:
            self._traces[name] = self._traces.get(name, 0) + 1
        if self._on_trace is not None:
            self._on_trace()

    def _make_entry(self, name: str, impl: Callable) -> Callable:
        def traced(p, x):
            # Body runs only when jax traces a new shape; counting here
            # counts exactly the XLA compiles for this entry point (and
            # keeps shape bookkeeping off the cached-dispatch hot path).
            self._note_trace(name)
            with self._lock:
                self._entry_shapes.add((name,) + tuple(x.shape))
            if _TRACER.enabled:
                # one instant per XLA compile: (entry, layout, batch
                # bucket) — the timeline marker for every cache miss
                _TRACER.instant(f"compile/{name}", "compile",
                                entry=name, layout=self.config.layout,
                                batch=int(x.shape[0]))
            return impl(p, x)
        # The lowered model is an argument, not a closure: a constant
        # would be baked into every executable (one device copy of the
        # leaf table per entry and batch bucket, and executables too
        # large for the persistent compile cache).
        return jax.jit(traced)

    def _ensure_prepared(self) -> LoweredEnsemble:
        """Model lowering for a `prepare=False` plan, eagerly (never
        inside a trace: lowering must run once, not once per compile)."""
        p = self._lowered
        if p is None:
            with self._lock:
                p = self._lowered
                if p is None:
                    p, pads, secs = _lower_model(self.ensemble, self.config)
                    self._lowered = p
                    self._build_model_pads = pads
                    self._lower_time_s = secs
        return p

    def _accumulate_trees(self, p: LoweredEnsemble,
                          bins: jax.Array) -> jax.Array:
        """Staged index+gather over the lowered model, from bins.

        Shared by the float path (after its binarize stage) and the
        quantized-pool path (which starts here — binarize never runs).
        `bins` may be int32 or uint8; the registry routes uint8 to the
        u8 kernel variants.  The per-layout kernel routing lives on the
        `LoweredEnsemble` itself (`leaf_sum`): soa runs the classic
        index+gather (optionally tree-blocked), depth_major matmuls
        against the precomputed one-hot, depth_grouped loops depth
        groups.  A fused-strategy plan scoring a pool also lands here:
        its trees are padded to cfg.block_t multiples, so the staged
        kernels get that block shape.
        """
        cfg = self.config
        block_t = (cfg.block_t if cfg.strategy == "fused"
                   else STAGED_TREE_ALIGN)
        return p.leaf_sum(bins, backend=cfg.backend, block_t=block_t)

    def _raw_impl(self, p: LoweredEnsemble, x: jax.Array) -> jax.Array:
        cfg = self.config
        base = self.ensemble.base_score[None, :]
        if cfg.strategy == "fused":
            return base + p.fused_raw(x, backend=cfg.backend,
                                      block_n=cfg.block_n,
                                      block_t=cfg.block_t)
        bins = ops.binarize_prepadded(x, p.borders, backend=cfg.backend)
        return base + self._accumulate_trees(p, bins)

    def _proba_impl(self, p: LoweredEnsemble, x: jax.Array) -> jax.Array:
        return proba_from_raw(self._raw_impl(p, x), self.ensemble.n_outputs)

    def _classify_impl(self, p: LoweredEnsemble,
                       x: jax.Array) -> jax.Array:
        return classify_from_raw(self._raw_impl(p, x),
                                 self.ensemble.n_outputs)

    # -- quantized-pool path (binarize skipped entirely) -------------------
    def _pool_raw_impl(self, p: LoweredEnsemble,
                       bins: jax.Array) -> jax.Array:
        # Pool bins carry the unpadded feature axis (shareable across
        # plans); pad data-side up to the lowered borders' aligned F.
        bins = ops.pad_features(bins, p.borders.shape[1])
        base = self.ensemble.base_score[None, :]
        return base + self._accumulate_trees(p, bins)

    def _pool_proba_impl(self, p: LoweredEnsemble,
                         bins: jax.Array) -> jax.Array:
        return proba_from_raw(self._pool_raw_impl(p, bins),
                              self.ensemble.n_outputs)

    def _pool_classify_impl(self, p: LoweredEnsemble,
                            bins: jax.Array) -> jax.Array:
        return classify_from_raw(self._pool_raw_impl(p, bins),
                                 self.ensemble.n_outputs)

    def _quantize_impl(self, p: LoweredEnsemble,
                       x: jax.Array) -> jax.Array:
        # Binarize against the *lowered* borders (zero model-side pads
        # at trace time), then drop the alignment columns so the pool is
        # schema-wide shareable, not plan-layout specific.
        bins = ops.binarize_u8_prepadded(x, p.borders,
                                         backend=self.config.backend)
        return bins[:, :self.ensemble.n_features]

    def _check_pool(self, pool: QuantizedPool) -> None:
        if pool.fingerprint != self.schema_fingerprint:
            raise ValueError(
                "QuantizedPool schema mismatch: pool was quantized under "
                f"fingerprint {pool.fingerprint} but this plan's borders "
                f"have fingerprint {self.schema_fingerprint} — its "
                "split_bins would index a different bin space.  "
                "Re-quantize with this plan's `quantize(x)` (pools are "
                "only shareable across models with identical borders).")

    def _call(self, name: str, x) -> jax.Array:
        if self._lowered is None:
            self._ensure_prepared()
        if isinstance(x, QuantizedPool):
            self._check_pool(x)
            bins = x.bins
            if not (isinstance(bins, jax.Array)
                    and bins.dtype == jnp.uint8):
                bins = jnp.asarray(bins, jnp.uint8)
            return self._entries[name + "_pool"](self._lowered, bins)
        if not (isinstance(x, jax.Array) and x.dtype == jnp.float32):
            x = jnp.asarray(x, jnp.float32)   # skip no-op asarray dispatch
        return self._entries[name](self._lowered, x)

    # -- public entry points -----------------------------------------------
    def quantize(self, x) -> QuantizedPool:
        """Binarize a float batch once into a reusable `QuantizedPool`.

        (N, F) float -> uint8 pool; `raw/proba/classify` accept the
        pool and skip binarization entirely.  Pools are shareable
        across every plan whose ensemble has identical borders
        (`schema_fingerprint` guards this at score time)."""
        if self.ensemble.borders.shape[0] > MAX_BINS - 1:
            raise ValueError(
                f"cannot quantize to uint8 bins: ensemble has "
                f"{self.ensemble.borders.shape[0]} borders "
                f"(> {MAX_BINS - 1})")
        p = self._ensure_prepared()
        x = jnp.asarray(x, jnp.float32)
        return QuantizedPool(self._entries["quantize"](p, x),
                             self.schema_fingerprint)

    def raw(self, x) -> jax.Array:
        """(N, F) floats or a `QuantizedPool` -> (N, C) raw scores
        (tree sum + base score).  The pool path never binarizes."""
        return self._call("raw", x)

    def proba(self, x) -> jax.Array:
        """(N, F) floats or a `QuantizedPool` -> (N, max(C, 2))
        class probabilities."""
        return self._call("proba", x)

    def classify(self, x) -> jax.Array:
        """(N, F) floats or a `QuantizedPool` -> (N,) int32 class ids."""
        return self._call("classify", x)

    def raw_uncached(self, x) -> jax.Array:
        """Un-jitted raw scores — for callers that bring their own jit
        (the `core.predict` shim, shard_map bodies).  Accepts floats or
        a `QuantizedPool` like `raw`."""
        p = self._ensure_prepared()
        if isinstance(x, QuantizedPool):
            self._check_pool(x)
            return self._pool_raw_impl(p, jnp.asarray(x.bins, jnp.uint8))
        return self._raw_impl(p, jnp.asarray(x, jnp.float32))

    def _shard_raw(self, lw: LoweredEnsemble, data: jax.Array,
                   kind: str, cfg: PredictConfig) -> jax.Array:
        """Shard-local raw tree sum (no base score) over one lowered
        model — the body every mesh entry maps.  `lw` is the plan's
        own `LoweredEnsemble` (or one tree shard of it) passed through
        shard_map as a replicated/partitioned pytree, so the full
        registry dispatch — any layout, any backend — runs per shard.
        `kind` is "pool" (uint8 bins, binarize never dispatched) or
        "float"."""
        block_t = (cfg.block_t if cfg.strategy == "fused"
                   else STAGED_TREE_ALIGN)
        if kind == "pool":
            bins = ops.pad_features(data, lw.borders.shape[1])
            return lw.leaf_sum(bins, backend=cfg.backend, block_t=block_t)
        if cfg.strategy == "fused":
            return lw.fused_raw(data, backend=cfg.backend,
                                block_n=cfg.block_n, block_t=cfg.block_t)
        bins = ops.binarize_prepadded(data, lw.borders,
                                      backend=cfg.backend)
        return lw.leaf_sum(bins, backend=cfg.backend, block_t=block_t)

    def sharded(self, mesh, *, data_axes: Sequence[str] = ("data",),
                model_axis: str = "model",
                strategy: Optional[str] = None,
                shard_axis: str = "auto") -> Callable[[Any], jax.Array]:
        """Mesh-distributed raw scores over floats or a `QuantizedPool`.

        The plan's own `LoweredEnsemble` — whatever layout it resolved
        to: soa / depth_major / depth_grouped / bitpacked — flows into
        `shard_map` as a pytree, so every shard runs the exact same
        registry-dispatched kernels as the single-device plan:

          * **row sharding** (the bulk default): the model is
            replicated (`P()`), rows partition over `data_axes`; a
            `QuantizedPool` shards its uint8 bins directly — binarize
            is never dispatched (the pool contract), and the result is
            bit-for-bit the single-device plan's.
          * **tree sharding** (giant ensembles): `layout.shard_trees`
            splits the tree axis into neutral-padded equal slices,
            stacked over `model_axis`; shard partial sums combine with
            a `psum` (float re-association: parity ~1e-6, not exact).
          * **hybrid**: a mesh carrying both `data_axes` and
            `model_axis` shards rows *and* trees (PR-2's semantics,
            now on the lowered pytree).

        `shard_axis` ("rows" | "trees" | "auto") picks how a pure data
        mesh is used; "auto" asks `tuning.best_shard_axis` per batch.
        Plans on the nodes layout (non-symmetric trees) are not
        sharded: this raises ValueError for them.
        Row counts need not divide the mesh: ragged batches are padded
        to the row-shard multiple inside the jitted entry and sliced
        back (pad rows are zeros; they never reach the caller).

        `strategy` overrides the plan's strategy for the shard body
        (serving forces `staged` for auto-resolved plans).  The
        shard_map closures are built once per (mesh, axes, strategy,
        shard_axis) and cached on the plan; jit handles per-shape
        caching under that."""
        if self.config.layout == "nodes":
            raise ValueError(
                "sharded scoring is not supported on the nodes layout "
                "(non-symmetric trees): score node tables on one device")
        key = (id(mesh), tuple(data_axes), model_axis, strategy,
               shard_axis)
        fn = self._sharded_cache.get(key)
        if fn is not None:
            return fn
        if shard_axis not in ("auto", "rows", "trees"):
            raise ValueError(f"shard_axis must be auto|rows|trees, "
                             f"got {shard_axis!r}")

        cfg = self.config
        if strategy is not None and strategy != cfg.strategy:
            cfg = dataclasses.replace(cfg, strategy=strategy)
            if not cfg.is_resolved:   # staged->fused needs block shapes
                cfg = cfg.resolve(self.ensemble)
        lowered = self._ensure_prepared()
        ens = self.ensemble
        t_align = (cfg.block_t if cfg.strategy == "fused"
                   else STAGED_TREE_ALIGN)

        axis_sizes = dict(mesh.shape)
        row_axes = tuple(a for a in data_axes if a in axis_sizes)
        tree_on_model = (model_axis in axis_sizes
                         and axis_sizes[model_axis] > 1)

        def _n_shards(axes):
            out = 1
            for a in axes:
                out *= int(axis_sizes[a])
            return out

        # mode -> (row axes, tree axes); "trees" on a pure data mesh
        # reuses the data axes as the model split
        modes: dict[str, tuple[tuple, tuple]] = {}
        if tree_on_model:
            modes["hybrid"] = (row_axes, (model_axis,))
            pick = lambda n: "hybrid"                     # noqa: E731
        elif shard_axis == "trees":
            modes["trees"] = ((), row_axes)
            pick = lambda n: "trees"                      # noqa: E731
        elif shard_axis == "rows" or _n_shards(row_axes) <= 1:
            modes["rows"] = (row_axes, ())
            pick = lambda n: "rows"                       # noqa: E731
        else:
            modes["rows"] = (row_axes, ())
            modes["trees"] = ((), row_axes)
            k = _n_shards(row_axes)

            def pick(n):
                return tuning.best_shard_axis(
                    n, ens.n_trees, k, n_outputs=ens.n_outputs,
                    leaf_table_bytes=lowered.leaf_table_bytes())

        entries: dict[tuple, Callable] = {}

        def _entry(mode: str, kind: str) -> Callable:
            cached = entries.get((mode, kind))
            if cached is not None:
                return cached
            r_axes, t_axes = modes[mode]
            n_row = _n_shards(r_axes)
            dp = P(r_axes) if r_axes else P()
            n_tree = _n_shards(t_axes)
            if n_tree > 1:
                stacked = layout_mod.stack_tree_shards(
                    layout_mod.shard_trees(lowered, n_tree,
                                           t_align=t_align))

                def _local(st, data):
                    lw = layout_mod.unstack_tree_shard(st)
                    return jax.lax.psum(
                        self._shard_raw(lw, data, kind, cfg), t_axes)

                model_spec = P(t_axes)
                smapped = jax.shard_map(_local, mesh=mesh,
                                        in_specs=(model_spec, dp),
                                        out_specs=dp, check_vma=False)
                model_arg = stacked
            else:
                def _local(lw, data):
                    return self._shard_raw(lw, data, kind, cfg)

                model_spec = P()
                smapped = jax.shard_map(_local, mesh=mesh,
                                        in_specs=(model_spec, dp),
                                        out_specs=dp, check_vma=False)
                model_arg = lowered
            name = f"sharded_{kind}"

            def _impl(model, data):
                self._note_trace(name)
                with self._lock:
                    self._entry_shapes.add((name,) + tuple(data.shape))
                if _TRACER.enabled:
                    _TRACER.instant(f"compile/{name}", "compile",
                                    entry=name, layout=cfg.layout,
                                    batch=int(data.shape[0]),
                                    shard_mode=mode,
                                    row_shards=n_row,
                                    tree_shards=n_tree)
                n = data.shape[0]
                n_pad = -(-n // n_row) * n_row
                if n_pad != n:
                    data = ops._pad_dim(data, 0, n_pad, kind="data")
                out = ens.base_score[None, :] + smapped(model, data)
                return out[:n] if n_pad != n else out

            # the model is placed on the mesh once and passed as an
            # argument (see _make_entry: never a baked-in constant)
            placed = jax.device_put(model_arg,
                                    NamedSharding(mesh, model_spec))
            entry = functools.partial(jax.jit(_impl), placed)
            entries[(mode, kind)] = entry
            return entry

        n_devices = int(np.prod([int(s) for s in axis_sizes.values()])) \
            if axis_sizes else 1

        def fn(x):
            if isinstance(x, QuantizedPool):
                self._check_pool(x)
                data = x.bins
                if not (isinstance(data, jax.Array)
                        and data.dtype == jnp.uint8):
                    data = jnp.asarray(data, jnp.uint8)
                kind = "pool"
            else:
                data = x
                if not (isinstance(data, jax.Array)
                        and data.dtype == jnp.float32):
                    data = jnp.asarray(data, jnp.float32)
                kind = "float"
            mode = pick(data.shape[0])
            if not _TRACER.enabled:
                return _entry(mode, kind)(data)
            with _TRACER.span(f"sharded/{kind}", "sharded",
                              shard_axis=mode, devices=n_devices,
                              rows=int(data.shape[0]),
                              layout=cfg.layout):
                return _entry(mode, kind)(data)

        self._sharded_cache[key] = fn
        return fn

    # -- introspection -----------------------------------------------------
    def _sharded_trace_impl(self, mesh, kind: str) -> Callable:
        """Un-jitted row-sharded raw impl over `mesh` (real or
        `AbstractMesh`) — the surface the contract checker's
        shard-parity pass abstract-traces.  Rows shard over every mesh
        axis, the lowered model replicates: the jaxpr must not
        all-gather the bins panel back onto one shard."""
        lowered = self._ensure_prepared()
        cfg = self.config
        dp = P(tuple(mesh.axis_names))

        def _local(lw, data):
            return self._shard_raw(lw, data, kind, cfg)

        smapped = jax.shard_map(_local, mesh=mesh, in_specs=(P(), dp),
                                out_specs=dp, check_vma=False)
        base = self.ensemble.base_score[None, :]
        return lambda data: base + smapped(lowered, data)

    def trace_entries(self, batch_sizes: Sequence[int] = (8,),
                      entries: Optional[Sequence[str]] = None, *,
                      mesh=None) -> dict[str, Any]:
        """Abstract traces (ClosedJaxprs) of the plan's entry points —
        the surface the contract checker's transfer/retrace lints walk.

        Traces the *un-jitted* impl methods with `jax.make_jaxpr` over
        ShapeDtypeStructs: nothing is compiled, `stats['traces']` does
        not tick, and repeat walks of the same (entry, batch shape)
        under the same quantization schema are served from a cache
        keyed like `QuantizedPool` scoring — on the borders
        fingerprint — so a re-lowered plan with identical borders
        reuses its traces.  Returns {"<entry>@<batch>": ClosedJaxpr}.

        Pool entries and `quantize` are skipped automatically when the
        ensemble exceeds the uint8 bin budget (they would raise at
        runtime too); pass `entries` to pin an explicit list.

        With `mesh` (a real mesh or a device-free `AbstractMesh`),
        the mesh-distributed entry points join the walk as
        `sharded_raw` / `sharded_raw_pool`, row-sharded over every
        mesh axis — the contract checker's shard-parity pass reads
        these; batch sizes must divide the mesh."""
        p = self._ensure_prepared()
        impls: dict[str, tuple[Callable, Any]] = {
            name: (functools.partial(impl, p), dtype)
            for name, impl, dtype in (
                ("raw", self._raw_impl, jnp.float32),
                ("proba", self._proba_impl, jnp.float32),
                ("classify", self._classify_impl, jnp.float32),
                ("raw_pool", self._pool_raw_impl, jnp.uint8),
                ("proba_pool", self._pool_proba_impl, jnp.uint8),
                ("classify_pool", self._pool_classify_impl, jnp.uint8),
                ("quantize", self._quantize_impl, jnp.float32))}
        mesh_key = None
        if mesh is not None:
            mesh_key = tuple(sorted(dict(mesh.shape).items()))
            impls["sharded_raw"] = (
                self._sharded_trace_impl(mesh, "float"), jnp.float32)
            impls["sharded_raw_pool"] = (
                self._sharded_trace_impl(mesh, "pool"), jnp.uint8)
        if entries is None:
            names = list(impls)
            if self.ensemble.borders.shape[0] > MAX_BINS - 1:
                names = [n for n in names
                         if not n.endswith("_pool") and n != "quantize"]
        else:
            unknown = sorted(set(entries) - set(impls))
            if unknown:
                raise KeyError(f"unknown plan entries {unknown}; "
                               f"known: {sorted(impls)}")
            names = list(entries)
        fingerprint = self.schema_fingerprint
        out: dict[str, Any] = {}
        for name in names:
            impl, dtype = impls[name]
            for n in batch_sizes:
                aval = jax.ShapeDtypeStruct(
                    (int(n), self.ensemble.n_features), dtype)
                key = (name, aval.shape, str(aval.dtype), fingerprint,
                       mesh_key if name.startswith("sharded") else None)
                with self._lock:
                    closed = self._abstract_traces.get(key)
                if closed is None:
                    # trace outside the lock (tracing is slow and
                    # reentrant-safe); first writer wins
                    traced = jax.make_jaxpr(impl)(aval)
                    with self._lock:
                        closed = self._abstract_traces.setdefault(
                            key, traced)
                        if closed is traced:
                            self._abstract_trace_misses += 1
                out[f"{name}@{int(n)}"] = closed
        return out

    @property
    def stats(self) -> dict[str, Any]:
        """Plan-cache telemetry: XLA traces per entry point, distinct
        (entry, batch shape) cache keys seen, the physical layout the
        plan lowered to plus the one-time lowering cost (pad ops and
        wall-clock seconds) — what serving dashboards need to see what
        shipped."""
        with self._lock:
            return {
                "traces": dict(self._traces),
                "total_traces": sum(self._traces.values()),
                "cache_entries": len(self._entry_shapes),
                "entry_shapes": sorted(self._entry_shapes),
                "layout": self.config.layout,
                "lower_time_s": self._lower_time_s,
                "build_model_pads": self._build_model_pads,
                "abstract_traces": len(self._abstract_traces),
                "abstract_trace_misses": self._abstract_trace_misses,
            }

    def describe(self) -> dict[str, Any]:
        out = {**self.ensemble.describe(),
               "strategy": self.config.strategy,
               "backend": self.config.backend,
               "layout": self.config.layout,
               "tree_block": self.config.tree_block,
               "block_n": self.config.block_n,
               "block_t": self.config.block_t,
               "schema_fingerprint": self.schema_fingerprint}
        if self._lowered is not None:
            out["lowered"] = self._lowered.describe()
        return out

    def __repr__(self) -> str:
        c = self.config
        return (f"<Predictor {c.strategy}/{c.backend}/{c.layout} "
                f"trees={self.ensemble.n_trees} "
                f"depth={self.ensemble.depth} C={self.ensemble.n_outputs}>")


# --------------------------------------------------------------------------
# CatBoost JSON ingestion
# --------------------------------------------------------------------------
def load_catboost_json(path: str | pathlib.Path) -> ObliviousEnsemble:
    """Parse a CatBoost oblivious-tree JSON export into an ensemble.

    Reads the subset of `save_model(..., format="json")` the paper's
    workloads need: `features_info.float_features[*].borders`,
    `oblivious_trees[*].splits` (float splits only: feature index +
    border value) and flat `leaf_values`, plus `scale_and_bias`.

    Conventions mapped onto this repo's model:
      * split j of a tree contributes bit j of the leaf index
        (CatBoost lists splits bottom-up, matching `ref.leaf_index`)
      * CatBoost's `x > border` with border at sorted index k becomes
        `bins >= k + 1` in quantized space
      * trees shallower than the deepest are padded with always-left
        splits (`PAD_SPLIT_BIN`), their leaf values at indices < 2^d
      * `leaf_values` is length 2^d * dim, leaf-major
    """
    obj = json.loads(pathlib.Path(path).read_text())
    floats = obj.get("features_info", {}).get("float_features", [])
    if not floats:
        raise ValueError(f"{path}: no features_info.float_features — not a "
                         "CatBoost JSON model export?")
    trees = obj.get("oblivious_trees", [])
    if not trees:
        raise ValueError(f"{path}: no oblivious_trees (only oblivious-tree "
                         "models are supported)")
    for t, tree in enumerate(trees):
        if "splits" not in tree or "leaf_values" not in tree:
            raise ValueError(f"{path}: tree {t} is missing "
                             "splits/leaf_values — truncated export?")

    def flat_index(feat, i):
        return int(feat.get("flat_feature_index",
                            feat.get("feature_index", i)))

    n_features = 1 + max(flat_index(f, i) for i, f in enumerate(floats))
    per_feature: list[list[float]] = [[] for _ in range(n_features)]
    for i, f in enumerate(floats):
        per_feature[flat_index(f, i)] = [float(v)
                                         for v in (f.get("borders") or [])]

    depth = max(len(t["splits"]) for t in trees)
    if depth < 1:
        raise ValueError(f"{path}: model has splitless trees only")
    d0 = len(trees[0]["splits"])
    n_leaf0 = len(trees[0]["leaf_values"])
    if n_leaf0 % (1 << d0):
        raise ValueError(f"{path}: tree 0 has {n_leaf0} leaf values, not a "
                         f"multiple of 2^depth={1 << d0}")
    n_outputs = n_leaf0 // (1 << d0)

    n_trees = len(trees)
    sf = np.zeros((n_trees, depth), np.int32)
    sb = np.full((n_trees, depth), PAD_SPLIT_BIN, np.int32)
    lv = np.zeros((n_trees, 1 << depth, n_outputs), np.float32)
    for t, tree in enumerate(trees):
        splits = tree["splits"]
        d = len(splits)
        vals = np.asarray(tree["leaf_values"], np.float32)
        if vals.size != (1 << d) * n_outputs:
            raise ValueError(
                f"{path}: tree {t} has {vals.size} leaf values; expected "
                f"2^{d} * {n_outputs} (inconsistent approx dimension)")
        for j, s in enumerate(splits):
            stype = s.get("split_type", "FloatFeature")
            if stype != "FloatFeature":
                raise ValueError(f"{path}: tree {t} split {j} has type "
                                 f"{stype!r}; only FloatFeature is "
                                 "supported")
            fi = int(s.get("float_feature_index",
                           s.get("feature_index", -1)))
            if not 0 <= fi < n_features:
                raise ValueError(f"{path}: tree {t} split {j} references "
                                 f"feature {fi} outside [0, {n_features})")
            if "border" not in s:
                raise ValueError(f"{path}: tree {t} split {j} has no "
                                 "border value")
            border = float(s["border"])
            feature_borders = per_feature[fi]
            if not feature_borders:
                raise ValueError(f"{path}: tree {t} splits on feature {fi} "
                                 "which has no borders")
            k = int(np.argmin(np.abs(np.asarray(feature_borders) - border)))
            if not np.isclose(feature_borders[k], border,
                              rtol=1e-6, atol=1e-9):
                raise ValueError(
                    f"{path}: tree {t} split {j} border {border} not found "
                    f"among feature {fi}'s borders")
            sf[t, j] = fi
            sb[t, j] = k + 1
        lv[t, :1 << d, :] = vals.reshape(1 << d, n_outputs)

    scale, bias = 1.0, np.zeros((n_outputs,), np.float32)
    snb = obj.get("scale_and_bias")
    if snb:
        scale = float(snb[0])
        raw_bias = snb[1]
        if isinstance(raw_bias, (int, float)):
            raw_bias = [raw_bias]
        b = np.asarray(raw_bias, np.float32)
        if b.size == 1:
            bias = np.full((n_outputs,), float(b[0]), np.float32)
        elif b.size == n_outputs:
            bias = b
        else:
            raise ValueError(f"{path}: scale_and_bias bias has {b.size} "
                             f"entries for {n_outputs} outputs")

    n_borders = np.asarray([len(b) for b in per_feature], np.int32)
    max_b = max(1, int(n_borders.max()))
    borders = np.full((max_b, n_features), np.inf, np.float32)
    for fi, vals in enumerate(per_feature):
        borders[:len(vals), fi] = vals

    return ObliviousEnsemble(
        split_features=jnp.asarray(sf),
        split_bins=jnp.asarray(sb),
        leaf_values=jnp.asarray(lv * np.float32(scale)),
        borders=jnp.asarray(borders),
        n_borders=jnp.asarray(n_borders),
        base_score=jnp.asarray(bias),
    )
