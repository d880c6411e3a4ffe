"""Serving engines — the paper's use case is batched prediction (its
Table 5 speedups exist only when samples arrive in batches; single-sample
inference gains nothing from vectorization, as the paper notes in its
limitations).  Request aggregation and bucket padding live in
`repro.serving.batching`; per-model counters in `repro.serving.metrics`.

* GBDTServer: batched oblivious-tree scoring with the vectorized predict
  pipeline — strategy (staged/fused/auto), backend, tree blocking and
  Pallas block shapes are all configurable; incoming batches are padded
  to size buckets so retraces stay bounded; optional device-mesh
  sharding.
* ModelRegistry: several named ensembles served from one process, each
  with its own server config and metrics.
* EmbeddingGBDTPipeline: the paper's image-embeddings workload as a
  production pattern — backbone embeddings -> KNN features -> GBDT head
  (any of the 10 assigned LM backbones can produce the embeddings).
* LMServer: prefill/decode serving for the assigned architectures.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knn
from repro.core.predictor import PredictConfig, Predictor, proba_from_raw
from repro.core.quantize import QuantizedPool
from repro.core.trees import ObliviousEnsemble
from repro.obs.trace import get_tracer
from repro.serving.batching import (Batcher, BucketedBatcher,  # noqa: F401
                                    Request, bucket_for, chunks)
from repro.serving.metrics import ServerMetrics

_TRACER = get_tracer()


class GBDTServer:
    """Batched GBDT scoring service over a compiled prediction plan.

    At construction the server builds one `Predictor` — `auto` choices
    resolved, model arrays padded to block multiples, jitted entry
    points cached — and every batch is scored through that plan; nothing
    model-side is re-prepared per request.  Every batch the batcher
    flushes is padded up to one of ``batcher.buckets`` before it reaches
    the plan, so the number of XLA traces is bounded by the bucket count
    — the `metrics.recompiles` counter asserts this in tests.

    Pass a `PredictConfig` as ``config``; the loose ``strategy`` /
    ``backend`` / ``tree_block`` / ``block_n`` / ``block_t`` kwargs are
    the deprecated equivalents kept for existing callers.

    Quantized-first path: ``quantize(xs)`` binarizes a batch once into
    a `QuantizedPool`; ``predict_pool(pool)`` scores it with zero
    binarize work.  Servers whose models share a feature schema share
    pools (see `ModelRegistry.predict_multi`).
    """

    def __init__(self, ensemble: ObliviousEnsemble, *,
                 config: Optional[PredictConfig] = None,
                 strategy: str = "auto", backend: str = "auto",
                 tree_block: int = 0,
                 block_n: Optional[int] = None,
                 block_t: Optional[int] = None,
                 mesh=None, max_batch: int = 256,
                 max_wait_ms: float = 2.0,
                 buckets: Optional[Sequence[int]] = None,
                 min_bucket: int = 16,
                 name: str = "gbdt",
                 deadline_ms: Optional[float] = None):
        legacy_kw = {"strategy": strategy, "backend": backend,
                     "tree_block": tree_block, "block_n": block_n,
                     "block_t": block_t}
        if config is None:
            config = PredictConfig(**legacy_kw)
        else:
            defaults = PredictConfig()
            clashing = [k for k, v in legacy_kw.items()
                        if v != getattr(defaults, k)]
            if clashing:
                raise TypeError(
                    "pass either config= or the deprecated predict "
                    f"kwargs, not both: {sorted(clashing)}")
        self.ensemble = ensemble
        self.mesh = mesh
        # deadline_ms arms the SLO accounting: every scored batch is
        # classified hit/miss against it and predict() timeouts count
        # as sheds (see serving.metrics.ServerMetrics / docs)
        self.metrics = ServerMetrics(name, deadline_ms=deadline_ms)
        # One plan per server: the tuner sizes fused blocks for the
        # largest bucket; the plan's trace counter feeds `recompiles`.
        # Mesh servers score through `Predictor.sharded`, which ships
        # this same lowered model to every shard — one lowering serves
        # both the local and the mesh path.
        self.predictor = Predictor.build(ensemble, config,
                                         expected_batch=max_batch,
                                         on_trace=self.metrics.note_trace)
        # the sharded path replicates the plan's own lowered model, so
        # mesh and local servers report the same resolved layout
        self.metrics.layout = self.predictor.config.layout
        # sharded predict stays on the paper-faithful staged pipeline
        # unless the caller explicitly asked for fused (fused-inside-
        # shard_map is not a serving-supported combination for `auto`)
        self._sharded = None
        if mesh is not None:
            sharded_strategy = ("staged" if config.strategy == "auto"
                                else config.strategy)
            self._sharded = self.predictor.sharded(
                mesh, strategy=sharded_strategy)

        def serve(xs: np.ndarray) -> np.ndarray:
            # lands on the batcher thread's track in exported traces
            with _TRACER.span(
                    "serve/batch", "serve", model=name, rows=int(len(xs)),
                    queue_wait_s=self.batcher.dispatch_queue_wait_s()):
                if self._sharded is not None:
                    raw = self._sharded(jnp.asarray(xs, jnp.float32))
                    return np.asarray(proba_from_raw(raw,
                                                     ensemble.n_outputs))
                return np.asarray(self.predictor.proba(xs))

        self.batcher = BucketedBatcher(serve, max_batch=max_batch,
                                       max_wait_ms=max_wait_ms,
                                       buckets=buckets,
                                       min_bucket=min_bucket,
                                       metrics=self.metrics)
        self._serve_padded = serve

    @property
    def config(self) -> PredictConfig:
        """The resolved plan configuration this server scores with."""
        return self.predictor.config

    @property
    def buckets(self) -> tuple[int, ...]:
        return self.batcher.buckets

    def predict(self, x: np.ndarray, timeout: float = 30.0) -> np.ndarray:
        """Single request through the deadline batcher (blocking).

        A timeout is accounted as a shed request (`metrics.shed_rate`)
        and surfaces as `TimeoutError` — the caller never got a score,
        so the latency reservoir is untouched."""
        fut = self.batcher.submit(0, np.asarray(x, np.float32))
        try:
            return fut.get(timeout=timeout)
        except queue.Empty:
            self.metrics.note_shed()
            raise TimeoutError(
                f"predict timed out after {timeout}s (counted as shed; "
                "batcher queue may be saturated)") from None

    def predict_batch(self, xs: np.ndarray) -> np.ndarray:
        """Synchronous bulk scoring through the same bucketed jit path.

        Oversized inputs are chunked at the largest bucket, so this
        shares the compile cache with the online path no matter the
        caller's array size.
        """
        xs = np.asarray(xs, np.float32)
        if len(xs) == 0:
            return self._empty_proba()
        top = self.buckets[-1]
        out = [self.batcher._run_batch(xs[start:stop])
               for start, stop in chunks(len(xs), top)]
        return np.concatenate(out, axis=0)

    # -- quantized-pool path (the shared-quantizer serving win) ------------
    @property
    def schema_fingerprint(self) -> str:
        """Which `QuantizedPool`s this server may score; servers sharing
        it share pools (ModelRegistry.predict_multi quantizes once per
        distinct fingerprint)."""
        return self.predictor.schema_fingerprint

    def quantize(self, xs) -> QuantizedPool:
        """Binarize a batch once for reuse across predicts/servers."""
        return self.predictor.quantize(np.asarray(xs, np.float32))

    def predict_pool(self, pool: QuantizedPool) -> np.ndarray:
        """Synchronous bulk scoring of a pre-quantized pool: binarize
        never runs.  Chunks at the largest bucket and pads each chunk
        up to a bucket, so retraces stay bounded by the bucket count
        exactly like the float path; each chunk is recorded in
        `metrics` the same way the batcher records float batches.

        Mesh servers score pools through the sharded pool entry: the
        pre-quantized bins panel is row-sharded across the mesh and the
        plan's lowered model is replicated, so binarize never runs
        there either."""
        if len(pool) == 0:
            return self._empty_proba()
        top = self.buckets[-1]
        out = []
        for start, stop in chunks(len(pool), top):
            chunk = pool.slice_rows(start, stop)
            bucket = bucket_for(len(chunk), self.buckets)
            t0 = time.perf_counter()
            padded = chunk.pad_rows(bucket)
            if self._sharded is not None:
                raw = self._sharded(padded)
                ys = np.asarray(proba_from_raw(raw,
                                               self.ensemble.n_outputs))
            else:
                ys = np.asarray(self.predictor.proba(padded))
            self.metrics.note_batch(len(chunk), bucket,
                                    time.perf_counter() - t0)
            out.append(ys[:len(chunk)])
        return np.concatenate(out, axis=0)

    def score_source(self, source, sinks=None, *,
                     config=None, resume_from: int = 0, **score_kw):
        """Bulk-apply this server's compiled plan to a whole dataset —
        the bridge from online serving to offline jobs (nightly
        rescore of the same deployed model, same plan, same compile
        caches).  `source` is a `repro.scoring.RowSource`, `sinks` a
        `ScoreSink` (or None for an in-memory array); returns the
        `ScoreResult` whose metrics snapshot reports `rows_per_s` in
        the same unit as this server's `metrics.snapshot()`.

        Defaults to ``output="proba"`` — what this server's online
        predicts return — unless the config says otherwise.

        Mesh servers run the bulk job through the same mesh: the
        scorer's chunk loop stays intact, each chunk scored through the
        sharded entry (`BulkScorer(mesh=...)`).
        """
        from repro.scoring.scorer import BulkScorer, ScoreConfig

        if config is None:
            score_kw.setdefault("output", "proba")
            config = ScoreConfig(**score_kw)
        elif score_kw:
            raise TypeError("pass either a ScoreConfig or config kwargs, "
                            f"not both: {sorted(score_kw)}")
        return BulkScorer(self.predictor, config, mesh=self.mesh).score(
            source, sinks, resume_from=resume_from)

    def _empty_proba(self) -> np.ndarray:
        width = 2 if self.ensemble.n_outputs == 1 else \
            self.ensemble.n_outputs
        return np.zeros((0, width), np.float32)

    def close(self):
        self.batcher.close()


class ReplicaGroup:
    """R `GBDTServer`s over disjoint submeshes, behind one model name.

    Requests round-robin across replicas; each replica runs the full
    sharded predict pipeline on its own devices, so any single request
    sees exactly the single-replica parity contract.  The group
    presents the `GBDTServer` scoring surface (`predict`,
    `predict_batch`, `predict_pool`, `quantize`, `schema_fingerprint`,
    `score_source`) so `ModelRegistry` routes to it transparently, and
    `metrics_snapshot()` is the fleet view (`ServerMetrics.merge`).
    """

    def __init__(self, name: str, servers: Sequence["GBDTServer"]):
        if not servers:
            raise ValueError("ReplicaGroup needs at least one server")
        self.name = name
        self.servers = list(servers)
        self._rr = 0
        self._rr_lock = threading.Lock()

    def _next(self) -> "GBDTServer":
        with self._rr_lock:
            server = self.servers[self._rr % len(self.servers)]
            self._rr += 1
        return server

    # -- GBDTServer surface -------------------------------------------------
    @property
    def ensemble(self):
        return self.servers[0].ensemble

    @property
    def mesh(self):
        return self.servers[0].mesh

    @property
    def schema_fingerprint(self) -> str:
        return self.servers[0].schema_fingerprint

    def quantize(self, xs) -> QuantizedPool:
        # borders are identical across replicas (same ensemble), so a
        # pool quantized once is scoreable on any of them
        return self.servers[0].quantize(xs)

    def predict(self, x, timeout: float = 30.0):
        return self._next().predict(x, timeout=timeout)

    def predict_batch(self, xs):
        return self._next().predict_batch(xs)

    def predict_pool(self, pool):
        return self._next().predict_pool(pool)

    def score_source(self, source, sinks=None, **kw):
        return self._next().score_source(source, sinks, **kw)

    def metrics_snapshot(self) -> dict[str, Any]:
        merged = ServerMetrics.merge([s.metrics for s in self.servers])
        merged["model"] = self.name
        return merged

    def close(self):
        for s in self.servers:
            s.close()


class ModelRegistry:
    """Several named GBDT ensembles served from one process.

    Each model gets its own `GBDTServer` (own batcher thread, own
    compiled `Predictor` plan, own metrics); registry-level `metrics()`
    aggregates the per-model snapshots for export.

    Replica groups: ``register(name, ens, replicas=R, mesh=mesh)``
    splits the mesh into R disjoint submeshes
    (`repro.distributed.gbdt.replica_submeshes`) and serves the model
    from one `GBDTServer` per submesh behind a round-robin
    `ReplicaGroup` — K models x R replicas share one physical mesh,
    and `predict_multi` still quantizes once per feature schema across
    all of them.

    Cache invalidation: a `Predictor` plan is immutable — it holds the
    padded model arrays and jit caches for the ensemble it was built
    from.  Swapping an ensemble under a name (``register(...,
    replace=True)``) therefore tears down the whole old server, plan
    included, and builds a fresh one; handing a new ensemble to an
    existing plan is not supported.
    """

    def __init__(self, **default_server_kw: Any):
        self._default_kw = default_server_kw
        self._servers: dict[str, GBDTServer | ReplicaGroup] = {}

    def register(self, name: str, ensemble: ObliviousEnsemble,
                 replace: bool = False, *, replicas: int = 1,
                 **server_kw: Any) -> "GBDTServer | ReplicaGroup":
        if name in self._servers:
            if not replace:
                raise KeyError(f"model {name!r} already registered "
                               "(pass replace=True to swap it)")
            # Swap = full teardown: the old server's Predictor plan
            # (padded arrays + jit caches) is bound to the old ensemble
            # and must not survive the swap.
            self._servers.pop(name).close()
        kw = {**self._default_kw, **server_kw, "name": name}
        if replicas > 1:
            from repro.distributed.gbdt import replica_submeshes

            mesh = kw.pop("mesh", None)
            if mesh is None:
                raise ValueError(
                    "replicas > 1 needs a mesh to split (pass mesh= "
                    "to register() or to the registry defaults)")
            subs = replica_submeshes(mesh, replicas)
            servers = [GBDTServer(ensemble,
                                  **{**kw, "mesh": sub,
                                     "name": f"{name}/r{i}"})
                       for i, sub in enumerate(subs)]
            group = ReplicaGroup(name, servers)
            self._servers[name] = group
            return group
        server = GBDTServer(ensemble, **kw)
        self._servers[name] = server
        return server

    def load(self, name: str, path, **server_kw: Any) -> GBDTServer:
        return self.register(name, ObliviousEnsemble.load(path),
                             **server_kw)

    def get(self, name: str) -> "GBDTServer | ReplicaGroup":
        if name not in self._servers:
            raise KeyError(f"unknown model {name!r}; registered: "
                           f"{sorted(self._servers)}")
        return self._servers[name]

    def names(self) -> list[str]:
        return sorted(self._servers)

    def predict(self, name: str, x: np.ndarray,
                timeout: float = 30.0) -> np.ndarray:
        return self.get(name).predict(x, timeout=timeout)

    def predict_batch(self, name: str, xs: np.ndarray) -> np.ndarray:
        return self.get(name).predict_batch(xs)

    def predict_multi(self, xs: np.ndarray,
                      names: Optional[Sequence[str]] = None
                      ) -> dict[str, np.ndarray]:
        """Score one batch through several models, quantizing once per
        feature schema.

        Servers whose ensembles share borders (same
        `schema_fingerprint`) get the batch binarized a single time —
        the `QuantizedPool` is then scored through each plan's
        pool path, which skips binarize entirely.  This is the
        quantize-once/score-many serving pattern the quantized-first
        API exists for (multi-model registries routinely serve model
        variants trained on one quantized dataset).  Mesh servers and
        replica groups take the same path: the sharded pool entry
        row-shards the already-quantized bins panel, so one quantize
        still covers every model — and every replica — that shares the
        schema.
        """
        if names is None:
            names = self.names()
        targets = [(n, self.get(n)) for n in names]
        pools: dict[str, QuantizedPool] = {}
        out: dict[str, np.ndarray] = {}
        for name, server in targets:
            fp = server.schema_fingerprint
            if fp not in pools:
                pools[fp] = server.quantize(xs)
            out[name] = server.predict_pool(pools[fp])
        return out

    def metrics(self) -> dict[str, dict[str, Any]]:
        return {n: (s.metrics_snapshot() if isinstance(s, ReplicaGroup)
                    else s.metrics.snapshot())
                for n, s in self._servers.items()}

    def unregister(self, name: str) -> None:
        self._servers.pop(name).close()

    def close(self) -> None:
        for s in self._servers.values():
            s.close()
        self._servers.clear()


class EmbeddingGBDTPipeline:
    """backbone embeddings -> KNN features -> GBDT (paper's
    image-embeddings workload, generalized to any backbone)."""

    def __init__(self, featurizer: knn.KNNFeaturizer,
                 ensemble: ObliviousEnsemble,
                 embed_fn: Optional[Callable] = None,
                 config: Optional[PredictConfig] = None):
        self.featurizer = featurizer
        self.ensemble = ensemble
        self.embed_fn = embed_fn          # raw input -> embedding (stub ok)
        self.predictor = Predictor.build(
            ensemble, config or PredictConfig(backend="ref"))

    def predict(self, inputs: np.ndarray) -> np.ndarray:
        emb = (self.embed_fn(inputs) if self.embed_fn is not None
               else jnp.asarray(inputs))
        feats = self.featurizer.transform(emb)
        x = jnp.concatenate([emb, feats], axis=1)
        return np.asarray(self.predictor.classify(x))


class LMServer:
    """Minimal continuous-batching LM server: prefill then step decode."""

    def __init__(self, cfg, params, *, max_seq: int = 512):
        import functools
        from repro.models import transformer as tf
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self._prefill = jax.jit(functools.partial(tf.prefill, cfg,
                                                  max_seq=max_seq))
        self._decode = jax.jit(functools.partial(tf.decode_step, cfg))

    def generate(self, tokens: np.ndarray, n_new: int,
                 frontend_embeds: Optional[np.ndarray] = None
                 ) -> np.ndarray:
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(frontend_embeds)
        logits, cache = self._prefill(self.params, batch)
        out = []
        tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
        for _ in range(n_new):
            out.append(np.asarray(tok))
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
        return np.concatenate(out, axis=1)
