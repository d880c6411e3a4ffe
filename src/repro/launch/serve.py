"""Serving launcher: GBDT batched scoring or LM generation.

  python -m repro.launch.serve --mode gbdt     # batched GBDT requests
  python -m repro.launch.serve --mode lm --arch glm4-9b
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def serve_gbdt(args):
    import json

    from repro.core import boosting, losses
    from repro.core.boosting import BoostingParams
    from repro.core.predictor import PredictConfig
    from repro.data import synthetic
    from repro.launch.obs_cli import finish_obs, start_tracing
    from repro.serving.engine import ModelRegistry

    start_tracing(args)
    ds = synthetic.load(args.dataset, scale=args.scale)
    loss = losses.make_loss(ds.loss, n_classes=max(ds.n_classes, 2),
                            group_index=ds.group_index_train)
    ens, _ = boosting.fit(ds.x_train, ds.y_train, loss=loss,
                          params=BoostingParams(
                              n_trees=args.trees, depth=ds.params.depth,
                              learning_rate=0.1))
    # One PredictConfig for the registry; each server builds its
    # compiled plan from it at registration (auto resolved there).
    config = PredictConfig(strategy=args.strategy, backend=args.backend,
                           layout=args.layout,
                           tree_block=args.tree_block)
    registry = ModelRegistry(max_batch=args.batch, config=config,
                             min_bucket=args.min_bucket,
                             deadline_ms=args.deadline_ms or None)
    server = registry.register(args.dataset, ens)
    # the multi-model shared-quantizer demo: K tree-slice variants of
    # the model share its quantization schema, so predict_multi
    # binarizes each batch once for all of them (at most one variant
    # per tree)
    n_variants = min(args.multi, ens.n_trees)
    per = max(1, ens.n_trees // n_variants)
    for i in range(1, n_variants):
        registry.register(f"{args.dataset}-v{i}",
                          ens.slice_trees(i * per,
                                          min((i + 1) * per, ens.n_trees)))
    stats = server.predictor.stats
    print(f"[serve:gbdt] model={args.dataset} plan={server.config} "
          f"buckets={server.buckets} "
          f"schema={server.schema_fingerprint}")
    print(f"[serve:gbdt] layout={stats['layout']} "
          f"lowered in {stats['lower_time_s'] * 1e3:.1f}ms "
          f"({stats['build_model_pads']} model pads)")
    t0 = time.perf_counter()
    n = 200
    for i in range(n):
        registry.predict(args.dataset, ds.x_test[i % len(ds.x_test)])
    dt = time.perf_counter() - t0
    print(f"[serve:gbdt] {n} sequential requests in {dt:.2f}s; "
          f"batches={len(server.batcher.batch_sizes)}")
    if args.multi > 1:
        xs = ds.x_test[:min(len(ds.x_test), args.batch)]
        t0 = time.perf_counter()
        out = registry.predict_multi(xs)
        dt = time.perf_counter() - t0
        print(f"[serve:gbdt] predict_multi({len(xs)} rows x "
              f"{len(out)} models, quantize-once) in {dt * 1e3:.1f}ms")
    print(f"[serve:gbdt] metrics: "
          f"{json.dumps(registry.metrics()[args.dataset], default=float)}")
    finish_obs(args, {f"serving/{n}": (
        s.metrics if hasattr(s, "metrics") else s.metrics_snapshot)
        for n, s in ((n, registry.get(n)) for n in registry.names())})
    registry.close()


def serve_lm(args):
    import jax
    from repro import configs
    from repro.models import transformer as tf
    from repro.serving.engine import LMServer

    cfg = configs.get(args.arch, smoke=True)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), max_positions=256)
    server = LMServer(cfg, params, max_seq=128 + (
        cfg.frontend_seq if cfg.family == "vlm" else 0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    fe = (np.zeros((2, cfg.frontend_seq, cfg.d_model), np.float32)
          if cfg.frontend else None)
    t0 = time.perf_counter()
    out = server.generate(toks, n_new=16, frontend_embeds=fe)
    dt = time.perf_counter() - t0
    print(f"[serve:lm] {cfg.name} generated {out.shape} tokens "
          f"in {dt:.2f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["gbdt", "lm"], default="gbdt")
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--dataset", default="santander")
    ap.add_argument("--scale", type=float, default=0.004)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--strategy", choices=["auto", "staged", "fused"],
                    default="auto")
    ap.add_argument("--backend", choices=["auto", "pallas", "ref"],
                    default="auto")
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "soa", "depth_major", "depth_grouped",
                             "bitpacked"],
                    help="physical model layout the plan lowers to "
                         "(auto = picked from the ensemble's depth "
                         "histogram by kernels.tuning.best_layout)")
    ap.add_argument("--tree-block", type=int, default=0,
                    help="staged-path tree block (0 = whole ensemble)")
    ap.add_argument("--min-bucket", type=int, default=16,
                    help="smallest batch-size padding bucket")
    ap.add_argument("--multi", type=int, default=1,
                    help="register K schema-sharing model variants and "
                         "demo the quantize-once predict_multi path")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="arm per-batch deadline-SLO accounting at this "
                         "latency (0 = off); attainment/shed/p99-under-"
                         "deadline land in the metrics snapshot")
    ap.add_argument("--show-kernels", action="store_true",
                    help="print the kernel registry table and exit")
    from repro.launch.obs_cli import add_obs_flags
    add_obs_flags(ap)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.configure()
    if args.show_kernels:
        from repro.core import layout as layout_mod
        from repro.kernels import registry as kernel_registry
        from repro.kernels import tuning
        print(kernel_registry.format_table())
        if kernel_registry.load_verified():
            print("\nverified: contract-checker verdict per impl "
                  "(results/analysis/contract-report.json; refresh "
                  "with `python -m repro.launch.analyze`)")
        else:
            print("\nverified: no contract report found — run "
                  "`python -m repro.launch.analyze` to populate")
        print()
        print(layout_mod.format_layout_table())
        # the layout this process would resolve for the requested flag
        # (auto shown against two canned depth histograms, since no
        # model is trained under --show-kernels)
        if args.layout != "auto":
            print(f"\nresolved layout: {args.layout} (pinned by --layout)")
        else:
            import numpy as np
            backend = (args.backend if args.backend != "auto"
                       else kernel_registry.default_backend())
            uniform = tuning.best_layout(np.full(100, 6), 1, 54,
                                         backend=backend)
            mixed = tuning.best_layout(np.tile([2, 3, 4, 6], 25), 1, 54,
                                       backend=backend)
            # a mixed-depth model too large for the f32 one-hot working
            # set (> VMEM budget) routes to the integer bitpacked layout
            huge = tuning.best_layout(np.tile([4, 6, 8, 10], 50_000), 1,
                                      512, backend=backend)
            print(f"\nresolved layout (auto, {backend} backend): "
                  f"uniform-depth -> {uniform}, mixed-depth -> {mixed}, "
                  f"huge-mixed -> {huge}")
        return
    (serve_gbdt if args.mode == "gbdt" else serve_lm)(args)


if __name__ == "__main__":
    main()
