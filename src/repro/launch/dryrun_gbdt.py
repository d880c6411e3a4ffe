import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Production-mesh dry-run for the paper's OWN model: a CatBoost-scale
GBDT ensemble served at batch on 256/512 chips.

Cells:
  gbdt-predict-1m   1,048,576 x 54 rows, 10k trees depth 8, 7 classes
                    (Covertype-scale model at the paper's 10000-iteration
                    setting) — samples shard over (pod, data), trees over
                    model with a psum combine (core/predict.predict_sharded)
  gbdt-train-iter   one boosting iteration (histograms + split + leaf
                    values) on 1M x 54 sharded rows

  python -m repro.launch.dryrun_gbdt [--multi-pod]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.hlo_analysis import (HBM_BW, LINK_BW, PEAK_FLOPS,
                                       collective_bytes_from_hlo)
from repro.launch.mesh import make_production_mesh

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

N_ROWS, N_FEATS = 1_048_576, 54
N_TREES, DEPTH, N_CLASSES, N_BINS = 10_000, 8, 7, 255


def _ensemble_abs():
    sds = jax.ShapeDtypeStruct
    return dict(
        split_features=sds((N_TREES, DEPTH), np.int32),
        split_bins=sds((N_TREES, DEPTH), np.int32),
        leaf_values=sds((N_TREES, 2 ** DEPTH, N_CLASSES), np.float32),
        borders=sds((N_BINS, N_FEATS), np.float32),
        x=sds((N_ROWS, N_FEATS), np.float32),
    )


def lower_predict(mesh):
    from repro.kernels import ref

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def predict(sf, sb, lv, borders, x):
        def local(sf, sb, lv, borders, xs):
            bins = ref.binarize(xs, borders)
            idx = ref.leaf_index(bins, sf, sb)
            part = ref.leaf_gather(idx, lv)
            return jax.lax.psum(part, "model")

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(P("model"), P("model"), P("model"),
                                     P(), P(dp)),
                           out_specs=P(dp))
        return fn(sf, sb, lv, borders, x)

    a = _ensemble_abs()
    shardings = (NamedSharding(mesh, P("model")),) * 3 + (
        NamedSharding(mesh, P()), NamedSharding(mesh, P(dp)))
    return jax.jit(predict, in_shardings=shardings).lower(
        a["split_features"], a["split_bins"], a["leaf_values"],
        a["borders"], a["x"])


def lower_train_iter(mesh):
    """One boosting iteration: grad/hess + histogram splits + leaf values,
    data sharded over (pod, data)."""
    from repro.core import boosting, losses

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    loss = losses.MultiClass(n_classes=N_CLASSES)

    def one_iter(bins, y, raw):
        g, h = loss.grad_hess(raw, y)
        sf, sb, sum_g, sum_h, leaf = boosting._build_tree(
            bins, g, h, jnp.full((N_FEATS,), N_BINS - 1, jnp.int32),
            jax.random.PRNGKey(0), depth=DEPTH, max_bins=64,
            l2=3.0, rsm=1.0)
        w = -0.5 * sum_g / (sum_h + 3.0)
        return sf, sb, w, raw + w[leaf]

    sds = jax.ShapeDtypeStruct
    shardings = (NamedSharding(mesh, P(dp, None)),
                 NamedSharding(mesh, P(dp)),
                 NamedSharding(mesh, P(dp, None)))
    return jax.jit(one_iter, in_shardings=shardings).lower(
        sds((N_ROWS, N_FEATS), np.int32), sds((N_ROWS,), np.int32),
        sds((N_ROWS, N_CLASSES), np.float32))


def run_cell(name: str, multi_pod: bool, force: bool = False) -> dict:
    pod = "multipod" if multi_pod else "singlepod"
    path = RESULTS / f"gbdt-{name}__paper__{pod}.json"
    if path.exists() and not force:
        return json.loads(path.read_text())
    path.parent.mkdir(parents=True, exist_ok=True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    try:
        t0 = time.time()
        with mesh:
            lowered = (lower_predict(mesh) if name == "predict-1m"
                       else lower_train_iter(mesh))
            compiled = lowered.compile()
        compile_s = time.time() - t0
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        try:
            mem = compiled.memory_analysis()
            mem_info = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
            }
        except Exception:
            mem_info = {}
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        # model flops: binarize compares + index + gather madds
        if name == "predict-1m":
            model_flops = N_ROWS * (N_FEATS * N_BINS
                                    + N_TREES * DEPTH + N_TREES * N_CLASSES)
        else:
            model_flops = N_ROWS * N_FEATS * DEPTH * 2 * N_CLASSES
        terms = {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll["total"] / (n_dev * LINK_BW),
        }
        res = {
            "arch": f"gbdt-{name}", "shape": "paper", "multi_pod": multi_pod,
            "n_devices": n_dev, "compile_seconds": round(compile_s, 1),
            "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
            "collective_bytes": coll, "memory_analysis": mem_info,
            "model_flops": model_flops,
            "useful_flops_ratio": (model_flops / (flops_dev * n_dev)
                                   if flops_dev else 0.0),
            **terms,
            "dominant": max(terms, key=terms.get),
            "roofline_fraction": (model_flops / (n_dev * PEAK_FLOPS)
                                  / max(terms.values())
                                  if max(terms.values()) > 0 else 0.0),
            "hlo_text_bytes": len(hlo),
            "status": "ok",
        }
    except Exception as e:
        res = {"arch": f"gbdt-{name}", "shape": "paper",
               "multi_pod": multi_pod, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    path.write_text(json.dumps(res, indent=1, default=str))
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    pods = ([True] if args.multi_pod else []) + \
        ([False] if args.single_pod or not args.multi_pod else [])
    for mp in pods:
        for cell in ("predict-1m", "train-iter"):
            r = run_cell(cell, mp, args.force)
            print(f"[{'2x16x16' if mp else '16x16'}] gbdt-{cell:12s} "
                  f"{r['status']} dom={r.get('dominant','-')} "
                  f"compile={r.get('compile_seconds','-')}s "
                  f"{r.get('error','')[:100]}", flush=True)


if __name__ == "__main__":
    main()
