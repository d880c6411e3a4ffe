#!/usr/bin/env python3
"""The control's readings for a node-table (non-symmetric tree)
configuration: `control.py`'s, over `model_nodes` and `reference_nodes`.

    python3 bench/tools/control_nodes.py --config covertype_lossguide \\
        --seeds 1,2,3

For each seed it makes the cell's data and model, samples the rows a
run compares (check_rows of the traffic), and prints the widest gap of
the control's probabilities from the float64 walk's:

    bf16      leaves rounded to bfloat16, sums in float32: one bfloat16
              MXU pass (the control)
"""
import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import data, model_nodes, reference, reference_nodes  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--traffic", default="bulk_sweep_nodes")
    args = ap.parse_args()
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    tr = json.loads((BENCH / "traffic" / f"{args.traffic}.json")
                    .read_text())
    for seed in [int(s) for s in args.seeds.split(",")]:
        x, _ = data.generate(cfg, seed)
        mdl = model_nodes.random_node_ensemble(cfg, x, seed)
        rows = x[data.sample_rows(len(x), int(tr["check_rows"]), seed)]
        want = reference.proba(reference_nodes.raw_f64(mdl, rows))
        got = reference.proba(reference_nodes.control_raw(mdl, rows))
        print(json.dumps({"config": args.config, "seed": seed,
                          "rows": len(rows),
                          "limit": cfg["limits"]["proba_max_abs_err"],
                          "bf16": reference.max_abs_err(got, want)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
