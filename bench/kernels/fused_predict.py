"""fused_predict: binarize, leaf index and leaf sum in one pass.

Work: the sum of the three stages, N*(F*B + T*D + T*C).  Bytes: the
float32 rows in, the model once per call (borders, split features and
bins, leaf table) and the float32 (N, C) sums out; the bins and the
index stay on chip.
"""

# Device op events of this kernel in a v5e trace: the HLO custom call
# a pallas_call compiles to is named after the kernel ("fused_predict.1", and
# "fused_predict_dm.1" for the depth-major layout), and the trace's "XLA Ops"
# line names each op event after its HLO instruction.
EVENTS = ("fused_predict",)


def work(d: dict, rows: int, calls: int) -> tuple[float, float]:
    f, b, t = d["features"], d["borders"], d["trees"]
    dp, c, leaves = d["depth"], d["outputs"], d["leaves"]
    model = b * f * 4 + t * dp * 4 * 2 + t * leaves * c * 4
    return (float(rows) * (f * b + t * dp + t * c),
            float(rows) * (4 * f + 4 * c) + float(calls) * model)
