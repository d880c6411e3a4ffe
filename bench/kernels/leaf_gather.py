"""leaf_gather: raw[n, c] = sum_t leaf_values[t, idx[n, t], c].

Work: N*T*C adds.  Bytes: the int32 index in, the (T, 2^D, C) float32
leaf table once per call, and the float32 (N, C) sums out.
"""

# Device op events of this kernel in a v5e trace: the HLO custom call
# a pallas_call compiles to is named after the kernel ("leaf_gather.1", and
# "leaf_gather_dm.1" for the depth-major layout), and the trace's "XLA Ops"
# line names each op event after its HLO instruction.
EVENTS = ("leaf_gather",)


def work(d: dict, rows: int, calls: int) -> tuple[float, float]:
    t, c, leaves = d["trees"], d["outputs"], d["leaves"]
    return (float(rows) * t * c,
            float(rows) * (4 * t + 4 * c) + float(calls) * t * leaves * c * 4)
