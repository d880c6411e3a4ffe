"""leaf_index: idx[n, t] = sum_d 2^d [bins[n, sf[t, d]] >= sb[t, d]].

Work: N*T*D compares.  Bytes: the uint8 bins in, the int32 index out,
and the (T, D) int32 split features and split bins once per call.
"""

# Device op events of this kernel in a v5e trace: the HLO custom call
# a pallas_call compiles to is named after the kernel ("leaf_index.1", and
# "leaf_index_dm.1" for the depth-major layout), and the trace's "XLA Ops"
# line names each op event after its HLO instruction.
EVENTS = ("leaf_index",)


def work(d: dict, rows: int, calls: int) -> tuple[float, float]:
    f, t, dp = d["features"], d["trees"], d["depth"]
    return (float(rows) * t * dp,
            float(rows) * (f + 4 * t) + float(calls) * t * dp * 4 * 2)
