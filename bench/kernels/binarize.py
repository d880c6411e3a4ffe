"""binarize: bins[n, f] = #{b : x[n, f] > borders[b, f]}.

Work: N*F*B compares.  Bytes: x in (4 B a value), uint8 bins out, and
the (B, F) float32 borders once per call.
"""

# Device op events of this kernel in a v5e trace: the HLO custom call
# a pallas_call compiles to is named after the kernel ("binarize.1", and
# "binarize_dm.1" for the depth-major layout), and the trace's "XLA Ops"
# line names each op event after its HLO instruction.
EVENTS = ("binarize",)


def work(d: dict, rows: int, calls: int) -> tuple[float, float]:
    f, b = d["features"], d["borders"]
    return (float(rows) * f * b,
            float(rows) * f * (4 + 1) + float(calls) * b * f * 4)
