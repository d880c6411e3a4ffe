"""node_index: idx[n, t] = the leaf row n reaches in non-symmetric tree t.

Work: N*T*D compares, D the depth limit: the path bound, a walk tests
at most D nodes (the kernel tests every node, and matches every path,
so this counts what the answer needs, not what the kernel does).
Bytes: the uint8 bins in, the int32 index out, and the tree's node
features and bins (T * nodes_per_tree each, int32) once per call.
`nodes_per_tree` comes from the run's counters (the client's model);
without it the count is that of a full tree of depth D.
"""

# Device op events of this kernel in a v5e trace: the HLO custom call
# a pallas_call compiles to is named after the kernel ("node_index.1").
EVENTS = ("node_index",)


def work(d: dict, rows: int, calls: int) -> tuple[float, float]:
    f, t, dp = d["features"], d["trees"], d["depth"]
    nodes = d.get("nodes_per_tree") or (1 << dp) - 1
    return (float(rows) * t * dp,
            float(rows) * (f + 4 * t) + float(calls) * t * nodes * 4 * 2)
