"""What a run hands the per-layer readers, and the roofline arithmetic.

Operations and bytes come from what the algorithm needs at the model's
logical sizes (bench/kernels/<kernel>.py), never from how a kernel
implements it, so a kernel that changes its method does not make the
count stale.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional

from harness import spec
from harness.trace import Summary


def dims(config: dict) -> dict:
    m, d = config["model"], config["data"]
    return {"features": int(d["features"]), "borders": int(m["borders"]),
            "trees": int(m["trees"]), "depth": int(m["depth"]),
            "outputs": int(m["outputs"]), "leaves": 1 << int(m["depth"])}


@dataclasses.dataclass
class Run:
    """One run's readings, for bench/metrics/<name>.py `read(run)`."""
    bench_dir: pathlib.Path
    dims: dict
    peaks: dict
    e2e: dict                        # end-to-end values by metric name
    counters: dict                   # the program's counters and spans
    trace: Optional[Summary] = None  # the traced slice, --trace 1 only
    traced_rows: int = 0             # valid rows scored in the slice

    def work(self, kernel: str, rows: int, calls: int) -> tuple[float, float]:
        return spec.kernel(self.bench_dir, kernel).work(self.dims, rows,
                                                        calls)

    def ops_per_row(self, kernels) -> float:
        return sum(self.work(k, 1, 0)[0] for k in kernels)

    def roofline(self, kernel: str) -> Optional[float]:
        """Least time over kernel time, in %: the least time is the
        larger of ops / peak op rate and bytes / peak HBM bandwidth."""
        if self.trace is None or self.traced_rows <= 0:
            return None
        mod = spec.kernel(self.bench_dir, kernel)
        secs, calls = self.trace.kernel(mod.EVENTS)
        if calls == 0 or secs <= 0:
            return None
        ops, nbytes = mod.work(self.dims, self.traced_rows, calls)
        least = max(ops / self.peaks["ops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / secs
