#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chip.

    python3 bench/run.py --workload covertype-bulk --seed 7 --seconds 10 --trace 0

See bench/harness/runner.py for what it prints and PERF.md for the cells.
"""
import os
import time


def _process_start() -> float:
    """perf_counter() at the moment the process was created (Linux
    /proc, 10 ms ticks); now, where /proc cannot say."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return now
    return now - max(uptime - ticks / os.sysconf("SC_CLK_TCK"), 0.0)


T_PROCESS0 = _process_start()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(t_process0=T_PROCESS0))
