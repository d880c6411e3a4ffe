"""bench/tools/spread.py on recorded result lines: the quartile spread,
the range with the run farthest from the median left out, and that range
against half of the metric's bound."""
import json

import pytest

from harness import spec

from conftest import BENCH

spread = spec.load_module(BENCH / "tools" / "spread.py", "bench_tool_spread")

# rows_per_s of covertype-bulk on one v5e, two sets of runs of one tree.
RECORDED = {"A": [56782.47, 56178.83, 56397.95, 56584.06, 57064.82],
            "B": [56355.48, 57040.58, 57440.90]}


def _write_runs(root):
    for set_, values in RECORDED.items():
        for seed, v in enumerate(values, 1):
            line = {"correct": seed != 2 or set_ != "B", "attempted": 929600,
                    "failed": 0, "device": {"platform": "tpu", "count": 1},
                    "metrics": {"rows_per_s": {"value": v, "unit": "rows/s"},
                                "setup_s": {"value": 14.0 + seed / 10,
                                            "unit": "s"}}}
            (root / f"run_covertype-bulk_{set_}_{seed}.out").write_text(
                '{"phase": "window"}\n' + json.dumps(line) + "\n")


def test_range_leaves_out_the_farthest_run_where_that_narrows_it():
    # A: median 56584.06; 57064.82 is farthest, the rest span 603.64
    assert spread.trimmed_range(RECORDED["A"]) == \
        pytest.approx(603.64 / 56584.06)
    # B: median 57040.58; 56355.48 is farthest, the rest span 400.32
    assert spread.trimmed_range(RECORDED["B"]) == \
        pytest.approx(400.32 / 57040.58)
    # two runs: leaving one out would leave no range, so both count
    assert spread.trimmed_range([100.0, 102.0]) == pytest.approx(2 / 101)


def test_summary_pins_spread_range_and_half_bound(tmp_path):
    _write_runs(tmp_path)
    runs, bad = spread.read_runs(tmp_path)
    assert bad == [("run_covertype-bulk_B_2.out", None)]
    recs = {r["metric"]: r for r in spread.summarize(
        runs, {"A", "B"}, {"rows_per_s": 0.03, "setup_s": 0.25})}
    r = recs["rows_per_s"]
    assert r["A"]["spread"] == pytest.approx(0.0112267, abs=1e-6)
    assert r["B"]["spread"] == pytest.approx(0.0190289, abs=1e-6)
    assert r["widest"] == r["B"]["spread"]
    assert r["bound_5x"] == pytest.approx(5 * r["B"]["spread"])
    assert r["A"]["range"] == pytest.approx(0.0106680, abs=1e-6)
    assert r["B"]["range"] == pytest.approx(0.0070182, abs=1e-6)
    assert r["mean_range"] == pytest.approx(0.0088431, abs=1e-6)
    assert r["mean_range_over_half_bound"] == \
        pytest.approx(0.0088431 / 0.015, abs=1e-4)
    assert r["widest_range_over_half_bound"] == \
        pytest.approx(0.0106680 / 0.015, abs=1e-4)
    assert recs["setup_s"]["mean_range_over_half_bound"] < 1


def test_bounds_come_from_the_benchmark():
    b = spread.bounds()
    assert set(b) >= {"rows_per_s", "setup_s"}
    assert all(0 < v <= 0.25 for v in b.values())
