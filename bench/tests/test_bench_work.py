"""Kernel work counts against hand counts, the roofline arithmetic, the
per-layer readers, and the peaks table."""
import pytest

from harness import spec
from harness.trace import Event, Summary
from harness.work import Run, dims

from conftest import BENCH

D = {"features": 3, "borders": 5, "trees": 4, "depth": 2, "outputs": 2,
     "leaves": 4}


def _work(kernel, rows, calls):
    return spec.kernel(BENCH, kernel).work(D, rows, calls)


def test_binarize_work():
    # 10 rows x 3 features x 5 borders compares; x in (4 B) + bins out
    # (1 B) per value; 5 x 3 float32 borders per call
    assert _work("binarize", 10, 2) == (150, 10 * 3 * 5 + 2 * 60)


def test_leaf_index_work():
    # 10 rows x 4 trees x depth 2; bins in (3 B) + index out (16 B) per
    # row; split features and bins, 4 x 2 int32 each, per call
    assert _work("leaf_index", 10, 2) == (80, 10 * 19 + 2 * 64)


def test_leaf_gather_work():
    # 10 rows x 4 trees x 2 outputs adds; index in (16 B) + sums out
    # (8 B) per row; the 4 x 4 x 2 float32 leaf table per call
    assert _work("leaf_gather", 10, 2) == (80, 10 * 24 + 2 * 128)


def test_fused_predict_is_the_three_stages_with_model_once():
    ops, nbytes = _work("fused_predict", 10, 2)
    assert ops == 150 + 80 + 80
    # rows in (12 B) and sums out (8 B) per row; borders 60 + splits 64
    # + leaf table 128 per call
    assert nbytes == 10 * 20 + 2 * (60 + 64 + 128)


def test_every_kernel_file_counts_work():
    for name in spec.kernel_names(BENCH):
        mod = spec.kernel(BENCH, name)
        assert isinstance(mod.EVENTS, tuple)
        ops, nbytes = mod.work(D, 1, 1)
        assert ops > 0 and nbytes > 0


def test_dims_from_config():
    cfg = {"data": {"features": 54}, "model": {"borders": 254,
           "trees": 10000, "depth": 8, "outputs": 7}}
    assert dims(cfg) == {"features": 54, "borders": 254, "trees": 10000,
                         "depth": 8, "outputs": 7, "leaves": 256}


def test_peaks_known_and_unknown_device():
    v5e = spec.peaks(BENCH, "TPU v5 lite")
    assert v5e["ops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks(BENCH, "TPU v9 imaginary")


def _run(ops_events, rows=10):
    s = Summary(window_s=1.0, busy_s=0.5, ops=ops_events, gaps=[],
                n_devices=1)
    return Run(BENCH, D, {"ops_per_s": 100.0, "hbm_bytes_per_s": 1000.0},
               {}, {"rows_per_s": 20.0, "traced_valid_rows": 10}, s, rows)


def test_roofline_share(monkeypatch):
    mod = spec.kernel(BENCH, "leaf_gather")
    monkeypatch.setattr(mod, "EVENTS", ("lg",))
    run = _run([Event("lg", 0, 1e9), Event("lg", 2e9, 1e9)])
    ops, nbytes = _work("leaf_gather", 10, 2)
    least = max(ops / 100.0, nbytes / 1000.0)
    assert run.roofline("leaf_gather") == pytest.approx(100 * least / 2.0)
    # no event of the kernel: nothing to read, never 0
    assert _run([]).roofline("leaf_gather") is None


def test_readers_read_nothing_without_a_trace():
    run = _run([])
    run.trace = None
    for name in ("idle_share.bulk", "idle_share.online", "mfu.online",
                 "binarize_roofline", "fused_predict_roofline"):
        assert spec.load_module(BENCH / "metrics" / f"{name}.py",
                                f"t_{name}").read(run) is None


def test_mfu_readers():
    run = _run([])
    per_row = sum(_work(k, 1, 0)[0]
                  for k in ("binarize", "leaf_index", "leaf_gather"))
    bulk = spec.load_module(BENCH / "metrics" / "mfu.bulk.py", "t_mfu_b")
    assert bulk.read(run) == pytest.approx(100 * per_row * 20.0 / 100.0)
    online = spec.load_module(BENCH / "metrics" / "mfu.online.py",
                              "t_mfu_o")
    fused = _work("fused_predict", 1, 0)[0]
    assert online.read(run) == pytest.approx(100 * fused * 10 / (0.5 * 100))
