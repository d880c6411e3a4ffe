"""Trace reduction on a hand-built trace: busy union, idle share, kernel
time and the labels of idle gaps."""
import pytest

from harness import trace
from harness.trace import Event, Trace


def _trace():
    ms = 1e6
    ops = [Event("fusion.1", 0 * ms, 2 * ms),
           Event("leaf_gather_call", 1 * ms, 3 * ms),   # overlaps fusion.1
           Event("leaf_gather_call", 6 * ms, 2 * ms),
           Event("binarize_kernel.3", 9 * ms, 0.5 * ms)]
    host = [Event(trace.WINDOW_SPAN, 0, 10 * ms),
            Event("bench.scoring.score", 0, 10 * ms),
            Event("sink write", 4.2 * ms, 1.5 * ms),
            Event("zero", 5 * ms, 0)]
    return Trace({"/device:TPU:0": ops}, host)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_and_idle_share():
    s = trace.summarize(_trace())
    assert s.window_s == pytest.approx(10e-3)
    # busy: [0, 4] + [6, 8] + [9, 9.5] = 6.5 ms
    assert s.busy_s == pytest.approx(6.5e-3)
    assert s.idle_share == pytest.approx(0.35)


def test_kernel_time_and_calls():
    s = trace.summarize(_trace())
    secs, calls = s.kernel(("leaf_gather_call",))
    assert calls == 2 and secs == pytest.approx(5e-3)
    secs, calls = s.kernel(("binarize_kernel",))
    assert calls == 1 and secs == pytest.approx(0.5e-3)
    assert s.kernel(("leaf_index",)) == (0.0, 0)


def test_gaps_longest_first_and_labelled_by_shortest_host_span():
    s = trace.summarize(_trace())
    labels = [g[0] for g in s.gaps]
    secs = [g[1] for g in s.gaps]
    assert secs == pytest.approx([2e-3, 1e-3, 0.5e-3])
    # the 4-6 ms gap's middle (5 ms) lies in "sink write"; the others
    # only in the sweep's span (the window's own span never labels)
    assert labels == ["sink write", "bench.scoring.score",
                      "bench.scoring.score"]


def test_window_clips_ops_outside_it():
    s = trace.summarize(_trace(), window=(1e6, 7e6))
    assert s.busy_s == pytest.approx(4e-3)      # [1, 4] + [6, 7]
    assert s.window_s == pytest.approx(6e-3)


def test_top_ops_sums_by_name():
    top = trace.summarize(_trace()).top_ops(2)
    assert top[0][0] == "leaf_gather_call"
    assert top[0][1] == pytest.approx(5e-3)
    assert len(top) == 2


def test_missing_window_span_or_device_is_an_error():
    t = _trace()
    with pytest.raises(ValueError):
        trace.summarize(Trace(t.device_ops, []))
    with pytest.raises(ValueError):
        trace.summarize(Trace({}, t.host))


def test_busy_is_averaged_over_devices():
    t = _trace()
    two = Trace({"/device:TPU:0": t.device_ops["/device:TPU:0"],
                 "/device:TPU:1": [Event("x", 0, 10e6)]}, t.host)
    s = trace.summarize(two)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((6.5e-3 + 10e-3) / 2)
