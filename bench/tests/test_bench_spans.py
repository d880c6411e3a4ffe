"""The program's spans in a hand-built trace: per-chunk stage times,
window clipping, the idle the host loop answers for, device time outside
the kernels, the outside-kernels reader and the stages tool."""
import pytest

from harness import spans, spec, trace
from harness.trace import Event, Trace
from harness.work import Run

from conftest import BENCH

MS = 1e6


def _trace():
    """Two chunks in a 20 ms window.

    main thread  prefetch_wait [0,1] [10,11] [19.5,22]; score [1,2]
                 [11,12]; sync [2,6] [12,16]; sink [6,7] [16,17]
    device       leaf_gather [3,8], copy [8,9]; leaf_index [13,15],
                 fusion [15,15.5] -> busy 8.5 ms, idle 11.5 ms
    sync spans hold 1 + 1.5 ms of that idle, so the host loop answers
    for 9 ms (45%); copy and fusion are 1.5 ms outside the kernels.
    """
    host = [Event(trace.WINDOW_SPAN, 0, 20 * MS)]
    for name, spans_ms in (
            ("bulk/prefetch_wait", [(0, 1), (10, 11), (19.5, 22)]),
            ("bulk/score", [(1, 2), (11, 12)]),
            ("bulk/sync", [(2, 6), (12, 16)]),
            ("bulk/sink", [(6, 7), (16, 17)]),
            ("bulk/read", [(0.2, 0.4), (9, 9.2)])):
        host += [Event(name, s * MS, (e - s) * MS) for s, e in spans_ms]
    ops = [Event("%leaf_gather.1 = f32[7,256]{1,0} custom-call(s32[64,256]"
                 " %a, f32[64,256,7] %b)", 3 * MS, 5 * MS),
           Event("copy.3", 8 * MS, 1 * MS),
           Event("leaf_index_u8.2", 13 * MS, 2 * MS),
           Event("%fusion.4 = f32[256,7]{0,1} fusion(f32[7,256] %c)",
                 15 * MS, 0.5 * MS)]
    return Trace({"/device:TPU:0": ops}, host)


def _patterns():
    return [p for k in spec.kernel_names(BENCH)
            for p in spec.kernel(BENCH, k).EVENTS]


def test_op_name_cuts_the_hlo_text():
    assert spans.op_name("%leaf_gather.1 = f32[7,256] custom-call(%a)") \
        == "leaf_gather.1"
    assert spans.op_name("copy.3") == "copy.3"


def test_per_chunk_times_clip_to_the_window():
    host = _trace().host
    assert spans.window(host, trace.WINDOW_SPAN) == (0, 20 * MS)
    assert spans.chunks(host, 0, 20 * MS) == 2
    # the third wait is cut at the window's end: (1 + 1 + 0.5) / 2
    assert spans.per_chunk_ms(host, ("bulk/prefetch_wait",), 0, 20 * MS) \
        == pytest.approx(1.25)
    assert spans.per_chunk_ms(host, ("bulk/score", "bulk/sink"), 0,
                              20 * MS) == pytest.approx(2.0)
    assert spans.per_chunk_ms(host, ("bulk/sync",), 0, 20 * MS) \
        == pytest.approx(4.0)
    # a narrower window holds one chunk
    assert spans.chunks(host, 0, 9 * MS) == 1
    assert spans.per_chunk_ms(host, ("bulk/sync",), 0, 9 * MS) \
        == pytest.approx(4.0)


def test_host_idle_leaves_out_the_idle_inside_sync():
    tr = _trace()
    ops = tr.device_ops["/device:TPU:0"]
    assert spans.host_idle_s(ops, tr.host, 0, 20 * MS) \
        == pytest.approx(9e-3)
    # the device's own idle share of the same window is 11.5 / 20
    assert trace.summarize(tr).idle_share == pytest.approx(0.575)


def test_overlap_of_interval_lists():
    assert spans.overlap_ns([[0, 4], [6, 8]], [[3, 7]]) == 2
    assert spans.overlap_ns([[0, 1]], [[1, 2]]) == 0


def test_outside_kernels_sums_unmatched_device_time():
    ops = _trace().device_ops["/device:TPU:0"]
    assert spans.outside_s(ops, _patterns()) == pytest.approx(1.5e-3)


def test_a_program_without_chunk_spans_reads_nothing():
    tr = _trace()
    host = [e for e in tr.host if not e.name.startswith("bulk/")]
    assert spans.per_chunk_ms(host, ("bulk/sync",), 0, 20 * MS) is None
    assert spans.host_idle_s(tr.device_ops["/device:TPU:0"], host, 0,
                             20 * MS) is None
    with pytest.raises(ValueError):
        spans.window(host, "no such span")


def _reader_run(summary, counters, traced_rows):
    return Run(BENCH, {}, {}, {}, counters, summary, traced_rows)


def test_outside_kernels_reader():
    reader = spec.load_module(BENCH / "metrics" / "bulk.outside_kernels_ms.py",
                              "t_outside_kernels")
    s = trace.summarize(_trace())
    # 300 traced rows in 256-row chunks are two chunks: 1.5 ms / 2
    run = _reader_run(s, {"chunk_rows": 256}, 300)
    assert reader.read(run) == pytest.approx(0.75)
    assert reader.read(_reader_run(None, {"chunk_rows": 256}, 300)) is None
    assert reader.read(_reader_run(s, {}, 300)) is None


def test_stages_tool_readings():
    tool = spec.load_module(BENCH / "tools" / "stages.py", "t_stages_tool")
    got = tool.readings(_trace(), BENCH)
    assert got["chunks"] == 2
    assert got["period_ms"] == pytest.approx(10.0)
    assert got["per_chunk_ms"]["bulk/read"] == pytest.approx(0.2)
    assert got["per_chunk_ms"]["bulk/quantize"] == 0.0
    # prefetch wait 1.25 + score 1 + sync 4 + sink 1 per chunk
    assert got["main_sum_ms"] == pytest.approx(7.25)
    assert got["idle_share.bulk.host"] == pytest.approx(45.0)
    assert got["outside_kernels_ms"] == pytest.approx(0.75)
    assert got["top_ops"][0][1] == pytest.approx(5e-3)
