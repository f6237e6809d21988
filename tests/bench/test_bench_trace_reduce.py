"""bench/trace_reduce.py on hand-built traces (no profiler, no chip)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def _trace():
    # window 0..100 ms on two chips; chip 0: a fusion 0-30, an all-reduce
    # 25-45 (overlapping the fusion for 5 ms), the attend kernel 50-60 and
    # 70-75, idle 45-50, 60-70 and 75-100; chip 1 busy 0-80
    return {"devices": {
        "/device:TPU:0": [("fusion.1", 0, 30 * MS),
                          ("all-reduce.3", 25 * MS, 20 * MS),
                          ("_view_kernel", 50 * MS, 10 * MS),
                          ("_view_kernel", 70 * MS, 5 * MS)],
        "/device:TPU:1": [("fusion.1", 0, 80 * MS)]},
        "host": [("bench:traced_window", 0, 100 * MS),
                 ("bench:next_batch", 44 * MS, 8 * MS),
                 ("bench:dispatch_step", 58 * MS, 40 * MS)]}


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_and_idle_share():
    t = _trace()
    w0, w1 = tr.window_of(t)
    busy = tr.busy(t, w0, w1)
    assert busy["/device:TPU:0"] == 60 * MS       # 0-45, 50-60, 70-75
    assert busy["/device:TPU:1"] == 80 * MS
    # clipped to a narrower window
    assert tr.busy(t, 20 * MS, 55 * MS)["/device:TPU:0"] == 30 * MS


def test_collective_exposed_counts_only_uncovered_time():
    t = _trace()
    exp = tr.collective_exposed(t, *tr.window_of(t))
    assert exp["/device:TPU:0"] == 15 * MS        # 30-45
    assert exp["/device:TPU:1"] == 0
    assert tr.is_collective("all-gather-start.2")
    assert not tr.is_collective("fusion.7")


def test_nested_ops_count_once():
    # a while op spans its body's ops; an all-reduce inside a loop body is
    # exposed where no other innermost op runs, whatever its parent
    t = {"devices": {"/device:TPU:0": [
        ("%while.1 = (s32[]) while(x)", 0, 100 * MS),
        ("%fusion.2 = f32[8]{0} fusion(x)", 0, 40 * MS),
        ("%all-reduce.3 = f32[8]{0} all-reduce(x)", 40 * MS, 30 * MS),
        ("%fusion.2 = f32[8]{0} fusion(x)", 60 * MS, 40 * MS)]},
        "host": [("bench:traced_window", 0, 100 * MS)]}
    w = tr.window_of(t)
    assert len(tr.leaves(t["devices"]["/device:TPU:0"])) == 3
    assert tr.collective_exposed(t, *w)["/device:TPU:0"] == 20 * MS
    assert tr.busy(t, *w)["/device:TPU:0"] == 100 * MS
    top = dict(tr.top_ops(t, *w))
    assert top == {"fusion fusion f32[8]": pytest.approx(0.080),
                   "all-reduce all-reduce f32[8]": pytest.approx(0.030)}


def test_short_name():
    assert tr.short_name("%checkpoint.9 = bf16[544,1,12,128]{3,2,1,0} "
                         "custom-call(s32[544,256]{1,0} %a)") == \
        "checkpoint custom-call bf16[544,1,12,128]"
    assert tr.short_name("%while.7 = (s32[], bf16[4,8]{1,0}) while(%t)") \
        == "while while s32[]"


def test_kernel_time_by_name():
    t = _trace()
    w = tr.window_of(t)
    assert tr.op_stats(t, *w, r"_view_kernel") == (2, 15 * MS)
    # only executions wholly inside the window count
    assert tr.op_stats(t, 0, 72 * MS, r"_view_kernel") == (1, 10 * MS)
    top = dict(tr.top_ops(t, *w))
    assert top["fusion"] == pytest.approx(0.030)
    assert top["all-reduce"] == pytest.approx(0.020)


def test_idle_gaps_labelled_by_host_span():
    t = _trace()
    gaps = tr.idle_gaps(t, *tr.window_of(t))
    assert gaps[0] == ("bench:dispatch_step", pytest.approx(0.025))
    assert gaps[1] == ("bench:dispatch_step", pytest.approx(0.010))
    assert gaps[2] == ("bench:next_batch", pytest.approx(0.005))


def test_window_required():
    with pytest.raises(ValueError):
        tr.window_of({"devices": {}, "host": []})
