"""The reduction from a profiler trace to busy time, idle share, per-op
sums and named idle gaps: on a trace built here, and on a small trace
recorded on a TPU v5e."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]


def test_gaps_inside_the_window():
    busy = [(1, 2), (3, 5)]
    assert tr.gaps(busy, (0, 6)) == [(0, 1), (2, 3), (5, 6)]
    assert tr.gaps(busy, (1.5, 4)) == [(2, 3)]
    assert tr.gaps([], (0, 1)) == [(0, 1)]


def test_names_at_takes_the_innermost_event_under_the_innermost_span():
    host = [("bench.run_scenario", 0, 10), ("bench.eval", 2, 4),
            ("PjitFunction(f)", 2.5, 3), ("PjitFunction(round)", 5, 6),
            ("other", 11, 12)]
    got = tr.names_at(host, [2.2, 2.7, 5.5, 7, 11.5, 20])
    assert got == ["bench.eval", "bench.eval/PjitFunction(f)",
                   "bench.run_scenario/PjitFunction(round)",
                   "bench.run_scenario", "other", "(no host event)"]


def test_short_name_is_the_instruction_name():
    assert tr.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.3"
    assert tr.short_name("kernel") == "kernel"


def test_self_times_leave_out_nested_ops():
    ops = [("while", 0, 10), ("a", 1, 3), ("b", 4, 5), ("c", 12, 13)]
    got = {n: own for n, _, _, own in tr.self_times(ops)}
    assert got == {"while": 7, "a": 2, "b": 1, "c": 1}


def test_summarize_a_built_trace():
    # two devices, window [0, 10): device 0 busy 0-4 (two overlapping
    # ops) and 6-8; device 1 busy 2-7
    dev = {"/device:TPU:0": [("fusion", 0, 3), ("kernel", 2, 4),
                             ("kernel", 6, 8), ("fusion", 12, 13)],
           "/device:TPU:1": [("fusion", 2, 7)]}
    host = [("bench.run_scenario", 0, 10), ("bench.eval", 4, 6)]
    s = tr.summarize(dev, host, (0, 10))
    assert s["window_s"] == 10
    assert s["busy_s"] == pytest.approx((6 + 5) / 2)
    assert s["idle_share"] == pytest.approx(1 - 5.5 / 10)
    # the op lines do not nest here: "kernel" starts inside "fusion" but
    # outlives it, and self time takes the overlap from the outer op
    assert s["ops"] == pytest.approx({"fusion": (2 + 5) / 2,
                                      "kernel": (2 + 2) / 2})
    assert s["op_counts"] == {"fusion": 1, "kernel": 1}
    gaps = dict(s["gaps"])
    # device 0 idles 4-6 (eval) and 8-10; device 1 idles 0-2 and 7-10
    assert gaps["bench.eval"] == pytest.approx(2 / 2)
    assert gaps["bench.run_scenario"] == pytest.approx((2 + 2 + 3) / 2)
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["fusion", 3.5]
    assert len(b["idle_gaps"]) == 2


def test_recorded_tpu_trace():
    found = sorted(RECORDED.glob("*.xplane.pb"))
    if not found:
        pytest.fail("the recorded trace is missing")
    s = tr.reduce_file(str(found[0]))
    assert s["n_devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert 0 < s["idle_share"] < 1
    # self times of nested ops add up to the busy time
    assert sum(s["ops"].values()) == pytest.approx(s["busy_s"], rel=1e-3)
    assert any("fused_agg_blend" in name for name in s["ops"])
    assert any(name.startswith("bench.") for name, _ in s["gaps"])
