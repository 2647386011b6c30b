"""The reduction from a profiler trace to device busy time, op time by name
and exposed collective time: on hand-made intervals, and on a small trace
recorded on a TPU v5e (one warm fit at 65,536 rows and one served batch)."""

import os

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op, Trace

GRAM = r"^%gram_padded\b"   # bench/layer_metrics/gram_roofline_pct.py

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "probe_trace.xplane.pb")


def _op(name, s, e):
    return Op(name, float(s), float(e), name)


def _trace(ops, window=(0.0, 100.0), host=()):
    return Trace(devices={"/device:TPU:0": ops}, host=list(host),
                 window=window)


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        (0, 4), (5, 7), (8, 9)]


def test_busy_is_the_union_clipped_to_the_window():
    t = _trace([_op("a", -10, 10), _op("b", 5, 20), _op("c", 90, 130)])
    assert tr.busy_s(t, "/device:TPU:0") == pytest.approx(30e-9)
    assert tr.mean_busy_s(t) == pytest.approx(30e-9)


def test_exposed_collective_excludes_time_covered_by_compute():
    t = _trace([_op("%fusion.1 = f()", 0, 10), _op("%all-reduce.3 = a()", 5, 25),
                _op("%fusion.2 = f()", 15, 18)])
    t.async_ops["/device:TPU:0"] = [_op("%all-reduce-start.1 = s()", 40, 50)]
    # 5..25 is covered 5..10 and 15..18: 12 ns exposed; 40..50 all exposed
    assert tr.exposed_collective_s(t, "/device:TPU:0") == pytest.approx(
        22e-9)


def test_op_seconds_matches_name_and_stats():
    ops = [Op("%gram_padded.1 = custom-call()", 0, 4, "%gram_padded.1 = x"),
           Op("%fusion = fusion()", 4, 6, "%fusion = fusion()"),
           Op("%gram_padded.1 = custom-call()", 10, 13, "%gram_padded.1 = x")]
    got = tr.op_seconds(_trace(ops), GRAM)
    assert got == {"/device:TPU:0": pytest.approx(7e-9)}


def test_top_ops_and_idle_gaps():
    host = [_op("PjitFunction(fit)", 0, 100), _op("PjitFunction(eigh)", 40,
                                                   80)]
    t = _trace([_op("b", 0, 10), _op("a", 10, 40), _op("b", 90, 95)],
               host=host)
    assert [n for n, _ in tr.top_ops(t)] == ["a", "b"]
    gaps = tr.idle_gaps(t, 2)
    assert gaps[0] == ["PjitFunction(eigh)", pytest.approx(50e-9)]
    assert gaps[1] == ["PjitFunction(fit)", pytest.approx(5e-9)]


def test_recorded_chip_trace():
    t = tr.load(RECORDED)
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.host and t.host[0].name == tr.WINDOW
    assert 0 < tr.mean_busy_s(t) <= t.window_s
    gram = tr.op_seconds(t, GRAM)["/device:TPU:0"]
    assert 0 < gram < tr.mean_busy_s(t)
    top = tr.top_ops(t)
    assert top[0][0] == "scatter_sorted.1" and len(top) == 10
    assert all(a[1] >= b[1] > 0 for a, b in zip(top, top[1:]))
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["DevicePut", pytest.approx(2.835e-3, rel=1e-3)]
    assert tr.exposed_collective_s(t, "/device:TPU:0") == 0.0
