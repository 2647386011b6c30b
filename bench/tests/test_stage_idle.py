"""The split of the fit window's device-idle time by the program's stage
spans (`kde_idle_ms`, `solve_idle_ms`, `other_idle_ms`): on hand-made
traces, in milliseconds, and on the recorded chip trace, which holds no
program spans."""

import os

import pytest

from bench import run as bench_run
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Trace

READERS = ("kde_idle_ms", "solve_idle_ms", "other_idle_ms")
MS = 1e6   # ns
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "probe_trace.xplane.pb")


def _ev(name, s, e):
    return Op(name, s * MS, e * MS, name)


# Window 0..100 ms, two fits.  Device busy 0-10, 20-30, 45-60, 85-95.
# repro/kde spans -10..40 (crosses the window's start) and 62..70;
# repro/solve 44..58 and 75..120 (crosses its end); the rest of the window
# is 40..44, 58..62 and 70..75.  Idle: kde 20 + 8, solve 1 + 15,
# other 4 + 2 + 5, together 100 - 45.
OPS = [_ev("op", 0, 10), _ev("op", 20, 30), _ev("op", 45, 60),
       _ev("op", 85, 95)]
HOST = [_ev(tr.WINDOW, 0, 100), _ev("repro/fit", -20, 58),
        _ev("repro/kde", -10, 40), _ev("repro/kde/deposit", 5, 15),
        _ev("repro/solve", 44, 58), _ev("repro/solve/gram", 46, 57),
        _ev("repro/fit", 60, 130), _ev("repro/kde", 62, 70),
        _ev("repro/sample", 71, 74), _ev("repro/solve", 75, 120)]
PER_FIT = {"kde_idle_ms": 14.0, "solve_idle_ms": 8.0, "other_idle_ms": 5.5}


def _rec(devices, host=HOST, fits=2):
    trace = Trace(devices=devices, host=list(host), window=(0.0, 100 * MS))
    return {"trace": trace, "window": {"fits": [{}] * fits}, "chips":
            len(devices), "peak": None, "spec": None}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("second_chip_busy", [False, True])
def test_idle_split_by_stage_span(name, second_chip_busy):
    devices = {"/device:TPU:0": OPS}
    if second_chip_busy:   # no idle there: the mean over chips halves
        devices["/device:TPU:1"] = [_ev("op", -5, 105)]
    want = PER_FIT[name] / (2 if second_chip_busy else 1)
    assert bench_run._reader(name)(_rec(devices)) == pytest.approx(want)


@pytest.mark.parametrize("fits", [1, 2, 3])
def test_split_sums_to_the_window_idle_time(fits):
    rec = _rec({"/device:TPU:0": OPS}, fits=fits)
    total = sum(bench_run._reader(n)(rec) for n in READERS) * fits
    pct = bench_run._reader("device_idle_pct.fit")(rec)
    assert total == pytest.approx(pct / 100 * rec["trace"].window_s * 1e3)
    assert total == pytest.approx(55.0)


@pytest.mark.parametrize("name", READERS)
def test_none_without_program_spans(name):
    read = bench_run._reader(name)
    bare = [o for o in HOST if not o.name.startswith("repro/")]
    assert read(_rec({"/device:TPU:0": OPS}, host=bare)) is None
    recorded = {"trace": tr.load(RECORDED), "window": {"fits": [{}]},
                "chips": 1, "peak": None, "spec": None}
    assert bench_run._reader("device_idle_pct.fit")(recorded) is not None
    assert read(recorded) is None
