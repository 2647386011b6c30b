"""Share of the serving window in which no operation ran on the chip, in %,
averaged over the chips: 100 (1 - busy / window) from the trace."""

from bench import trace_reduce


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.mean_busy_s(tr) / tr.window_s)
