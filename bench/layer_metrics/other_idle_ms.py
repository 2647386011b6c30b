"""Milliseconds per fit in which no operation ran on the chip, outside the
program's stage spans `repro/kde` and `repro/solve`: the `repro/leverage`
and `repro/sample` stages, the fold's glue between stages, and the loop
between fits.  Averaged over the chips, from the trace.

With `kde_idle_ms` and `solve_idle_ms` it splits the window's idle time:
the three, times the fits, sum to (1 - busy / window) times the window,
which `device_idle_pct.fit` reads as a share.  None where the trace holds
neither stage span (a program without spans).
"""

from bench import trace_reduce

STAGES = ("repro/kde", "repro/solve")


def _spans(host, name, lo, hi):
    """Merged intervals of the host events named ``name``, clipped to
    [lo, hi]."""
    return trace_reduce.union(
        (max(o.start, lo), min(o.end, hi)) for o in host
        if o.name == name and min(o.end, hi) > max(o.start, lo))


def _overlap(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def idle_ms(rec, name):
    """Device-idle ms per fit inside the spans named ``name`` (one of
    `STAGES`), or, with ``name=None``, in the rest of the window."""
    tr = rec["trace"]
    fits = len(rec["window"].get("fits") or [])
    if tr is None or not tr.devices or not fits:
        return None
    lo, hi = tr.window
    stages = {s: _spans(tr.host, s, lo, hi) for s in STAGES}
    if name is None:
        if not any(stages.values()):
            return None
        region, t = [], lo
        for s, e in trace_reduce.union(
                iv for ivs in stages.values() for iv in ivs):
            if s > t:
                region.append((t, s))
            t = e
        if hi > t:
            region.append((t, hi))
    else:
        region = stages[name]
        if not region:
            return None
    length = sum(e - s for s, e in region)
    idle = [length - _overlap(region, trace_reduce.union(
                (max(o.start, lo), min(o.end, hi)) for o in ops
                if min(o.end, hi) > max(o.start, lo)))
            for ops in tr.devices.values()]
    return 1e-6 * sum(idle) / len(idle) / fits


def read(rec):
    return idle_ms(rec, None)
