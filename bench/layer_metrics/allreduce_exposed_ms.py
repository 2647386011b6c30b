"""Milliseconds per fit of collective ops (the KDE lattice and
normal-equation psums) during which no other op ran on the chip, averaged
over the chips, from the trace."""

from bench import trace_reduce


def read(rec):
    tr = rec["trace"]
    fits = len(rec["window"].get("fits") or [])
    if tr is None or not tr.devices or not fits or rec["chips"] < 2:
        return None
    if not any(trace_reduce.COLLECTIVE.search(trace_reduce.short_name(o.name))
               for d in (tr.devices, tr.async_ops)
               for ops in d.values() for o in ops):
        return None
    per = [trace_reduce.exposed_collective_s(tr, p) for p in tr.devices]
    return 1e3 * sum(per) / len(per) / fits
