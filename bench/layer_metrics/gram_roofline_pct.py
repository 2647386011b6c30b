"""Share of its roofline that the Gram kernel reaches, in %.

The least time is the larger of the nominal flop over the chip's bf16 peak
and the nominal bytes over its HBM bandwidth (`bench.work.gram`, rows per
chip), times the fits in the window; the measured time is the summed
device time of the Gram kernel's events in the trace, averaged over the
chips.  None where the trace holds no such event.
"""

from bench import trace_reduce, work

# The Pallas Gram kernel's ops in a TPU trace: its custom call takes the
# name of the function that wraps the pallas_call (PERF.md, "Layers").
PATTERN = r"^%gram_padded\b"


def read(rec):
    tr, peak = rec["trace"], rec["peak"]
    fits = len(rec["window"].get("fits") or [])
    if tr is None or peak is None or not fits:
        return None
    per_dev = [s for s in trace_reduce.op_seconds(tr, PATTERN).values()
               if s > 0]
    if len(per_dev) != len(tr.devices) or not per_dev:
        return None
    spec = rec["spec"]
    flop, bytes_ = work.gram(spec["n"] // rec["chips"], spec["m"], spec["d"])
    return work.roofline_pct(fits * flop, fits * bytes_,
                             sum(per_dev) / len(per_dev), peak)
