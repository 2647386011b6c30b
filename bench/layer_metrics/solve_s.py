"""Mean seconds of the solve stage (SolveStage: the streaming Gram pass and
the whitened solve) over the fits of the window, from the program's own
per-stage clock (`pipe.seconds["solve"]`)."""


def read(rec):
    fits = rec["window"].get("fits") or []
    vals = [f["solve"] for f in fits if "solve" in f]
    return sum(vals) / len(vals) if vals else None
