"""Mean seconds of the density stage (DensityStage: the binned KDE deposit,
FFT smoothing and read-back) over the fits of the window, from the
program's own per-stage clock (`pipe.seconds["kde"]`)."""


def read(rec):
    fits = rec["window"].get("fits") or []
    vals = [f["kde"] for f in fits if "kde" in f]
    return sum(vals) / len(vals) if vals else None
