"""Milliseconds per fit in which no operation ran on the chip inside the
program's `repro/kde` spans (DensityStage: Scott bandwidth, deposit, FFT
smoothing, read-back, and the wait for the densities).  Averaged over the
chips, from the trace; None where the trace holds no `repro/kde` span.
Its sub-spans (`repro/kde/...`) only name the idle gaps of the breakdown."""

from bench.layer_metrics.other_idle_ms import idle_ms


def read(rec):
    return idle_ms(rec, "repro/kde")
