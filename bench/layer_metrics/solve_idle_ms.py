"""Milliseconds per fit in which no operation ran on the chip inside the
program's `repro/solve` spans (SolveStage: plan resolution, the streaming
Gram pass, the landmark kernel matrix and the whitened solve).  Averaged
over the chips, from the trace; None where the trace holds no
`repro/solve` span.  Its sub-spans (`repro/solve/...`) only name the idle
gaps of the breakdown."""

from bench.layer_metrics.other_idle_ms import idle_ms


def read(rec):
    return idle_ms(rec, "repro/solve")
