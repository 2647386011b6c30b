"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, outputs, reference,
comparison with the cell's own limits) at a small size on the CPU, with one
fault planted in the program: an answer altered where it is produced (the
largest coefficient of the solve, by 1%), the sum across chips left out
(only the first chip's contribution kept, on four forced host devices),
the solve returning the coefficients it started from (zeros), half of the
rows left out of the normal equations with the mean taken over the rest;
for serving, a model that answers nothing (zeros), half of each batch's
rows dropped, one answer per batch altered.  The sound run at the same
size is correct.
"""

import jax
import jax.numpy as jnp
import pytest

from bench.tests import test_rehearsal as reh


def answer_altered(setattr):
    from repro.core import nystrom
    orig = nystrom.solve_from_state

    def solve(state, lam, **kw):
        fit = orig(state, lam, **kw)
        top = jnp.argmax(jnp.abs(fit.beta))
        return fit._replace(beta=fit.beta.at[top].multiply(1.01))
    setattr(nystrom, "solve_from_state", solve)


def solve_left_at_zero(setattr):
    from repro.core import nystrom
    orig = nystrom.solve_from_state

    def solve(state, lam, **kw):
        fit = orig(state, lam, **kw)
        return fit._replace(beta=jnp.zeros_like(fit.beta))
    setattr(nystrom, "solve_from_state", solve)


def half_rows_left_out(setattr):
    from repro.core import nystrom
    orig = nystrom._gram_normal_eq

    def gram(kernel, x, y, xm, **kw):
        half = x.shape[0] // 2
        raw = orig(kernel, x[:half], y[:half], xm, **kw)
        return jax.tree.map(lambda v: v * (x.shape[0] / half), raw)
    setattr(nystrom, "_gram_normal_eq", gram)


def exchange_left_out(setattr):
    from repro.core import streaming

    def first_chip_only(self, state, axes):
        return jax.tree.map(lambda v: jax.lax.psum(
            jnp.where(jax.lax.axis_index(axes) == 0, v, 0), axes), state)
    setattr(streaming.PlainAccumulator, "psum", first_chip_only)


def serve_state_unchanged(setattr):
    from repro.serving import artifact
    setattr(artifact.ServableKRR, "predict",
            lambda self, x: jnp.zeros((x.shape[0],), x.dtype))


def serve_half_batch(setattr):
    from repro.serving import engine
    orig = engine.ServingEngine._jit_for

    def jit_for(self, active, bucket):
        fn = orig(self, active, bucket)
        return lambda x: fn(x.at[x.shape[0] // 2:].set(0.0))
    setattr(engine.ServingEngine, "_jit_for", jit_for)


def serve_answer_altered(setattr):
    from repro.serving import engine
    orig = engine.ServingEngine._deliver

    def deliver(self, items, out):
        return orig(self, items, out.at[0].add(1.0))
    setattr(engine.ServingEngine, "_deliver", deliver)


FIT_FAULTS = [answer_altered, solve_left_at_zero, half_rows_left_out]
SERVE_FAULTS = [serve_state_unchanged, serve_half_batch, serve_answer_altered]
CASES = [(w, f) for w in ("fig1_matern.fit", "fig3_gaussian.fit")
         for f in FIT_FAULTS]


@pytest.mark.parametrize("workload", ["fig1_matern.fit",
                                      "fig3_gaussian.fit"])
def test_sound_run_is_correct(workload):
    out = reh.rehearse(workload)
    assert out["correct"] is True, out["check"]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__}" for w, f in CASES])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch.setattr)
    out = reh.rehearse(workload)
    assert out["correct"] is False, out["check"]


def test_four_chip_sound_run_is_correct():
    out = reh.rehearse_x4()
    assert out["correct"] is True, out["check"]


@pytest.mark.parametrize("fault", FIT_FAULTS + [exchange_left_out],
                         ids=lambda f: f.__name__)
def test_four_chip_fault_is_not_correct(fault):
    root = reh.bench_run.ROOT
    prelude = (f"import sys\nsys.path[:0] = {[root, root + '/src']!r}\n"
               f"from bench.tests import test_faults\n"
               f"test_faults.{fault.__name__}(setattr)\n")
    out = reh.rehearse_x4(prelude=prelude)
    assert out["correct"] is False, out["check"]


def test_sound_serving_matches_the_reference():
    out = reh.rehearse("fig1_matern.serve_poisson")
    assert float(out["numbers"]["served_wrong"]) == 0
    assert float(out["numbers"]["served_gap"]) <= 1e-6


@pytest.mark.parametrize("fault", SERVE_FAULTS, ids=lambda f: f.__name__)
def test_serving_fault_moves_the_gap(fault, monkeypatch):
    fault(monkeypatch.setattr)
    out = reh.rehearse("fig1_matern.serve_poisson")
    assert float(out["numbers"]["served_gap"]) > 1e-2
