"""Program spans (`repro.spans`): the fit fold's stage spans on the
profiler's host plane, nested in one ``repro/fit`` span per fit; the
seconds the spans publish under their historical keys; and no span from
code running under a JAX trace."""

from __future__ import annotations

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.data import krr_data
from repro.pipeline import CalibrateStage, PipelineConfig, SAKRRPipeline
from repro.pipeline import StageContext

STAGES = ("kde", "leverage", "sample", "solve")


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler session; (its result, {thread line name:
    [(event name, stats, start_ns, end_ns)]}) of the host plane's program
    spans."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (ev.name, dict(ev.stats), ev.start_ns, ev.end_ns)
                    for ev in line.events if ev.name.startswith("repro/"))
    return out, lines


def _named(lines, name):
    return [ev for evs in lines.values() for ev in evs if ev[0] == name]


def test_fit_leaves_stage_spans_nested_in_its_fit_span(tmp_path):
    data = krr_data.bimodal(jax.random.PRNGKey(0), 4096, d=3)
    cfg = PipelineConfig(num_landmarks=32, tile=1024)
    SAKRRPipeline(cfg).fit(data.x, data.y)     # compiles outside the trace

    def two_fits():
        return [SAKRRPipeline(cfg).fit(data.x, data.y) for _ in range(2)]

    pipes, lines = _traced(tmp_path, two_fits)
    line, = [evs for evs in lines.values()
             if any(ev[0] == "repro/fit" for ev in evs)]
    fits = sorted((ev for ev in line if ev[0] == "repro/fit"),
                  key=lambda ev: ev[2])
    assert len(fits) == 2
    assert fits[1][1]["fit"] == fits[0][1]["fit"] + 1
    for pipe, (_, _, lo, hi) in zip(pipes, fits):
        assert set(pipe.seconds) == set(STAGES)
        for stage in STAGES:
            inside = [ev for ev in line if ev[0] == f"repro/{stage}"
                      and lo <= ev[2] and ev[3] <= hi]
            assert len(inside) == 1, stage
            assert pipe.seconds[stage] <= (hi - lo) * 1e-9
    # the sub-spans that name idle gaps sit inside their stage's span
    for sub in ("repro/kde/deposit", "repro/solve/gram",
                "repro/sample/top_k"):
        stage = sub.rsplit("/", 1)[0]
        found = [ev for ev in line if ev[0] == sub]
        assert len(found) == 2, sub
        for _, _, s, e in found:
            assert any(ev[0] == stage and ev[2] <= s and e <= ev[3]
                       for ev in line), sub


@pytest.mark.parametrize("jitted", [False, True])
def test_span_under_a_jax_trace_records_nothing(tmp_path, jitted):
    def f(x):
        with spans.span("repro/test/inner", k=1):
            return jnp.sin(x) * 2.0

    x = jnp.arange(8.0)
    g = jax.jit(f) if jitted else f
    want = np.asarray(jnp.sin(x) * 2.0)
    out, lines = _traced(tmp_path,
                         lambda: jax.block_until_ready(g(x)))
    np.testing.assert_array_equal(np.asarray(out), want)
    assert len(_named(lines, "repro/test/inner")) == (0 if jitted else 1)


def test_span_seconds_and_stats(tmp_path):
    def body():
        with spans.span("repro/test/sleep", fit=3) as sp:
            time.sleep(0.02)
        return sp

    sp, lines = _traced(tmp_path, body)
    (_, stats, s, e), = _named(lines, "repro/test/sleep")
    assert stats == {"fit": 3}
    assert 0.02 <= sp.seconds and sp.seconds == pytest.approx(
        (e - s) * 1e-9, abs=2e-3)
    with spans.span("repro/test/untraced") as sp:   # no session: still timed
        time.sleep(0.01)
    assert sp.seconds >= 0.01


@pytest.mark.parametrize("folds", [1, 2])
def test_calibrate_writes_its_keys_from_spans(tmp_path, folds):
    data = krr_data.bimodal(jax.random.PRNGKey(1), 2048, d=3)
    cfg = PipelineConfig(num_landmarks=32, tile=512)
    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=2048, d=3, lam=cfg.resolve_lam(2048),
                       num_landmarks=32)
    stage = CalibrateStage(lam_grid=[1e-3, 1e-2], h_grid=[0.2, 0.4],
                           folds=folds)
    _, lines = _traced(tmp_path, lambda: stage(ctx))
    tags = [""] if folds == 1 else [f"f{j}|" for j in range(folds)]
    want = {"calibrate"} | {f"calibrate[{t}{k}]" for t in tags
                            for k in ("kde", "h=0.2", "h=0.4", "val")}
    assert set(ctx.seconds) == want
    assert all(v > 0 for v in ctx.seconds.values())
    assert len(_named(lines, "repro/calibrate/h")) == 2 * folds
    assert len(_named(lines, "repro/calibrate/val")) == folds


@pytest.mark.parametrize("entry", ["partial_fit", "predict_many"])
def test_api_entry_points_time_through_spans(tmp_path, entry):
    data = krr_data.bimodal(jax.random.PRNGKey(2), 2048, d=3)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=32, tile=512))
    if entry == "partial_fit":
        pipe.fit(data.x[:1536], data.y[:1536])
        call = lambda: pipe.partial_fit(data.x[1536:], data.y[1536:])
    else:
        pipe.fit_many(data.x, jnp.stack([data.y, 2.0 * data.y]))
        call = lambda: pipe.predict_many(data.x[:256])
    _, lines = _traced(tmp_path, call)
    assert len(_named(lines, f"repro/{entry}")) == 1
    assert pipe.state.seconds[entry] > 0
