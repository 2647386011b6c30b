"""The numbers that decide ``correct``, and their limits.

Each number is a gap between what the timed path produced and what the
plain reference (`bench.reference`) computes from the same inputs; each has
a limit set from the readings in ``bench/limits/<workload>.json`` (see
PERF.md for the readings behind every limit).  A number that is missing,
not finite, or over its limit makes the run not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

F32 = jnp.float32


def _np(a):
    return np.asarray(jax.device_get(a), np.float64)


def _max_gap(a, b) -> float:
    """max |a - b| / max |b|."""
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))),
                                             1e-30))


def fit_numbers(prog: dict, ref: dict, x, f_star, spec: dict) -> dict:
    """Gaps between a fit's outputs (host arrays under the keys of
    `reference.fit`) and the reference's (device arrays).  The normal
    equations and the weights are compared over the landmarks both drew;
    the two risks against f* are reported beside their gap."""
    items = reference.kern_items(spec["kernel"])
    p_ref, q_ref = _np(ref["densities"]), _np(ref["probs"])
    idx_ref = np.asarray(jax.device_get(ref["landmark_idx"]))
    w_ref = _np(ref["weights"])
    idx = np.asarray(prog["landmark_idx"])
    common, at_prog, at_ref = np.intersect1d(idx, idx_ref,
                                             return_indices=True)
    w_prog = np.asarray(prog["weights"], np.float64)
    f_ref = reference.predict(x, ref["landmarks"], ref["beta"],
                              kern_items=items)
    f_prog = reference.predict(
        x, jnp.asarray(prog["landmarks"], F32),
        jnp.asarray(prog["beta"], F32), kern_items=items)
    risk_ref = float(jnp.mean((f_ref - f_star) ** 2))
    risk_prog = float(jnp.mean((f_prog - f_star) ** 2))
    pred_gap = float(jnp.max(jnp.abs(f_prog - f_ref))
                     / jnp.maximum(jnp.max(jnp.abs(f_ref)), 1e-30))
    g_ref, rhs_ref = _np(ref["gram"]), _np(ref["rhs"])
    g_prog = np.asarray(prog["gram"], np.float64)
    rhs_prog = np.asarray(prog["rhs"], np.float64)
    return {
        "gram_gap": (_max_gap(g_prog[np.ix_(at_prog, at_prog)],
                              g_ref[np.ix_(at_ref, at_ref)])
                     if common.size else math.inf),
        "rhs_gap": (_max_gap(rhs_prog[at_prog], rhs_ref[at_ref])
                    if common.size else math.inf),
        "density_gap": _max_gap(np.asarray(prog["densities"], np.float64),
                                p_ref),
        "probs_tv": 0.5 * float(np.sum(np.abs(
            np.asarray(prog["probs"], np.float64) - q_ref))),
        "landmarks_missed": float(idx.size - common.size),
        "weights_gap": (float(np.max(np.abs(w_prog[at_prog] - w_ref[at_ref])
                                     / w_ref[at_ref]))
                        if common.size else math.inf),
        "pred_gap": pred_gap,
        "risk_gap": abs(risk_prog - risk_ref) / risk_ref,
        "risk_program": risk_prog,
        "risk_reference": risk_ref,
    }


def serve_numbers(answers: list, rows: list, model: dict,
                  spec: dict) -> dict:
    """Gap between the answers served for a sample of requests (None for
    one that failed or never came) and the reference's float32 predictions
    of the served model on the same rows."""
    items = reference.kern_items(spec["kernel"])
    bad = sum(a is None or np.shape(a) != (r.shape[0],)
              for a, r in zip(answers, rows))
    stacked = np.concatenate(rows).astype(np.float32)
    want = _np(reference.predict(jnp.asarray(stacked), model["landmarks"],
                                 model["beta"], kern_items=items))
    if bad:
        gap = math.inf
    else:
        got = np.concatenate([np.asarray(a, np.float64) for a in answers])
        gap = _max_gap(got, want)
    return {"served_gap": gap, "served_wrong": float(bad)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the limited numbers."""
    out, ok = {}, bool(limits)
    for name, lim in sorted(limits.items()):
        value = numbers.get(name, math.nan)
        limit = lim["limit"] if isinstance(lim, dict) else lim
        out[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or not value <= limit:
            ok = False
    return ok, out
