"""Quickstart: the paper in ~40 lines.

Fit kernel ridge regression on 20k points from the paper's bimodal design
three ways — exact KRR (small-n oracle), Nyström+uniform, Nyström+SA
(the paper's method) — and compare error and the time spent estimating
leverage scores.

  PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import jax.numpy as jnp

from repro.core import kde, kernels, krr, leverage, nystrom
from repro.data import krr_data
from repro.launch import compile_cache

compile_cache.configure()
N, D = 20_000, 3
key = jax.random.PRNGKey(0)
kd, ks1, ks2 = jax.random.split(key, 3)

data = krr_data.bimodal(kd, N, d=D)
kern = kernels.Matern(nu=1.5)
lam = 0.075 * N ** (-2 / 3)
m = int(5 * N ** (1 / 3))  # number of Nyström landmarks

# --- the paper's method: density -> analytic leverage -> sampling weights ---
t0 = time.perf_counter()
dens = kde.estimate_densities(data.x)                      # Õ(n) binned KDE
sa = leverage.sa_leverage(dens, lam, kern, d=D)            # Eq. (6), closed form
sa_seconds = time.perf_counter() - t0
print(f"SA leverage for n={N:,}: {sa_seconds*1e3:.1f} ms "
      f"(d_stat ≈ {float(sa.d_stat):.1f} effective dims)")

# --- Nyström fits ------------------------------------------------------------
for name, probs, k in (("uniform", jnp.full((N,), 1.0 / N), ks1),
                       ("SA (paper)", sa.probs, ks2)):
    fit = nystrom.fit(k, kern, data.x, data.y, lam, m, probs)
    err = float(krr.in_sample_risk(nystrom.fitted(kern, fit, data.x),
                                   data.f_star))
    print(f"Nyström[{name:>10}]  m={m}  in-sample error = {err:.5f}")

# --- exact KRR oracle on a subsample (O(n^3) — small n only) -----------------
sub = 2_000
exact = krr.fit(kern, data.x[:sub], data.y[:sub], lam)
err = float(krr.in_sample_risk(
    krr.predict(kern, exact, data.x[:sub]), data.f_star[:sub]))
print(f"exact KRR on n={sub} subsample: in-sample error = {err:.5f}")

# --- the same pipeline as one configured object (repro.pipeline) -------------
# SAKRRPipeline chains KDE -> SA leverage -> landmark sampling -> *streaming*
# Nystrom solve -> batched predict.  The solve accumulates K_nm^T K_nm over
# row tiles (lax.scan on CPU, the fused Pallas `gram` kernel on TPU), so the
# (n, m) cross-kernel matrix is never materialized and the same code scales
# to n = 1e6+ and shards rows across a mesh (repro.distributed.sharding).
from repro.pipeline import PipelineConfig, SAKRRPipeline

pipe = SAKRRPipeline(PipelineConfig(nu=1.5, num_landmarks=m)).fit(data.x, data.y)
err = float(krr.in_sample_risk(pipe.fitted(data.x), data.f_star))
stages = "  ".join(f"{k}={v*1e3:.0f}ms" for k, v in pipe.seconds.items())
print(f"SAKRRPipeline      m={m}  in-sample error = {err:.5f}   ({stages})")
