"""End-to-end driver (the paper's kind): large-scale Nyström KRR with SA
leverage scores through the streaming `repro.pipeline` stack.

KDE → analytic SA leverage (Eq. 6) → importance-sampled landmarks →
streaming Nyström solve (G = K_nm^T K_nm accumulated over row tiles; the
(n, m) cross-kernel matrix is never materialized) → batched predict.
Default n = 1,000,000 with m = 1024 landmarks fits on a laptop CPU in
O(tile · m) memory; the paper's 5e5-on-a-Xeon headline is the warm-up.
RC/BLESS leverage baselines are run at reduced n for the timing comparison.

  PYTHONPATH=src python examples/krr_largescale.py [--n 1000000] [--m 1024]

`--calibrate` additionally tunes (lam, h) on data through the one-fold
shared-Gram/shared-deposit sweep (`SAKRRPipeline.calibrate`) before the
refit, instead of trusting the paper's asymptotic rates.
"""

import argparse
import time

import jax

from repro.core import krr, rls
from repro.data import krr_data
from repro.launch import compile_cache
from repro.pipeline import PipelineConfig, SAKRRPipeline


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--m", type=int, default=1024, help="Nystrom landmarks")
    ap.add_argument("--tile", type=int, default=16384,
                    help="rows per streaming slab")
    ap.add_argument("--compare-n", type=int, default=20_000,
                    help="n for the RC/BLESS timing comparison")
    ap.add_argument("--calibrate", action="store_true",
                    help="tune (lam, h) on a holdout fold before the refit "
                         "(one shared Gram per h, one KDE deposit total)")
    args = ap.parse_args()
    compile_cache.configure()

    key = jax.random.PRNGKey(7)

    # -- headline run: the full evaluate fold at n -------------------------
    n = args.n
    data = krr_data.bimodal(jax.random.fold_in(key, 0), n, d=3)
    cfg = PipelineConfig(nu=1.5, num_landmarks=args.m, tile=args.tile)
    n_eval = min(n, 100_000)
    pipe = SAKRRPipeline(cfg)
    if args.calibrate:
        out = pipe.calibrate(data.x, data.y, x_eval=data.x[:n_eval],
                             y_eval=data.y[:n_eval],
                             f_star=data.f_star[:n_eval])
        scores = out["scores"]
        print(f"calibrated over {len(out['cv_scores'])} (lam, h) candidates: "
              f"lam={out['lam']:.3e} (paper rate {cfg.resolve_lam(n):.3e}), "
              f"h={out['bandwidth']:.3g}")
    else:
        scores = pipe.evaluate(data.x, data.y, x_eval=data.x[:n_eval],
                               y_eval=data.y[:n_eval],
                               f_star=data.f_star[:n_eval])
    stage = "  ".join(f"{k}={v:.2f}s" for k, v in pipe.seconds.items())
    print(f"n={n:,} m={pipe.state.num_landmarks}  {stage}")
    print(f"  d_stat≈{pipe.d_stat:.1f}   risk={scores['risk']:.5f}   "
          f"rmse={scores['rmse']:.4f}")

    # -- leverage-method comparison at reduced n ---------------------------
    nc = args.compare_n
    lam_c = 0.075 * nc ** (-2 / 3)
    data_c = krr_data.bimodal(jax.random.fold_in(key, 2), nc, d=3)
    pipe_c = SAKRRPipeline(PipelineConfig(nu=1.5)).fit(data_c.x, data_c.y)
    t_sa = pipe_c.seconds["kde"] + pipe_c.seconds["leverage"]
    print(f"n={nc:,}  SA:    {t_sa:6.2f}s")
    kern = pipe_c.kernel
    t0 = time.perf_counter()
    rls.recursive_rls(kern, data_c.x, lam_c)
    print(f"n={nc:,}  RC:    {time.perf_counter()-t0:6.2f}s")
    t0 = time.perf_counter()
    rls.bless(kern, data_c.x, lam_c)
    print(f"n={nc:,}  BLESS: {time.perf_counter()-t0:6.2f}s")


if __name__ == "__main__":
    main()
