"""Beyond-paper bridge: SA leverage scores as landmark weights for Nyström
ATTENTION (Nyströmformer-style) — the paper's "sample where density is low"
insight applied to softmax-attention approximation.

The softmax kernel exp(q.k/sqrt(d)) is not stationary, but landmark quality
is still governed by coverage of the key distribution; SA weights computed
from the key density up-weight rare keys exactly like they up-weight rare
inputs in KRR.  Caveat straight from the paper (§3.2 / App. B.4): the SA
density exponent d/(2α) − 1 flattens as d grows, so the demo uses
low-dimensional keys (d=4) — in line with the paper's own scope, and with
attention heads whose keys concentrate near low-dimensional structure.

We build a bimodal key set (5% of keys in a rare-but-queried mode) and
compare the attention-output error with m landmarks sampled uniformly vs by
SA weights, averaged over sampling seeds.

  PYTHONPATH=src python examples/nystrom_attention.py
"""

import jax
import jax.numpy as jnp

from repro.core import kde, kernels, leverage, sampling
from repro.launch import compile_cache


def softmax_attention(q, k, v):
    logits = q @ k.T / jnp.sqrt(q.shape[-1])
    return jax.nn.softmax(logits, axis=-1) @ v


def nystrom_attention(q, k, v, landmarks):
    """Nyströmformer: softmax(QK^T) ~ F @ pinv(A) @ B with landmark set L."""
    kl = k[landmarks]
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    F = jax.nn.softmax(q @ kl.T * scale, axis=-1)         # (n, m)
    A = jax.nn.softmax(kl @ kl.T * scale, axis=-1)        # (m, m)
    B = jax.nn.softmax(kl @ k.T * scale, axis=-1)         # (m, n)
    return F @ jnp.linalg.pinv(A) @ (B @ v)


def main() -> None:
    compile_cache.configure()
    key = jax.random.PRNGKey(0)
    n, d, m, reps = 2048, 4, 32, 8
    kq, kk, kv, km, ks1 = jax.random.split(key, 5)
    # bimodal keys: 95% around 0, 5% in a far mode that dominates some queries
    kmain = jax.random.normal(kk, (n, d))
    krare = 5.0 + 0.25 * jax.random.normal(km, (n, d))
    is_rare = jax.random.uniform(ks1, (n,)) < 0.05
    k = jnp.where(is_rare[:, None], krare, kmain)
    # a third of the queries target the rare mode
    q = jax.random.normal(kq, (n, d))
    q = q.at[: n // 3].add(5.0)
    v = jax.random.normal(kv, (n, d))

    exact = softmax_attention(q, k, v)

    dens = kde.kde_direct(k, k, 0.7)
    sa = leverage.sa_leverage(dens, lam=1e-2,
                              kernel=kernels.Matern(nu=0.5), d=d)
    results = {}
    for name, probs in (("uniform", jnp.full((n,), 1.0 / n)),
                        ("sa-leverage", sa.probs)):
        errs, cov = [], []
        for r in range(reps):
            idx = sampling.sample_without_replacement(
                jax.random.PRNGKey(100 + r), probs, m)
            approx = nystrom_attention(q, k, v, idx)
            errs.append(float(jnp.linalg.norm(approx - exact)
                              / jnp.linalg.norm(exact)))
            cov.append(float(jnp.mean(is_rare[idx])))
        results[name] = sum(errs) / reps
        print(f"{name:>12}: rel. attention error = {results[name]:.4f}  "
              f"(rare-mode landmark share: {100*sum(cov)/reps:.0f}%, "
              f"population share 5%)")
    assert results["sa-leverage"] < results["uniform"], results
    print("SA landmark weighting covers the rare key mode that uniform "
          "sampling under-represents.")


if __name__ == "__main__":
    main()
