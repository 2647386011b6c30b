"""Inputs made on the device from the run's seed.

A copy of the paper's bimodal design (arXiv:2103.05238, App. B), kept here
so that a change to the program's own generator cannot move the yardstick:
w.p. n/(n + n^gamma) a point is Unif[0,1]^d, else it lies in the far mode
offset + T^d, T the triangular law with density 4(1 - 2t) on [0, 0.5].
Targets are f*(x) = g(||x||_2 / d) with
g(t) = 1.6|(t - .4)(t - .6)| - t(t - 1)(t - 2) - .5, plus N(0, noise_sd^2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def seed_words(seed: int, count: int = 4) -> list[int]:
    """``count`` independent 31-bit words from any whole-number seed."""
    ss = np.random.SeedSequence(abs(int(seed)))
    return [int(w) >> 1 for w in ss.generate_state(count)]


def target(x):
    t = jnp.linalg.norm(x, axis=-1) / x.shape[-1]
    return 1.6 * jnp.abs((t - 0.4) * (t - 0.6)) - t * (t - 1.0) * (t - 2.0) - 0.5


def _inputs(key, n: int, d: int, gamma: float, offset: float):
    k1, k2, k3 = jax.random.split(key, 3)
    far = jax.random.uniform(k1, (n,)) < n ** gamma / (n + n ** gamma)
    near = jax.random.uniform(k2, (n, d), dtype=F32)
    tri = offset + 0.5 * (1.0 - jnp.sqrt(1.0 - jax.random.uniform(
        k3, (n, d), dtype=F32)))
    return jnp.where(far[:, None], tri, near)


def _make(key, *, n, d, gamma, offset, noise_sd):
    kx, ky = jax.random.split(key)
    x = _inputs(kx, n, d, gamma, offset)
    f = target(x)
    y = f + noise_sd * jax.random.normal(ky, (n,), dtype=F32)
    return x, y, f


_dataset = jax.jit(_make, static_argnames=("n", "d", "gamma", "offset",
                                           "noise_sd"))


def dataset(key, law: dict, n: int, d: int, *, row_sharding=None):
    """(x, y, f_star) for the bimodal ``law``, made in one jitted call.

    ``row_sharding``: a (NamedSharding for (n, d), one for (n,)) pair that
    places the rows across a mesh as they are made.
    """
    kw = dict(n=n, d=d, gamma=float(law["gamma"]),
              offset=float(law["offset"]), noise_sd=float(law["noise_sd"]))
    if row_sharding is None:
        return _dataset(key, **kw)
    xs, vs = row_sharding
    fn = jax.jit(functools.partial(_make, **kw),
                 out_shardings=(xs, vs, vs))
    return fn(key)


@functools.partial(jax.jit, static_argnames=("n", "d", "gamma", "offset"))
def queries(key, *, n, d, gamma, offset):
    """Query rows from the same input law (far mode included)."""
    return _inputs(key, n, d, gamma, offset)
