"""Seeded synthetic GP fields, made on the device from the run's seed.

A copy of the random-Fourier-feature draw of `repro.data.gp_sample_field`
(the paper's §6 synthetic experiment: inputs uniform on [lo, hi]^D, a
latent f ~ GP(0, k_SE) at the true hyperparameters, y = f + N(0,
sigma_eps^2)), kept here so that a change to the program's generator can
never change what the benchmark measures. Also the stripe partition of the
paper's agents (sorted along the first coordinate, split into equal
contiguous stripes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of `seed` (a plain PRNGKey keeps
    only the low 32 bits when 64-bit types are off)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def np_rng(seed: int, *stream) -> np.random.Generator:
    """Host generator for one named stream of the run's seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def _field(key, n: int, theta, features: int, lo: float, hi: float,
           dim: int):
    ls = jnp.asarray(theta[:dim], jnp.float32)
    sigma_f, sigma_eps = theta[dim], theta[dim + 1]
    kx, kw, kb, kf, kn = jax.random.split(key, 5)
    X = jax.random.uniform(kx, (n, dim), jnp.float32, lo, hi)
    # spectral density of sigma_f^2 exp(-sum d^2 / l^2): N(0, 2 / l^2)
    W = jax.random.normal(kw, (features, dim), jnp.float32) \
        * (jnp.sqrt(2.0) / ls)[None, :]
    b = jax.random.uniform(kb, (features,), jnp.float32, 0.0, 2 * jnp.pi)
    w = jax.random.normal(kf, (features,), jnp.float32)

    def f_at(Z):
        phi = jnp.sqrt(2.0 / features) * jnp.cos(
            jnp.dot(Z, W.T, precision="highest") + b[None, :])
        return sigma_f * jnp.dot(phi, w, precision="highest")

    f = f_at(X)
    y = f + sigma_eps * jax.random.normal(kn, (n,), jnp.float32)
    return X, y


def make_field(seed: int, n: int, cfg: dict, stream: int = 0):
    """(X (n, D), y (n,)) float32 on the device, one jitted call from the
    seed; `stream` draws an independent field sample of the same GP."""
    dim = int(cfg["input_dim"])
    lo, hi = cfg["domain"]
    fn = jax.jit(_field, static_argnums=(1, 3, 4, 5, 6))
    key = jax.random.fold_in(seed_key(seed), stream)
    return fn(key, n, tuple(float(t) for t in cfg["true_theta"]),
              int(cfg["rff_features"]), float(lo), float(hi), dim)


def stripe_partition(X, y, M: int):
    """Sort by the first coordinate and split into M equal stripes ->
    (Xp (M, n/M, D), yp (M, n/M))."""
    n = (X.shape[0] // M) * M
    if n != X.shape[0]:
        raise ValueError(f"{X.shape[0]} points do not split into {M} "
                         f"equal stripes")
    order = jnp.argsort(X[:, 0])
    return (X[order].reshape(M, n // M, X.shape[1]),
            y[order].reshape(M, n // M))
