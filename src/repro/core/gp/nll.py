"""Negative marginal log-likelihood (paper P1) and gradients (paper eq. 4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import cov_matrix, cov_grads

LOG_2PI = jnp.log(2.0 * jnp.pi)


def effective_jitter(log_theta: jax.Array, dtype, jitter: float = 1e-8):
    """Dtype-aware factorization jitter: relative, floored at 8*eps(dtype).

    The seed added an absolute 1e-8 to the diagonal, which is a no-op
    against float32 covariances (same failure PR 1 fixed in the NPAE
    aggregation): float32 Cholesky of a near-singular C needs a guard on
    the order of eps(float32), not eps(float64). `jitter` is now RELATIVE
    to the prior diagonal sigma_f^2 + sigma_eps^2 and floored at
    8*eps(dtype) — a deliberate semantic change: callers passing explicit
    jitters now state them as fractions of the diagonal, which makes the
    guard amplitude-invariant (float64 at the paper's O(1) signal scales
    keeps the seed's 1e-8 order; float32 training is actually guarded).
    The scale is stop_gradient'd:
    the guard is a numerical device, not part of the model, so autodiff
    and the analytic/fused trace-identity gradients agree exactly.
    """
    theta = jax.lax.stop_gradient(jnp.exp(log_theta))
    scale = theta[-2] ** 2 + theta[-1] ** 2
    return jnp.maximum(jitter, 8 * jnp.finfo(dtype).eps) * scale


def nll_from_cov(C: jax.Array, y: jax.Array) -> jax.Array:
    """NLL given an already-built covariance C — the single Cholesky body
    shared by `nll` and the cached-geometry path (core.training.cache), so
    the two can never drift apart."""
    n = y.shape[0]
    L = jnp.linalg.cholesky(C)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
    return 0.5 * (y @ alpha + logdet + n * LOG_2PI)


def inner_from_cov(C: jax.Array, y: jax.Array) -> jax.Array:
    """inner = C^-1 - alpha alpha^T, the trace-identity operand of eq. 4 —
    shared by `nll_grad_analytic` and the fused cached path."""
    return inner_from_factor(jnp.linalg.cholesky(C), y)


def inner_from_factor(L: jax.Array, y: jax.Array) -> jax.Array:
    """`inner_from_cov` from C's Cholesky factor L: the explicit inverse."""
    n = y.shape[0]
    Cinv = jax.scipy.linalg.cho_solve((L, True), jnp.eye(n, dtype=L.dtype))
    alpha = Cinv @ y
    return Cinv - jnp.outer(alpha, alpha)


def nll(log_theta: jax.Array, X: jax.Array, y: jax.Array,
        jitter: float = 1e-8) -> jax.Array:
    """0.5 * (y^T C^-1 y + log|C| + N log 2pi), via Cholesky (Rasmussen A.4).

    `jitter` is relative with an 8*eps(dtype) floor — see effective_jitter.
    """
    C = cov_matrix(X, log_theta,
                   jitter=effective_jitter(log_theta, X.dtype, jitter))
    return nll_from_cov(C, y)


nll_value_and_grad = jax.jit(jax.value_and_grad(nll))


def nll_grad_analytic(log_theta: jax.Array, X: jax.Array, y: jax.Array,
                      jitter: float = 1e-8) -> jax.Array:
    """Gradient via the paper's trace identity (eq. 4), in log-theta coords.

    dNLL/dtheta_j = 0.5 tr{ (C^-1 - C^-1 y y^T C^-1) dC/dtheta_j }
    (the paper's eq. 4 states dL/dtheta_j for the *log-likelihood*; this is the
    negated version consistent with minimizing the NLL).

    SLOW reference path: materializes the full (D+2, N, N) derivative stack.
    Training loops use the cached-geometry fused path instead
    (core.training.cache.nll_grad_cached -> ops.nll_grad_fused).
    """
    C = cov_matrix(X, log_theta,
                   jitter=effective_jitter(log_theta, X.dtype, jitter))
    inner = inner_from_cov(C, y)
    dC = cov_grads(X, log_theta)            # (D+2, N, N) wrt raw theta
    g_raw = 0.5 * jnp.einsum("ij,kji->k", inner, dC)
    return g_raw * jnp.exp(log_theta)        # chain rule to log-theta
