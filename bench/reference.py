"""Plain references for the GP fleet, written from the paper alone.

Nothing here imports the program or takes anything it made: each
reference starts from the data the benchmark generated and the
configuration's hyperparameters.

- `rbcm`: the rBCM aggregate (paper eq. 14-15) of the per-agent GP
  moments (eq. 10-11), in float64 on the host. The program reaches the
  network sums by DAC (eq. 35) and reads them out as M times the agents'
  mean estimate; the Perron matrix is doubly stochastic, so that readout
  is the exact sum for any number of rounds, and the reference takes the
  sums directly.
- `window_factor`: the Cholesky factor and weights of a window of
  points, in float64 on the host.
- `dec_apx`: DEC-apx-GP (Alg. 3, eq. 34) with the NLL gradient of eq. 4
  from an explicit inverse, in float32 at 'highest' matmul precision on
  the default device, then the serving factors at the consensus
  hyperparameters. A float64 host run of 100 iterations of 40 agents
  would take minutes; float32 'highest' is the precision the
  configuration states. Its linear algebra is an argument, so that the
  control (`bench/control.py`) runs the same algorithm at 'high'.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import scipy.linalg as sla


def _kernel(A, B, theta):
    """SE kernel k(A, B) in float64; theta = (l_1..l_D, sigma_f, sigma_eps)."""
    dim = A.shape[1]
    ls = np.asarray(theta[:dim], np.float64)
    d2 = np.zeros((A.shape[0], B.shape[0]))
    for d in range(dim):
        diff = (A[:, d, None] - B[None, :, d]) / ls[d]
        d2 += diff * diff
    return theta[dim] ** 2 * np.exp(-d2)


def factor(X, y, theta, jitter: float):
    """(L, alpha) of C = K(X, X) + (sigma_eps^2 + jitter) I, float64."""
    X = np.asarray(X, np.float64)
    C = _kernel(X, X, theta)
    C[np.diag_indices_from(C)] += theta[-1] ** 2 + jitter
    L = np.linalg.cholesky(C)
    alpha = sla.cho_solve((L, True), np.asarray(y, np.float64))
    return L, alpha


def local_moments(X, L, alpha, Xq, theta):
    """Per-agent posterior mean and variance at Xq (eq. 10-11)."""
    Xq = np.asarray(Xq, np.float64)
    ks = _kernel(np.asarray(X, np.float64), Xq, theta)      # (n, q)
    v = sla.solve_triangular(L, ks, lower=True)
    var = np.maximum(theta[-2] ** 2 - np.sum(v * v, axis=0), 1e-12)
    return ks.T @ alpha, var


def rbcm(Xp, yp, Xq, theta, jitter: float):
    """rBCM mean and variance at Xq of the agents (Xp[i], yp[i])."""
    pv = theta[-2] ** 2
    s_mu = s_prec = s_beta = 0.0
    for X, y in zip(Xp, yp):
        L, alpha = factor(X, y, theta, jitter)
        mu, var = local_moments(X, L, alpha, Xq, theta)
        beta = 0.5 * (np.log(pv) - np.log(var))
        s_mu = s_mu + beta * mu / var
        s_prec = s_prec + beta / var
        s_beta = s_beta + beta
    prec = s_prec + (1.0 - s_beta) / pv
    return s_mu / prec, 1.0 / prec


def window_factor(Xw, yw, theta, jitter: float):
    """(L, alpha) of every agent's window, float64."""
    out = [factor(X, y, theta, jitter) for X, y in zip(Xw, yw)]
    return np.stack([o[0] for o in out]), np.stack([o[1] for o in out])


def path_adjacency(M: int) -> np.ndarray:
    A = np.zeros((M, M))
    idx = np.arange(M - 1)
    A[idx, idx + 1] = A[idx + 1, idx] = 1.0
    return A


def _highest():
    """The linear algebra of `dec_apx` at 'highest' precision."""
    import functools

    import jax
    import jax.numpy as jnp
    dot = functools.partial(jnp.matmul,
                            precision=jax.lax.Precision.HIGHEST)

    def inverse(C):
        L = jnp.linalg.cholesky(C)
        eye = jnp.eye(C.shape[-1], dtype=C.dtype)
        return jax.scipy.linalg.cho_solve((L, True), eye)

    def factor_solve(C, y):
        L = jnp.linalg.cholesky(C)
        return L, jax.scipy.linalg.cho_solve((L, True), y)
    return SimpleNamespace(name="highest", dot=dot, inverse=inverse,
                           factor_solve=factor_solve)


def dec_apx(Xp, yp, theta0, A, *, rho: float, kappa: float, iters: int,
            train_jitter_rel: float, serve_jitter: float, linalg=None):
    """DEC-apx-GP from theta0 -> (per-agent log thetas (M, K), consensus
    log theta (K,), L (M, n, n), alpha (M, n)) in float32. `linalg`
    (dot, inverse, factor_solve) defaults to float32 at 'highest'."""
    import jax
    import jax.numpy as jnp

    la = _highest() if linalg is None else linalg
    dim = Xp.shape[-1]

    def cov(lt, X, jitter):
        th = jnp.exp(lt)
        d = (X[:, None, :] - X[None, :, :]) / th[:dim]
        K = th[dim] ** 2 * jnp.exp(-jnp.sum(d * d, axis=-1))
        eye = jnp.eye(X.shape[0], dtype=X.dtype)
        return K, K + (th[dim + 1] ** 2 + jitter) * eye

    def grad(lt, X, y):
        # 0.5 tr((C^-1 - a a^T) dC/dlog theta_j), j = l_1..l_D, sf, se
        th = jnp.exp(lt)
        jit = train_jitter_rel * (th[dim] ** 2 + th[dim + 1] ** 2)
        K, C = cov(lt, X, jit)
        Ci = la.inverse(C)
        a = la.dot(Ci, y[:, None])[:, 0]
        W = (Ci - jnp.outer(a, a)) * K
        g_l = [jnp.sum(W * (X[:, d, None] - X[None, :, d]) ** 2)
               / th[d] ** 2 for d in range(dim)]
        g_se = th[dim + 1] ** 2 * jnp.trace(Ci - jnp.outer(a, a))
        return jnp.stack(g_l + [jnp.sum(W), g_se])

    def run(Xp, yp, lt0, A):
        M = Xp.shape[0]
        deg = jnp.sum(A, axis=1)[:, None]
        th = jnp.broadcast_to(lt0, (M, lt0.shape[0]))
        p = jnp.zeros_like(th)

        def body(_, carry):
            th, p = carry
            nbr = la.dot(A, th)
            g = jax.vmap(grad)(th, Xp, yp)
            p = p + rho * (deg * th - nbr)                          # (34a)
            th = (rho * nbr - g + (kappa + deg * rho) * th - p) \
                / (kappa + 2 * deg * rho)                          # (34b)
            return th, p

        th, _ = jax.lax.fori_loop(0, iters, body, (th, p))
        lt = jnp.mean(th, axis=0)

        def fac(X, y):
            _, C = cov(lt, X, serve_jitter)
            return la.factor_solve(C, y)
        L, alpha = jax.vmap(fac)(Xp, yp)
        return th, lt, L, alpha

    out = jax.jit(run)(jnp.asarray(Xp, jnp.float32),
                       jnp.asarray(yp, jnp.float32),
                       jnp.log(jnp.asarray(theta0, jnp.float32)),
                       jnp.asarray(A, jnp.float32))
    return tuple(np.asarray(o) for o in out)
