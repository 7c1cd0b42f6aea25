"""Streaming multi-agent GP experts: sliding windows with incremental factors.

The batch pipeline (`fit_experts`) factorizes each agent's (Ni, Ni) kernel
matrix once and freezes the fleet. Agents that keep observing would have to
pay the O(Ni^3) refactorization per new point. `OnlineExperts` instead keeps
a fixed-shape AGE-ORDERED window per agent (oldest observation in slot 0,
newest in slot count-1, empty slots a contiguous sentinel tail) and
maintains the Cholesky factor L_i and weight vector alpha_i = C_i^{-1} y_i
INCREMENTALLY, O(W^2) per event against O(W^3) for a refit:

  observe(state, agent, x, y)  — if the window is full, evict the oldest
      first; then APPEND at slot `count`: because everything below the
      insert slot is a sentinel, the new sub-diagonal column is exactly
      zero, so insertion is one blocked triangular solve for the new row
      plus a scalar sqrt — no trailing sweep at all. alpha follows by two
      blocked triangular solves.
  evict_oldest(state, agent)   — drop slot 0: one rank-1 Cholesky UPDATE
      of the trailing (W-1)^2 block with the evicted point's sub-diagonal
      column (kernels.ops.cholupdate_evict — the O(W^2) column sweep).
      Age order makes the evicted row STATICALLY slot 0, so the one-slot
      shift is written by the sweep itself: on the jnp path each panel's
      write lands one slot up-left (and its panel skip kicks in for
      partially filled windows); on the TPU the Pallas kernel writes each
      column one slot up-left and the fresh sentinel row and column, so
      the evict makes one pass over the factor into the kernel's
      transposed layout and one pass out, nothing else. Vmapped over a
      fleet (`observe_fleet`), each agent's "window full" flag goes into
      the kernel, which copies a filling window's factor through
      unchanged: no select over the factors.

Fixed shapes make every operation jit-able with a traced `agent` index:
empty slots are *sentinel observations* — pseudo-inputs placed
`_SENTINEL`-far from the data (so every kernel row k(x_sent, .) underflows
to exactly 0.0) with y = 0. The covariance row/column of a sentinel slot is
exactly e_p (sigma_f^2 + sigma_eps^2 + jitter), its Cholesky row/column is
e_p * s_diag, and its alpha entry is 0 — so `to_fitted()` hands the window
arrays straight to the batch `PredictionEngine` and every decentralized
method (PoE/BCM families, NPAE cross-covariances, CBNN scores) works
unchanged on the live fleet, sentinels contributing nothing.

Validity of the sentinel trick requires lengthscales << _SENTINEL (so the
cross-kernel underflows): exp(-x) is 0.0 below x ~ -750 in float64, and
(1e6 / l)^2 > 750 for any l < 3.6e4 — comfortably true for normalized
inputs. Sentinel coordinates stay pairwise _SENTINEL-separated by
construction: eviction shifts the tail down and appends a fresh sentinel
at `last coordinate + _SENTINEL` (see `_evict_oldest_shift`).

Slot order fixes the factorization order; the refit reference (`refit`)
uses the same slot order, so incremental factors are directly comparable
(the Cholesky factor of a PD matrix is unique).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...kernels.ops import cholupdate_evict
from ..gp.kernel import se_kernel, unpack

_SENTINEL = 1e6


def _sentinel_coords(W: int, D: int, dtype) -> jax.Array:
    """(W, D) pseudo-inputs, pairwise _SENTINEL-separated and _SENTINEL-far
    from any O(1) data point."""
    return jnp.broadcast_to(
        (_SENTINEL * jnp.arange(1, W + 1, dtype=dtype))[:, None], (W, D))


def _s_diag(log_theta, jitter):
    """Cholesky diagonal of an empty (sentinel) slot."""
    _, sigma_f, sigma_eps = unpack(log_theta)
    return jnp.sqrt(sigma_f**2 + sigma_eps**2 + jitter)


_SOLVE_BK = 256


def _fwd_solve(L, b):
    """Blocked forward substitution L sol = b (L lower). XLA's CPU
    triangular_solve is ~10x off streaming rate for a single rhs; static
    panel slices turn all but the (bk, bk) diagonal solves into gemvs."""
    n = L.shape[0]
    sol = jnp.zeros_like(b)
    for k0 in range(0, n, _SOLVE_BK):
        k1 = min(k0 + _SOLVE_BK, n)
        rhs = b[k0:k1] - L[k0:k1, :k0] @ sol[:k0]
        s = jax.scipy.linalg.solve_triangular(L[k0:k1, k0:k1], rhs,
                                              lower=True)
        sol = sol.at[k0:k1].set(s)
    return sol


def _fwd_solve_t(U, b):
    """Forward substitution L sol = b given U = L^T: the same panels, the
    products in vector-matrix form over U's rows."""
    n = U.shape[0]
    sol = jnp.zeros_like(b)
    for k0 in range(0, n, _SOLVE_BK):
        k1 = min(k0 + _SOLVE_BK, n)
        rhs = b[k0:k1] - sol[:k0] @ U[:k0, k0:k1]
        s = jax.scipy.linalg.solve_triangular(U[k0:k1, k0:k1].T, rhs,
                                              lower=True)
        sol = sol.at[k0:k1].set(s)
    return sol


def _bwd_solve(L, b):
    """Blocked back substitution L^T sol = b."""
    n = L.shape[0]
    sol = jnp.zeros_like(b)
    for k1 in range(n, 0, -_SOLVE_BK):
        k0 = max(0, k1 - _SOLVE_BK)
        # vector-matrix form reads L row-major (vs the strided L^T gemv);
        # transposing the small diagonal block beats the trans=1 path
        rhs = b[k0:k1] - sol[k1:] @ L[k1:, k0:k1]
        s = jax.scipy.linalg.solve_triangular(L[k0:k1, k0:k1].T, rhs,
                                              lower=False)
        sol = sol.at[k0:k1].set(s)
    return sol


def _cho_solve(L, b):
    """alpha = (L L^T)^{-1} b by the two blocked triangular solves."""
    return _bwd_solve(L, _fwd_solve(L, b))


class OnlineExperts(NamedTuple):
    """Per-agent streaming state (a jit-able fixed-shape pytree).

    Age-ordered window: slots [0, count) hold real observations oldest
    first; slots [count, W) are sentinels (see module docstring).
    """
    log_theta: jax.Array   # (D+2,)
    Xw: jax.Array          # (M, W, D) window inputs; sentinels when invalid
    yw: jax.Array          # (M, W)    window targets; 0 when invalid
    L: jax.Array           # (M, W, W) chol of the masked window covariance
    alpha: jax.Array       # (M, W)    C_i^{-1} y_i; 0 in sentinel slots
    count: jax.Array       # (M,) int32 — number of valid observations
    jitter: jax.Array      # () factorization jitter (module-wide constant)

    @property
    def num_agents(self) -> int:
        return self.Xw.shape[0]

    @property
    def window(self) -> int:
        return self.Xw.shape[1]

    @property
    def valid(self) -> jax.Array:
        """(M, W) bool — which slots hold real observations."""
        return jnp.arange(self.window)[None, :] < self.count[:, None]

    def to_fitted(self):
        """View as batch `FittedExperts` — serves through PredictionEngine
        unchanged (sentinel slots contribute exactly nothing)."""
        from ..prediction.engine import FittedExperts
        return FittedExperts(self.log_theta, self.Xw, self.yw, self.L,
                             self.alpha)


def init_online(log_theta, M: int, W: int, D: int, dtype=None,
                jitter: float = 1e-8) -> OnlineExperts:
    """Empty fleet: every slot a sentinel, factors exactly s_diag * I."""
    log_theta = jnp.asarray(log_theta)
    if dtype is None:
        dtype = log_theta.dtype
    log_theta = log_theta.astype(dtype)
    jit_arr = jnp.asarray(jitter, dtype)
    Xw = jnp.broadcast_to(_sentinel_coords(W, D, dtype)[None], (M, W, D))
    L = jnp.broadcast_to(
        (_s_diag(log_theta, jit_arr) * jnp.eye(W, dtype=dtype))[None],
        (M, W, W))
    return OnlineExperts(log_theta, Xw, jnp.zeros((M, W), dtype), L,
                         jnp.zeros((M, W), dtype),
                         jnp.zeros((M,), jnp.int32), jit_arr)


def _window_cov(log_theta, jitter, Xi, valid):
    """Masked window covariance: real block K + noise, sentinel rows/cols
    exactly e_p (sigma_f^2 + sigma_eps^2 + jitter) — the matrix the
    incremental updates maintain the factor of."""
    _, sigma_f, sigma_eps = unpack(log_theta)
    W = Xi.shape[0]
    v = valid.astype(Xi.dtype)
    K = se_kernel(Xi, Xi, log_theta) * v[:, None] * v[None, :]
    return (K + (sigma_eps**2 + jitter) * jnp.eye(W, dtype=Xi.dtype)
            + sigma_f**2 * jnp.diag(1.0 - v))


def refit(state: OnlineExperts) -> OnlineExperts:
    """O(W^3) from-scratch refactorization of every window — the reference
    the incremental path is tested/benchmarked against."""
    valid = state.valid

    def one(Xi, yi, vi):
        C = _window_cov(state.log_theta, state.jitter, Xi, vi)
        L = jnp.linalg.cholesky(C)
        return L, _cho_solve(L, yi * vi)

    L, alpha = jax.vmap(one)(state.Xw, state.yw, valid)
    return state._replace(L=L, alpha=alpha)


def from_batch(log_theta, Xp, yp, window: int | None = None,
               jitter: float = 1e-8) -> OnlineExperts:
    """Seed a streaming fleet from batch data given OLDEST FIRST (keeps the
    last `window` points per agent when the window is smaller)."""
    Xp, yp = jnp.asarray(Xp), jnp.asarray(yp)
    M, Ni, D = Xp.shape
    W = Ni if window is None else int(window)
    if W < Ni:
        Xp, yp = Xp[:, Ni - W:], yp[:, Ni - W:]
        Ni = W
    state = init_online(log_theta, M, W, D, dtype=Xp.dtype, jitter=jitter)
    state = state._replace(
        Xw=state.Xw.at[:, :Ni].set(Xp), yw=state.yw.at[:, :Ni].set(yp),
        count=jnp.full((M,), Ni, jnp.int32))
    return refit(state)


# -- per-agent incremental cores (vmap-able) --------------------------------

def _evict_oldest_shift(log_theta, jitter, Xw, yw, L, evict=True):
    """Drop slot 0: the remaining points' factor is the rank-1 UPDATE of
    the trailing block with the evicted sub-diagonal column (the factor
    mass column 0 carried), written one slot up-left; slot W-1 becomes a
    fresh sentinel at `last coordinate + _SENTINEL` (keeps all sentinel
    coordinates pairwise _SENTINEL-separated — after a full-window evict
    it is the ONLY sentinel, otherwise it extends the monotone sentinel
    tail). A window that is already empty only rotates its sentinels.
    Where `evict` (traced is fine) is False, the window comes back as it
    went in: the factor through the kernel's own pass-through (see
    kernels.ops.cholupdate_evict), the small arrays through a select."""
    L = cholupdate_evict(L, _s_diag(log_theta, jitter), evict)
    Xs = jnp.concatenate([Xw[1:], Xw[-1:] + _SENTINEL])
    ys = jnp.concatenate([yw[1:], jnp.zeros((1,), yw.dtype)])
    return jnp.where(evict, Xs, Xw), jnp.where(evict, ys, yw), L


def _append_one(log_theta, jitter, Xw, yw, L, slot, x, y):
    """Write (x, y) into sentinel slot `slot` (everything below it is a
    sentinel, so the new sub-diagonal column is exactly zero): one blocked
    triangular solve for the new row, no trailing sweep."""
    W, D = Xw.shape
    _, sigma_f, sigma_eps = unpack(log_theta)
    idx = jnp.arange(W)
    x = x.astype(Xw.dtype)
    kvec = se_kernel(Xw, x[None], log_theta)[:, 0]          # sentinels -> 0.0
    c1 = jnp.where(idx < slot, kvec, 0.0)
    # the solve reads L^T: on the TPU that is the evict kernel's own
    # output, so no copy of the factor in a second layout is made for it
    w = jnp.where(idx < slot, _fwd_solve_t(L.T, c1), 0.0)
    d2 = sigma_f**2 + sigma_eps**2 + jitter - jnp.sum(w * w)
    d = jnp.sqrt(jnp.maximum(d2, jnp.finfo(Xw.dtype).tiny))
    L = L.at[slot].set(w + d * (idx == slot))   # row: (w_{<slot}, d, 0...)
    Xw = Xw.at[slot].set(x)
    yw = yw.at[slot].set(y.astype(yw.dtype))
    return Xw, yw, L


def _observe_one(log_theta, jitter, Xw, yw, L, count, x, y, vmapped=False):
    # device scopes: online/evict holds the evict's passes over the factor
    full = count >= Xw.shape[0]
    with jax.named_scope("online/evict"):
        if vmapped:
            # a cond would become a select over every factor: the evict
            # takes the per-agent flag instead
            Xw, yw, L = _evict_oldest_shift(log_theta, jitter, Xw, yw, L,
                                            full)
        else:
            Xw, yw, L = jax.lax.cond(
                full,
                lambda a: _evict_oldest_shift(log_theta, jitter, *a),
                lambda a: a, (Xw, yw, L))
    count = jnp.where(full, count - 1, count)
    with jax.named_scope("online/append"):
        Xw, yw, L = _append_one(log_theta, jitter, Xw, yw, L, count, x, y)
    with jax.named_scope("online/alpha"):
        alpha = _cho_solve(L, yw)
    return Xw, yw, L, alpha, count + 1


def _evict_one(log_theta, jitter, Xw, yw, L, count):
    with jax.named_scope("online/evict"):
        Xw, yw, L = jax.lax.cond(
            count > 0,
            lambda a: _evict_oldest_shift(log_theta, jitter, *a),
            lambda a: a, (Xw, yw, L))
    with jax.named_scope("online/alpha"):
        alpha = _cho_solve(L, yw)
    return Xw, yw, L, alpha, jnp.maximum(count - 1, 0)


def _scatter_agent(state: OnlineExperts, agent, parts) -> OnlineExperts:
    Xw, yw, L, alpha, count = parts
    return state._replace(
        Xw=state.Xw.at[agent].set(Xw), yw=state.yw.at[agent].set(yw),
        L=state.L.at[agent].set(L), alpha=state.alpha.at[agent].set(alpha),
        count=state.count.at[agent].set(count))


# -- public streaming API ----------------------------------------------------

def observe(state: OnlineExperts, agent, x, y) -> OnlineExperts:
    """Agent `agent` (traced index is fine) ingests one observation,
    evicting its oldest when the window is full. O(W^2)."""
    parts = _observe_one(state.log_theta, state.jitter, state.Xw[agent],
                         state.yw[agent], state.L[agent],
                         state.count[agent], x, y)
    return _scatter_agent(state, agent, parts)


def observe_fleet(state: OnlineExperts, xs, ys) -> OnlineExperts:
    """Every agent ingests one observation (xs (M, D), ys (M,)) — the
    vmapped hot path for synchronous streams."""
    Xw, yw, L, alpha, count = jax.vmap(
        functools.partial(_observe_one, vmapped=True),
        in_axes=(None, None, 0, 0, 0, 0, 0, 0))(
            state.log_theta, state.jitter, state.Xw, state.yw, state.L,
            state.count, xs, ys)
    return state._replace(Xw=Xw, yw=yw, L=L, alpha=alpha, count=count)


def evict_oldest(state: OnlineExperts, agent) -> OnlineExperts:
    """Drop agent's oldest observation (no-op on an empty window)."""
    parts = _evict_one(state.log_theta, state.jitter, state.Xw[agent],
                       state.yw[agent], state.L[agent], state.count[agent])
    return _scatter_agent(state, agent, parts)
