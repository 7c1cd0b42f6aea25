"""Cached-geometry training hot path — the training analogue of the
factor-cached PredictionEngine (docs/training_engine.md).

Every ADMM iteration of the paper's training methods (§3-§4) needs, per
agent, the local NLL gradient at the current theta. The seed autodiffed
`nll`, re-deriving the pairwise geometry of each agent's X (norms, the
x @ x^T Gram expansion of sq_dists, the diff^2 terms) on EVERY iteration and
paying the Cholesky VJP; the analytic alternative materialized the full
(D+2, N, N) derivative stack of `cov_grads`. But the geometry is pure data —
only theta changes across iterations. This module splits the work
accordingly:

  TrainingCache    — once per fit: the per-agent per-dimension UNSCALED
                     diff^2 stacks d2u[d] = (x_d - x'_d)^2 (a jit-able
                     pytree; `build_training_cache`).
  nll_grad_cached  — per iteration: elementwise scale + exp rebuild C,
                     one Cholesky, inner = C^-1 - alpha alpha^T, then the
                     one-pass fused contraction `ops.nll_grad_fused`
                     (Pallas on TPU, blocked jnp elsewhere) for all D+2
                     gradient components.
  make_local_grad  — resolves the `grad_fn` hook shared by every ADMM
                     training loop (admm_centralized, admm_decentralized,
                     and the sharded step).

Equivalence with autodiff is exact up to roundoff (tests/test_training_fused:
1e-6 f64, 1e-4 f32) because the effective jitter is stop_gradient'd in both
paths (gp.nll.effective_jitter).
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...kernels.ops import nll_grad_fused
from ..gp.kernel import diff2_stack, unpack
from ..gp.nll import effective_jitter, inner_from_factor, nll, nll_from_cov


class TrainingCache(NamedTuple):
    """Per-agent training-time geometry, computed once per fit.

    Leaves carry an optional leading agent axis (M, ...) in simulated mode
    and no leading axis per shard in sharded mode.
    """
    d2u: jax.Array    # (..., D, N, N) unscaled per-dimension diff^2 stacks
    y: jax.Array      # (..., N)       local targets


def build_training_cache(Xp: jax.Array, yp: jax.Array) -> TrainingCache:
    """Precompute the iteration-invariant geometry. Xp (M, N, D) or (N, D).

    Memory: O(D N^2) per agent, held ONCE across the whole ADMM run —
    amortized against the O(D N^2) work (matmuls + elementwise) that
    sq_dists/cov_grads re-spent on it every iteration.
    """
    if Xp.ndim == 3:
        return TrainingCache(jax.vmap(diff2_stack)(Xp), yp)
    return TrainingCache(diff2_stack(Xp), yp)


def cov_from_cache(log_theta, d2u, jitter: float = 1e-8):
    """(C, K) from the cached geometry: the per-iteration covariance rebuild
    reduces to one FMA contraction over d2u, one exp, and the diagonal."""
    ls, sigma_f, sigma_eps = unpack(log_theta)
    d2s = jnp.einsum("d,dij->ij", 1.0 / ls**2, d2u)
    K = sigma_f**2 * jnp.exp(-d2s)
    n = d2u.shape[-1]
    jit_eff = effective_jitter(log_theta, d2u.dtype, jitter)
    C = K + (sigma_eps**2 + jit_eff) * jnp.eye(n, dtype=K.dtype)
    return C, K


def nll_from_cache(log_theta, d2u, y, jitter: float = 1e-8):
    """NLL value from cached geometry — matches gp.nll on the same data."""
    C, _ = cov_from_cache(log_theta, d2u, jitter)
    return nll_from_cov(C, y)


def nll_grad_cached(log_theta, d2u, y, jitter: float = 1e-8,
                    use_pallas: bool | None = None,
                    interpret: bool | None = None):
    """dNLL/dlog_theta (D+2,) via the cached-geometry fused path.

    Per-iteration cost: one Cholesky + one triangular pair for the explicit
    inverse + the single fused contraction. No autodiff, no geometry
    recompute, no (D+2, N, N) stack. Each part runs under its own device
    scope (`admm/nll_grad` holds the covariance rebuild and the
    contraction).
    """
    with jax.named_scope("admm/nll_grad"):
        C, K = cov_from_cache(log_theta, d2u, jitter)
    with jax.named_scope("admm/cholesky"):
        L = jnp.linalg.cholesky(C)
    with jax.named_scope("admm/inverse"):
        inner = inner_from_factor(L, y)
    with jax.named_scope("admm/nll_grad"):
        return nll_grad_fused(log_theta, d2u, inner, K=K,
                              use_pallas=use_pallas, interpret=interpret)


def local_nll(log_theta, aux, jitter: float = 1e-8):
    """Per-agent NLL VALUE from whatever aux `make_local_grad`'s prepare
    built — TrainingCache (fused path) or the raw (Xi, yi) tuple (autodiff /
    custom hooks). The diagnostics (`diag=True`) mode of the ADMM loops
    vmaps this over the agent axis to carry per-iteration NLL through the
    scan without a second geometry pass."""
    if isinstance(aux, TrainingCache):
        return nll_from_cache(log_theta, aux.d2u, aux.y, jitter=jitter)
    return nll(log_theta, *aux, jitter=jitter)


# One agent's gradient working set (C, K, inner and the triangular-solve
# temporaries of the explicit inverse) in units of N^2 * itemsize: the TPU
# compiler needed 16.4 GiB for 16 vmapped agents at N=4096 in float32.
_GRAD_WORKSET = 16
_GRAD_BATCH_BYTES = 4 * 2**30


def agent_batch(num_agents: int, n: int, dtype) -> int:
    """How many agents' gradients to evaluate at once (`lax.map`'s
    batch_size) so their working set stays under _GRAD_BATCH_BYTES — all of
    them for small windows, a few at a time for large ones (a vmap over 16
    agents of 4096 points does not fit a 16 GiB chip)."""
    per_agent = _GRAD_WORKSET * n * n * jnp.dtype(dtype).itemsize
    return max(1, min(num_agents, _GRAD_BATCH_BYTES // per_agent))


def make_local_grad(grad_fn=None, jitter: float = 1e-8,
                    cache_limit_mb: float = 4096.0):
    """Resolve the `grad_fn` hook of the ADMM training loops.

    grad_fn:
      None            — cached-geometry fused path (the default hot path):
                        `prepare` builds a TrainingCache once per fit,
                        guarded by `cache_limit_mb` (the cache is
                        O(M D N^2); fleets past the limit fall back to the
                        autodiff hook with a UserWarning at trace time, so
                        existing call sites never OOM where the seed ran —
                        same policy as fit_experts' cross-Gram guard).
      "fused"         — cached-geometry path, UNGUARDED: the explicit
                        opt-in for callers who sized the cache themselves.
      "autodiff"      — the seed behavior, jax.grad(nll) on raw (X, y).
      callable        — custom per-agent gradient (log_theta, Xi, yi) ->
                        (D+2,), e.g. for regularized or preconditioned
                        local objectives.

    Returns (prepare, grad): `prepare(Xp, yp)` -> aux pytree whose leaves
    share Xp's leading agent axis; `grad(log_theta, aux_i)` -> (D+2,) local
    NLL gradient for one agent. Training loops vmap `grad` over the agent
    axis of `aux` (simulated mode) or close it over one shard's aux
    (sharded mode) — the update rule of eq. (34) is untouched either way.
    """
    if grad_fn in (None, "fused"):
        guarded = grad_fn is None

        def prepare(Xp, yp):
            if guarded:
                n, D = Xp.shape[-2], Xp.shape[-1]
                m = Xp.shape[0] if Xp.ndim == 3 else 1
                est_mb = (m * D * n * n
                          * jnp.dtype(Xp.dtype).itemsize / 2**20)
                if est_mb > cache_limit_mb:
                    warnings.warn(
                        f"cached-geometry training would hold {est_mb:.0f} "
                        f"MB of diff^2 stacks (M={m}, N={n}, D={D}) > "
                        f"{cache_limit_mb:.0f} MB; falling back to autodiff "
                        f"gradients — pass grad_fn='fused' to force the "
                        f"cache", stacklevel=2)
                    return (Xp, yp)
            return build_training_cache(Xp, yp)

        def grad(log_theta, aux):
            if isinstance(aux, TrainingCache):
                return nll_grad_cached(log_theta, aux.d2u, aux.y,
                                       jitter=jitter)
            return jax.grad(partial(nll, jitter=jitter))(log_theta, *aux)
        return prepare, grad

    # thread the SAME jitter into the autodiff baseline — the two hooks must
    # optimize the same objective for any jitter, not just the default
    g = (jax.grad(partial(nll, jitter=jitter)) if grad_fn == "autodiff"
         else grad_fn)

    def prepare(Xp, yp):
        return (Xp, yp)

    def grad(log_theta, aux):
        return g(log_theta, *aux)
    return prepare, grad
