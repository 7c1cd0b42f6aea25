"""Decentralized ADMM factorized GP training (paper §4) — the paper's central
training contribution.

Edge-formulation consensus ADMM (P4) on a strongly connected graph:
  DEC-c-GP   (eq. 30): nested local optimization per round.
  DEC-apx-GP (eq. 34): closed-form local update (Theorem 1).
  DEC-gapx-GP (Alg. 4): DEC-apx-GP on augmented datasets.

Simulated mode: agents on a leading axis, neighbor sums = adjacency matmuls —
exact reference semantics for ANY strongly connected graph.
Sharded mode: shard_map over a mesh axis with ppermute ring messages —
the TPU-native deployment (cycle graph), bitwise-same update rule.

Every loop takes a `grad_fn` hook for the local NLL gradient (default: the
cached-geometry fused path of core.training.cache — per-iteration work is
elementwise exp + Cholesky + the one-pass ops.nll_grad_fused contraction;
"autodiff" restores the seed jax.grad(nll) behavior; any callable
(log_theta, Xi, yi) -> (D+2,) plugs in custom local objectives). The
update rule of eq. (34) is identical under every hook.

Theorem 1 requires kappa_i > L_i^2/m_i^2 - rho*lambda_min(D+A); the paper uses
kappa_i = 5000, rho = 500 in all experiments and so do we by default.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..consensus.graph import axis_size

from ..gp.nll import nll
from .cache import agent_batch, local_nll, make_local_grad


def _graph_terms(A: jax.Array, dtype):
    """(A cast for matmul, degree vector) — static across ADMM iterations,
    computed ONCE before the scan (the seed re-derived sum(A) every round
    inside the loop bodies)."""
    return A.astype(dtype), jnp.sum(A, axis=1).astype(dtype)


def _dec_diag(thetas_next, thetas_prev, Af, rho, aux):
    """Per-iteration diagnostics ys for the decentralized loops (diag=True).

    primal = worst EDGE disagreement max_{(i,j) in E} |theta_i - theta_j|
    (the consensus constraints of P4 are edge-wise theta_i = theta_j); dual
    = rho * max |theta^{s+1} - theta^s| (the iterate step scaled by rho);
    plus per-agent NLL and the theta trajectory. The (M, M, K) edge-
    difference broadcast is fine at diagnostic fleet sizes and never runs
    in the diag=False program.
    """
    diffs = jnp.abs(thetas_next[:, None, :] - thetas_next[None, :, :])
    primal = jnp.max(diffs * Af[:, :, None])
    disagreement = jnp.max(
        jnp.abs(thetas_next - jnp.mean(thetas_next, axis=0)))
    return {
        "residuals": disagreement,
        "primal_residuals": primal,
        "dual_residuals": rho * jnp.max(jnp.abs(thetas_next - thetas_prev)),
        "nll": jax.vmap(local_nll)(thetas_next, aux),
        "theta_trajectory": thetas_next,
    }


def _dec_info(ys):
    """diag=True info dict: `residuals` stays the v0 top-level key, the
    extended per-iteration series ride info['diagnostics']."""
    return {"residuals": ys["residuals"], "diagnostics": dict(ys)}


@partial(jax.jit,
         static_argnames=("iters", "nested_iters", "grad_fn", "diag"))
def train_dec_c_gp(log_theta0, Xp, yp, A, rho: float = 500.0,
                   iters: int = 100, nested_iters: int = 10,
                   nested_lr: float = 1e-5, grad_fn=None,
                   diag: bool = False):
    """DEC-c-GP (Alg. 2, eq. 30). Nested problem solved by GD with the
    gradient of Appendix A.2 (local NLL gradient through the grad_fn hook,
    quadratic/linear terms analytic).

    `diag=True` (static) carries per-iteration diagnostics through the scan
    — edge-wise primal residuals, dual residuals, per-agent NLL, theta
    trajectory — under info["diagnostics"]; the diag=False program is
    unchanged."""
    M = Xp.shape[0]
    thetas = jnp.broadcast_to(log_theta0, (M, log_theta0.shape[0])).astype(Xp.dtype)
    p = jnp.zeros_like(thetas)
    prepare, lgrad = make_local_grad(grad_fn)
    aux = prepare(Xp, yp)
    Af, deg = _graph_terms(A, thetas.dtype)

    def nested(theta_i, theta_i_prev, nbr_sum, deg_i, p_i, aux_i):
        # obj = L_i(th) + th^T p_i + rho * sum_j ||th - (th_i^s + th_j^s)/2||^2
        # d(obj)/dth = grad L_i(th) + p_i
        #              + rho * (2 deg th - deg th_i^s - nbr_sum)
        def g(th):
            return (lgrad(th, aux_i) + p_i
                    + rho * (2.0 * deg_i * th
                             - (deg_i * theta_i_prev + nbr_sum)))

        def body(th, _):
            return th - nested_lr * g(th), None
        th, _ = jax.lax.scan(body, theta_i, None, length=nested_iters)
        return th

    def body(carry, _):
        thetas, p = carry
        nbr_sum = Af @ thetas
        p = p + rho * (deg[:, None] * thetas - nbr_sum)             # (30a)
        thetas_next = jax.vmap(nested, in_axes=(0, 0, 0, 0, 0, 0))(
            thetas, thetas, nbr_sum, deg, p, aux)                   # (30b)
        if diag:
            return (thetas_next, p), _dec_diag(thetas_next, thetas, Af,
                                               rho, aux)
        disagreement = jnp.max(jnp.abs(thetas_next - jnp.mean(thetas_next, 0)))
        return (thetas_next, p), disagreement

    (thetas, p), ys = jax.lax.scan(body, (thetas, p), None, length=iters)
    return thetas, (_dec_info(ys) if diag else {"residuals": ys})


def dec_apx_update(thetas, p, grads, nbr_sum, deg, rho, kappa):
    """One DEC-apx-GP sweep (34a)-(34b), shared by all execution modes.

    thetas (M, K), p (M, K), grads = grad L_i(theta_i) (M, K),
    nbr_sum = sum_{j in N_i} theta_j (M, K), deg (M,).
    """
    with jax.named_scope("admm/update"):
        degc = deg[:, None]
        p_next = p + rho * (degc * thetas - nbr_sum)                # (34a)
        thetas_next = (rho * nbr_sum - grads
                       + (kappa + degc * rho) * thetas - p_next) \
            / (kappa + 2.0 * degc * rho)                            # (34b)
    return thetas_next, p_next


@partial(jax.jit, static_argnames=("iters", "grad_fn", "diag"))
def train_dec_apx_gp(log_theta0, Xp, yp, A, rho: float = 500.0,
                     kappa: float = 5000.0, iters: int = 100, grad_fn=None,
                     diag: bool = False):
    """DEC-apx-GP (Alg. 3 / Theorem 1): closed-form decentralized ADMM.

    The per-iteration hot path: the cached-geometry gradient (grad_fn hook)
    mapped across the agent axis in memory-bounded vmapped batches
    (`agent_batch`), one adjacency matmul, the closed-form sweep of eq. (34).

    `diag=True` (static) carries per-iteration diagnostics through the scan
    (see train_dec_c_gp) under info["diagnostics"]; diag=False programs are
    unchanged."""
    M = Xp.shape[0]
    thetas = jnp.broadcast_to(log_theta0, (M, log_theta0.shape[0])).astype(Xp.dtype)
    p = jnp.zeros_like(thetas)
    prepare, lgrad = make_local_grad(grad_fn)
    aux = prepare(Xp, yp)                       # once per fit, NOT per iter
    batch = agent_batch(M, Xp.shape[1], Xp.dtype)

    def fleet_grads(thetas, aux):
        return jax.lax.map(lambda a: lgrad(*a), (thetas, aux),
                           batch_size=batch)
    Af, deg = _graph_terms(A, thetas.dtype)

    def body(carry, _):
        thetas, p = carry
        with jax.named_scope("admm/update"):
            nbr_sum = Af @ thetas
        grads = fleet_grads(thetas, aux)
        thetas_next, p = dec_apx_update(thetas, p, grads, nbr_sum, deg,
                                        rho, kappa)
        if diag:
            return (thetas_next, p), _dec_diag(thetas_next, thetas, Af,
                                               rho, aux)
        disagreement = jnp.max(
            jnp.abs(thetas_next - jnp.mean(thetas_next, axis=0)))
        return (thetas_next, p), disagreement

    (thetas, p), ys = jax.lax.scan(body, (thetas, p), None, length=iters)
    return thetas, (_dec_info(ys) if diag else {"residuals": ys})


def train_dec_gapx_gp(log_theta0, Xp_aug, yp_aug, A, rho: float = 500.0,
                      kappa: float = 5000.0, iters: int = 100, grad_fn=None,
                      diag: bool = False):
    """DEC-gapx-GP (Alg. 4): sample -> flood -> augment (done by caller via
    gp.partition), then DEC-apx-GP on D_{+i}."""
    return train_dec_apx_gp(log_theta0, Xp_aug, yp_aug, A,
                            rho=rho, kappa=kappa, iters=iters,
                            grad_fn=grad_fn, diag=diag)


# ---------------------------------------------------------------------------
# Sharded execution mode: one agent per mesh-axis member, ring (cycle) graph,
# neighbor exchange via ppermute. Used by tests to prove simulated == sharded
# and by launch/ to run the GP fleet on real meshes.
# ---------------------------------------------------------------------------

def dec_apx_gp_sharded_step(theta_i, p_i, Xi, yi, axis_name: str,
                            rho: float = 500.0, kappa: float = 5000.0,
                            local_grad=None):
    """One DEC-apx-GP round for THIS agent inside shard_map (cycle graph).

    `local_grad` is the per-shard resolution of the grad_fn hook: a callable
    (theta,) -> (D+2,) already closed over this agent's cached geometry
    (train_dec_apx_gp_sharded builds the TrainingCache once per fit, outside
    the iteration scan). None falls back to autodiffing nll on (Xi, yi) so
    the step stays usable standalone."""
    M = axis_size(axis_name)
    perm_fwd = [(i, (i + 1) % M) for i in range(M)]
    perm_bwd = [(i, (i - 1) % M) for i in range(M)]
    left = jax.lax.ppermute(theta_i, axis_name, perm_fwd)
    right = jax.lax.ppermute(theta_i, axis_name, perm_bwd)
    if M == 1:
        nbr_sum = jnp.zeros_like(theta_i)      # self-permute: no neighbors
    elif M == 2:
        nbr_sum = left                          # fwd == bwd: ONE shared neighbor
    else:
        nbr_sum = left + right
    deg = jnp.asarray(float(min(M - 1, 2)), theta_i.dtype)
    if local_grad is None:
        g = jax.grad(nll)(theta_i, Xi, yi)
    else:
        g = local_grad(theta_i)
    th, p = dec_apx_update(theta_i[None], p_i[None], g[None],
                           nbr_sum[None], deg[None], rho, kappa)
    return th[0], p[0]


def train_dec_apx_gp_sharded(mesh, axis_name, log_theta0, Xp, yp,
                             rho: float = 500.0, kappa: float = 5000.0,
                             iters: int = 100, grad_fn=None):
    """Full DEC-apx-GP under shard_map on `mesh` (cycle graph over axis_name).

    Xp, yp carry the agent axis which is sharded over the mesh axis. The
    grad_fn hook resolves PER SHARD: each agent builds its own TrainingCache
    inside the shard_map body, once, before the iteration scan.

    Returns (thetas, info) with the SAME info["residuals"] series as the
    simulated loops — the per-iteration max consensus disagreement
    max_i |theta_i - mean(theta)|, computed with pmean/pmax collectives
    inside the scan (replicated across devices) — plus info["p"], the final
    dual variables. Against `train_dec_apx_gp` on the matching cycle graph
    the series agrees to reduction-order roundoff
    (tests/test_training_admm.py).
    """
    from jax.sharding import PartitionSpec as P

    M = Xp.shape[0]
    thetas0 = jnp.broadcast_to(log_theta0, (M, log_theta0.shape[0])).astype(Xp.dtype)
    p0 = jnp.zeros_like(thetas0)
    prepare, lgrad = make_local_grad(grad_fn)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
             out_specs=(P(axis_name), P(axis_name), P()), check_vma=False)
    def run(thetas, p, Xl, yl):
        aux = jax.tree.map(lambda a: a[0], prepare(Xl, yl))

        def local_grad(th):
            return lgrad(th, aux)

        def body(carry, _):
            th, pp = carry
            th2, pp2 = dec_apx_gp_sharded_step(
                th[0], pp[0], Xl[0], yl[0], axis_name, rho=rho, kappa=kappa,
                local_grad=local_grad)
            # the simulated loops' residual, on the ring: mean over the
            # agent (mesh) axis, worst per-agent deviation via pmax — the
            # result is replicated, so it exits through a P() out_spec
            mean = jax.lax.pmean(th2, axis_name)
            disagreement = jax.lax.pmax(jnp.max(jnp.abs(th2 - mean)),
                                        axis_name)
            return (th2[None], pp2[None]), disagreement
        (th, pp), resids = jax.lax.scan(body, (thetas, p), None,
                                        length=iters)
        return th, pp, resids

    thetas, p, resids = run(thetas0, p0, Xp, yp)
    return thetas, {"residuals": resids, "p": p}
