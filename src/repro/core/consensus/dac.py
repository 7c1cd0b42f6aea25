"""Discrete-time average consensus (paper eq. 35, Olfati-Saber 2007).

w_i^{s+1} = w_i^s + eps * sum_{j in N_i} a_ij (w_j^s - w_i^s)

Simulated mode: one matmul with the Perron matrix per iteration; supports any
(possibly time-varying) adjacency. Lemma 1 requires eps in (0, 1/Delta).

Inside jit we run a fixed iteration count (DESIGN.md §9 item 4); the maximin
stopping criterion (Yadav & Salapaka 2007) is provided as a Python-level
wrapper `dac_until` for adaptive runs, and `dac_residual` reports the
max-min spread so callers can verify convergence post-hoc.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .graph import axis_size, max_degree, perron


def _maximin_residual(w: jax.Array) -> jax.Array:
    """Per-consensus maximin spread (Yadav & Salapaka), worst column.

    Each column of a (M, K) stack is an INDEPENDENT consensus; different
    columns settle at different values, so the spread across the whole array
    never vanishes. The stopping criterion is the max over per-column
    spreads, which does go to zero at consensus.
    """
    return jnp.max(jnp.max(w, axis=0) - jnp.min(w, axis=0))


@partial(jax.jit, static_argnames=("iters",))
def dac(w0: jax.Array, A: jax.Array, iters: int, eps: float | None = None):
    """Run `iters` DAC sweeps. w0 (M,) or (M, K) — K parallel consensuses.

    Returns (w_final, trajectory_residuals (iters,)).
    """
    if eps is None:
        eps = 1.0 / (max_degree(A) + 1.0)
    P = perron(A, eps).astype(w0.dtype)

    def body(w, _):
        with jax.named_scope("dac/round"):
            w_next = P @ w
            return w_next, _maximin_residual(w_next)

    return jax.lax.scan(body, w0, None, length=iters)


def dac_residual(w: jax.Array) -> jax.Array:
    """Maximin spread: network has reached consensus when this is ~0."""
    return _maximin_residual(w)


def dac_until(w0, A, tol: float = 1e-9, max_iters: int = 100_000,
              eps: float | None = None, chunk: int = 64):
    """Adaptive DAC: run in jit-ed chunks until the maximin criterion fires.

    Returns (w, total_iters). This mirrors the distributed stopping rule the
    paper cites: every agent tracks running max and min; when they coincide the
    network has converged.
    """
    w, iters = w0, 0
    while iters < max_iters:
        w, res = dac(w, A, chunk, eps=eps)
        iters += chunk
        if float(res[-1]) < tol:
            break
    return w, iters


def dac_time_varying(w0: jax.Array, A_seq: jax.Array, eps: float):
    """DAC over a TIME-VARYING graph (paper Assumption 1): A_seq (T, M, M)
    gives the adjacency at each iteration; convergence requires the union
    over every gamma-window to be strongly connected.

    Returns (w_final, residual trajectory)."""
    def body(w, A_t):
        with jax.named_scope("dac/round"):
            M = A_t.shape[0]
            P_t = jnp.eye(M, dtype=w.dtype) - eps * (
                jnp.diag(jnp.sum(A_t, axis=1)) - A_t).astype(w.dtype)
            w_next = P_t @ w
            return w_next, _maximin_residual(w_next)

    return jax.lax.scan(body, w0, A_seq)


def dac_sharded(w_local: jax.Array, axis_name: str, iters: int,
                eps: float | None = None, with_residuals: bool = False):
    """DAC on a cycle graph over a mesh axis via ppermute (sharded mode).

    Call inside shard_map; w_local is this agent's scalar/vector. Every agent
    exchanges with its ring neighbors only — this is the paper's neighbor-wise
    message pattern mapped onto the TPU ICI ring.

    `with_residuals=True` additionally returns the per-round maximin spread
    trajectory (iters,) — the sharded counterpart of `dac`'s residual ys,
    replicated across devices (pmax/pmin) like `dac_sharded_residual`. The
    diagnostic costs two extra collectives per round, so it is opt-in
    (the engines' diagnostics mode; serving paths leave it off).
    """
    M = axis_size(axis_name)
    if eps is None:
        eps = 1.0 / 3.0  # cycle graph: Delta = 2, eps < 1/Delta
    perm_fwd = [(i, (i + 1) % M) for i in range(M)]
    perm_bwd = [(i, (i - 1) % M) for i in range(M)]

    def body(w, _):
        with jax.named_scope("dac/round"):
            left = jax.lax.ppermute(w, axis_name, perm_fwd)
            right = jax.lax.ppermute(w, axis_name, perm_bwd)
            nbr = (left - w) + (right - w)
            if M == 2:
                # On a 2-ring the forward and backward permutations deliver
                # the SAME single neighbor; counting it twice doubles the
                # consensus gain vs the simulated single-edge graph. Halve
                # to match.
                nbr = 0.5 * nbr
            w_next = w + eps * nbr
            res = dac_sharded_residual(w_next, axis_name) \
                if with_residuals else None
            return w_next, res

    w, resids = jax.lax.scan(body, w_local, None, length=iters)
    return (w, resids) if with_residuals else w


def dac_sharded_residual(w_local: jax.Array, axis_name: str) -> jax.Array:
    """Maximin consensus spread ACROSS the mesh axis (sharded counterpart of
    `dac_residual`): max over devices minus min over devices, worst entry.

    The result is computed with pmax/pmin so it is replicated on every
    device — safe to emit through an unsharded shard_map out_spec.
    """
    hi = jax.lax.pmax(w_local, axis_name)
    lo = jax.lax.pmin(w_local, axis_name)
    return jnp.max(hi - lo)


def ring_allreduce(w_local: jax.Array, axis_name: str, op=jnp.add):
    """EXACT all-reduce over a mesh axis using only neighbor ring messages.

    Each of the `n - 1` steps forwards the travelling message one hop with
    ppermute and folds it into the local accumulator, so after a full lap
    every device holds op(w_0, ..., w_{n-1}) — the same neighbor-only
    message pattern as `dac_sharded`, but a finite exact protocol instead of
    an asymptotic averaging iteration. Used by the sharded serving engine
    for the reductions that must match the replicated engine bit-for-bit-ish
    (CBNN M_eff counts, global score maxima) and as its
    `consensus="exact"` mode.

    Note devices fold contributions in ring-arrival order, so different
    devices may differ in the last ulp for non-associative ops; follow with
    `jax.lax.pmean` if exact replication is required.
    """
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc, msg = w_local, w_local
    for _ in range(n - 1):
        msg = jax.lax.ppermute(msg, axis_name, perm)
        acc = op(acc, msg)
    return acc


def ring_allgather(w_local: jax.Array, axis_name: str) -> jax.Array:
    """EXACT all-gather over a mesh axis using only neighbor ring messages.

    Returns (n,) + w_local.shape with out[i] = device i's w_local on EVERY
    device: each of the `n - 1` hops forwards the travelling message and
    index-places it at its origin slot. Placement (no reduction) means the
    result is bit-identical on every device — no pmean needed before an
    unsharded out_spec. This is the collective behind the sharded
    npae_sparse path: agents exchange their (m, q) low-rank NPAE factors
    (core.sparse.lowrank) instead of O(Ni)-sized data, and every shard
    assembles the SAME full cross-covariance.
    """
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((n,) + w_local.shape, w_local.dtype).at[idx].set(w_local)
    msg = w_local
    for hop in range(1, n):
        msg = jax.lax.ppermute(msg, axis_name, perm)
        out = out.at[(idx - hop) % n].set(msg)
    return out


def ring_allsum(w_local: jax.Array, axis_name: str) -> jax.Array:
    """`ring_allreduce` with addition (exact network sums on the ring)."""
    return ring_allreduce(w_local, axis_name, jnp.add)


def ring_allmax(w_local: jax.Array, axis_name: str) -> jax.Array:
    """`ring_allreduce` with elementwise max — the ring-message realization
    of max-flooding (every agent learns the global max in n-1 hops)."""
    return ring_allreduce(w_local, axis_name, jnp.maximum)
