"""Degraded-mode consensus: masked reductions over the live subgraph.

The paper's protocols assume every agent answers every round (eq. 35
iterates a FIXED Perron matrix). Under churn that assumption breaks in
two ways: a dead agent's stale state keeps getting averaged in, and a
partitioned graph silently converges per-component. This module makes
both failure modes explicit instead of silently wrong:

  dac_masked        DAC over a per-round live-agent mask (and optional
                    per-round edge-survival masks): each round rebuilds
                    the Perron update from the LIVE subgraph's degrees —
                    the edge-weight renormalization that keeps eq. 35's
                    stability condition (eps < 1/Delta_t) holding on any
                    subgraph — and dead agents freeze their state (they
                    neither send nor receive). Exchanges stay symmetric,
                    so component totals are conserved round to round.
  dac_masked_sums   the degraded counterpart of the engines' `_dac_sums`
                    readout: network sums estimated from the READOUT
                    component only. With dead-from-round-0 agents the
                    estimate equals exact masked aggregation; with
                    mid-run dropout it is an honest estimate over the
                    survivors (flagged degraded by the caller, guarded
                    by the maximin residual).
  ring_allsum_masked the exact-ring counterpart for the sharded engine's
                    collectives: dead members contribute zero instead of
                    stale values.

Convergence failures (partition the union graph never heals, residual
above tolerance, non-finite moments) surface as `ConsensusDiverged` from
the serving layer — never as silent NaN/stale results. Partition
DETECTION is host-side (`graph.connected_components` on the final live
subgraph); this module only provides the masked numerics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .dac import _maximin_residual
from .graph import max_degree


class ConsensusDiverged(RuntimeError):
    """A consensus run failed to converge (residual above tolerance) or
    produced non-finite moments; raised instead of returning them."""


def _masked_maximin(w: jax.Array, alive: jax.Array) -> jax.Array:
    """Maximin spread over the LIVE rows only: dead agents hold frozen
    state that never re-converges and must not dominate the criterion."""
    a = alive.astype(bool)[:, None] if w.ndim == 2 else alive.astype(bool)
    hi = jnp.max(jnp.where(a, w, -jnp.inf), axis=0)
    lo = jnp.min(jnp.where(a, w, jnp.inf), axis=0)
    return jnp.max(hi - lo)


def dac_masked(w0: jax.Array, A: jax.Array, alive_seq: jax.Array,
               eps: float | None = None, edge_seq: jax.Array | None = None):
    """DAC sweeps over a time-varying live subgraph.

    w0 (M,) or (M, K); A (M, M) the full-fleet adjacency; alive_seq
    (iters, M) per-round live masks (0/1); edge_seq (iters, M, M)
    optional per-round edge-survival masks (message loss). Returns
    (w_final, masked maximin residual trajectory (iters,)).

    Per round t the effective adjacency is A_t = A * alive_t outer
    alive_t (* edge_t) and the update is w + eps * (A_t @ w - d_t * w)
    with d_t the LIVE-subgraph degrees — eq. 35 renormalized to the
    round's topology. eps defaults to 1/(Delta_full + 1), valid on every
    subgraph since Delta_t <= Delta_full. Dead agents are frozen via a
    where(), so a rejoining agent resumes relaying from the value it
    held at dropout (it missed the intermediate rounds — exactly the
    stale-rejoin semantics the residual guard exists to catch).
    """
    if eps is None:
        eps = 1.0 / (max_degree(A) + 1.0)
    A = A.astype(w0.dtype)
    alive_seq = alive_seq.astype(w0.dtype)
    xs = (alive_seq,) if edge_seq is None \
        else (alive_seq, edge_seq.astype(w0.dtype))

    def body(w, x):
        with jax.named_scope("dac/round"):
            alive_t = x[0]
            A_t = A * alive_t[:, None] * alive_t[None, :]
            if edge_seq is not None:
                A_t = A_t * x[1]
            d_t = jnp.sum(A_t, axis=1)
            w_next = w + eps * (A_t @ w - d_t[:, None] * w) \
                if w.ndim == 2 else w + eps * (A_t @ w - d_t * w)
            keep = alive_t[:, None] > 0 if w.ndim == 2 else alive_t > 0
            w_next = jnp.where(keep, w_next, w)
            return w_next, _masked_maximin(w_next, alive_t)

    return jax.lax.scan(body, w0, xs)


def dac_masked_sums(w0: jax.Array, A: jax.Array, alive_seq: jax.Array,
                    readout: jax.Array, n_relay: jax.Array,
                    edge_seq: jax.Array | None = None,
                    eps: float | None = None):
    """Degraded network-sums readout (the engines' `_dac_sums` under a
    fault plan).

    w0 (M, K) payload rows; readout (M,) 0/1 marks the surviving
    component members the answer is read from; n_relay the count of
    agents whose payload ever entered that component's relay (the
    conservation denominator — with dead-from-round-0 agents this is
    exactly the live member count and the estimate is exact masked
    aggregation). Returns (sums (K,), final masked residual).

    Identity at the no-fault limit: all-alive, readout all-ones,
    n_relay = M reduces to M * mean(w) — but NOT bitwise (the per-round
    masked update multiplies where the exact path matmuls a fixed
    Perron), which is why callers dispatch empty plans to `_dac_sums`.
    """
    w, res = dac_masked(w0, A, alive_seq, eps=eps, edge_seq=edge_seq)
    r = readout.astype(w0.dtype)
    comp_mean = jnp.sum(w * r[:, None], axis=0) / jnp.maximum(jnp.sum(r), 1.0)
    # the trajectory's last entry is remeasured over the READOUT members
    # only: other components legitimately settle at different values and
    # must not trip the caller's convergence guard
    res = res.at[-1].set(_masked_maximin(w, readout))
    return n_relay.astype(w0.dtype) * comp_mean, res


def ring_allsum_masked(w_local: jax.Array, axis_name: str,
                       alive: jax.Array):
    """Exact ring sum where dead members contribute zero.

    `alive` is THIS member's 0/1 liveness scalar (replicated layout:
    each shard passes its own flag). Dead members still forward ring
    messages — the ring stays intact — but their own payload is zeroed
    before entering the lap, the protocol-level hook the sharded
    engine's degraded mode builds on. Returns the sum of live
    contributions on every member.
    """
    from .dac import ring_allsum
    return ring_allsum(w_local * alive.astype(w_local.dtype), axis_name)
