"""Factor-cached, query-tiled prediction engine — the serving hot path.

The paper's decentralized prediction methods (§5, Algs. 5-18) all consume the
same per-agent local quantities. The per-call functions re-factorize each
agent's (Ni, Ni) kernel matrix on EVERY request and materialize the full
(Nt, M, M) NPAE covariance tensor all at once, so prediction cannot scale in
the number of queries. Nested-aggregation practice (Rulliere et al.; the
grBCM line of Liu et al.) fits the experts once and serves from cached
factors; this module does the same:

  FittedExperts   — per-agent Cholesky L_i and weights alpha_i = C_i^{-1} y_i,
                    computed once after training (`fit_experts`). A jit-able
                    pytree (NamedTuple of arrays).
  map_query_tiles — lax.map over fixed-size query chunks: sequential tiles
                    bound peak memory at O(chunk * M^2) for the NPAE family
                    and O(chunk * M) for the PoE family at ANY Nt.
  PredictionEngine — serving front-end: all 13 decentralized methods plus the
                    centralized references behind one jit-cached `predict`.
                    With `stream_mean=True` posterior means ride the fused
                    Gram-matvec Pallas kernel (kernels.rbf_matvec).

This engine runs the fleet REPLICATED on one device (and is the only server
of the NPAE family, whose per-query (M, M) solves need strongly-complete
exchange). Its multi-device sibling is prediction/sharded.ShardedEngine:
the same FittedExperts sharded over the agent axis of a mesh, consensus on
the device ring, plus CBNN query routing (docs/serving_sharded.md).

Equivalence with the per-call paths is covered by tests/test_engine.py
(<= 1e-6 for every method).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from ...obs import default_registry
from ..consensus.degraded import ConsensusDiverged, dac_masked_sums
from ..consensus.graph import connected_components
from ..gp.kernel import unpack
from . import aggregation as agg
from ..sparse import (SparseExperts, npae_terms_lowrank,
                      sparse_moments_cached, sparse_scores)
from .cbnn import _mask_from_scores, cbnn_mask_cached
from .decentralized import (dec_poe_from_moments, dec_gpoe_from_moments,
                            dec_bcm_from_moments, dec_rbcm_from_moments,
                            dec_grbcm_from_moments, dec_npae_from_terms,
                            dec_npae_star_from_terms, dec_nn_npae_from_terms)
from .local import (chol_factors, cross_gram, local_moments_cached,
                    npae_terms_cached, stream_means)


class FittedExperts(NamedTuple):
    """Per-agent state computed once after training (a jit-able pytree)."""
    log_theta: jax.Array   # (D+2,)
    Xp: jax.Array          # (M, Ni, D)
    yp: jax.Array          # (M, Ni)
    L: jax.Array           # (M, Ni, Ni)  chol(K(X_i, X_i) + sigma_eps^2 I)
    alpha: jax.Array       # (M, Ni)      C_i^{-1} y_i
    Kcross: jax.Array | None = None   # (M, M, Ni, Ni) cross-agent Gram
    #                                   blocks (fit_experts cache_cross=True)

    @property
    def num_agents(self) -> int:
        return self.Xp.shape[0]

    @property
    def prior_var(self) -> jax.Array:
        _, sigma_f, _ = unpack(self.log_theta)
        return sigma_f**2


def fit_experts(log_theta, Xp, yp, jitter: float = 1e-8,
                cache_cross: bool = False,
                cross_cache_limit_mb: float = 1024.0) -> FittedExperts:
    """Factorize every agent's kernel matrix ONCE; reused by all methods.

    `cache_cross=True` additionally precomputes the (M, M, Ni, Ni)
    cross-agent Gram blocks the NPAE family re-assembles on every query
    batch, trading O(M^2 Ni^2) memory for the dominant per-request cost.
    The estimate is guarded against `cross_cache_limit_mb` at trace time
    (shapes are static); raise the limit explicitly for big fleets. Note
    `cache_cross` is a Python-level flag: under jit, close over it
    (functools.partial) rather than passing it as a traced argument.
    """
    L, alpha = chol_factors(log_theta, Xp, yp, jitter)
    Kcross = None
    if cache_cross:
        M, Ni = Xp.shape[0], Xp.shape[1]
        est_bytes = M * M * Ni * Ni * jnp.dtype(Xp.dtype).itemsize
        if est_bytes / 2**20 > cross_cache_limit_mb:
            raise ValueError(
                f"cache_cross would materialize {est_bytes:,} bytes "
                f"({est_bytes / 2**20:.2f} MB) of cross-agent Gram blocks "
                f"(M={M}, Ni={Ni}) > limit {cross_cache_limit_mb:.0f} MB; "
                f"raise cross_cache_limit_mb, serve without the cache, or "
                f"serve the NPAE family from sparse pseudo-representations "
                f"instead — FleetConfig(sparse_m=...) with method "
                f"'npae_sparse' needs no cross-Gram at all "
                f"(docs/sparse_experts.md)")
        Kcross = cross_gram(log_theta, Xp)
    return FittedExperts(log_theta, Xp, yp, L, alpha, Kcross)


def map_query_tiles(tile_fn, Xs, chunk: int):
    """Apply `tile_fn((chunk, D)) -> (per_query_tree, reduced_tree)` over
    fixed-size query tiles with lax.map (sequential => bounded peak memory).

    per_query_tree leaves must have leading axis `chunk`; they are stitched
    along the query axis and the padding tail is stripped. reduced_tree
    leaves are combined with an elementwise max over tiles (residual
    semantics: report the worst tile).
    """
    Nt, D = Xs.shape
    n_tiles = -(-Nt // chunk)
    pad = n_tiles * chunk - Nt
    # edge-replicate the tail: padded slots duplicate the LAST REAL query, so
    # the max-reduced residuals describe the served workload, never a
    # synthetic X=0 point
    padded = jnp.pad(Xs, ((0, pad), (0, 0)), mode="edge")
    if n_tiles == 1:
        # single tile: skip the scan (lets XLA fuse across the whole batch)
        perq, reduced = tile_fn(padded)
        return jax.tree.map(lambda a: a[:Nt], perq), reduced
    perq, reduced = jax.lax.map(tile_fn, padded.reshape(n_tiles, chunk, D))
    perq = jax.tree.map(
        lambda a: a.reshape((n_tiles * chunk,) + a.shape[2:])[:Nt], perq)
    reduced = jax.tree.map(lambda a: jnp.max(a, axis=0), reduced)
    return perq, reduced


_DAC_CORES = {"poe": dec_poe_from_moments, "gpoe": dec_gpoe_from_moments,
              "bcm": dec_bcm_from_moments, "rbcm": dec_rbcm_from_moments}


class PredictionEngine:
    """Serving front-end over FittedExperts: jit-cached, query-tiled methods.

    Decentralized: poe gpoe bcm rbcm grbcm npae npae_star and the CBNN
    variants nn_poe nn_gpoe nn_bcm nn_rbcm nn_grbcm nn_npae.
    Centralized references: cen_poe cen_gpoe cen_bcm cen_rbcm cen_grbcm
    cen_npae.

    The grbcm variants additionally require `fitted_aug` (augmented experts)
    and `fitted_comm` (the communication expert as a 1-agent FittedExperts) —
    paper eq. 16-17; CBNN scores always come from the BASE local datasets
    (eq. 39 is defined on D_i).

    One compiled program per (method, query-batch geometry): repeated
    requests with the same Nt reuse the jit cache, and `chunk`-sized tiles
    bound peak memory at any Nt. Configuration attributes are baked at first
    `predict` per method — mutate the engine only through `swap_experts`
    (same-shape factor hot-swap, keeps every compiled program: the experts
    are a traced ARGUMENT of the cached jits) and `rewire` (membership /
    topology change, drops the compiled cache because A and M are baked
    into the traces).
    """

    METHODS = ("poe", "gpoe", "bcm", "rbcm", "grbcm", "npae", "npae_star",
               "nn_poe", "nn_gpoe", "nn_bcm", "nn_rbcm", "nn_grbcm",
               "nn_npae", "npae_sparse", "cen_poe", "cen_gpoe", "cen_bcm",
               "cen_rbcm", "cen_grbcm", "cen_npae")

    # exact-NPAE members that need the dense cross-Gram and therefore can
    # never serve from SparseExperts (npae_sparse is their low-rank stand-in)
    _DENSE_ONLY = ("npae", "npae_star", "nn_npae", "cen_npae")

    def __init__(self, fitted: FittedExperts, A, *, chunk: int = 256,
                 dac_iters: int = 200, jor_iters: int = 500,
                 dale_iters: int = 2000, pm_iters: int = 100,
                 eta_nn: float = 0.1, npae_jitter: float = 1e-6,
                 fitted_aug: FittedExperts | None = None,
                 fitted_comm: FittedExperts | None = None,
                 stream_mean: bool = False, degraded_tol: float = 1e-2):
        self.fitted = fitted
        self.A = A
        self.chunk = int(chunk)
        self.dac_iters = int(dac_iters)
        self.jor_iters = int(jor_iters)
        self.dale_iters = int(dale_iters)
        self.pm_iters = int(pm_iters)
        self.eta_nn = float(eta_nn)
        self.npae_jitter = float(npae_jitter)
        self.fitted_aug = fitted_aug
        self.fitted_comm = fitted_comm
        self.stream_mean = bool(stream_mean)
        self.degraded_tol = float(degraded_tol)
        self.diagnostics = False
        self._compiled: dict[str, object] = {}
        self._chaos_cache: dict = {}      # FaultPlan -> derived mask arrays
        self._trace_count = 0
        reg = default_registry()
        self._traces_total = reg.counter(
            "gp_jit_traces_total", "engine traces (compiled programs), by "
            "engine and method")
        self._degraded_total = reg.counter(
            "gp_degraded_predictions_total", "predictions served in degraded "
            "mode (dropped agents / partitions / scrubbed payloads)")
        self._diverged_total = reg.counter(
            "gp_consensus_diverged_total", "predictions that raised "
            "ConsensusDiverged (residual or finiteness guard)")
        self._scrubbed_gauge = reg.gauge(
            "gp_scrubbed_payloads", "agents with non-finite consensus "
            "payloads scrubbed in the last degraded prediction")
        self._alive_gauge = reg.gauge(
            "gp_alive_agents", "agents alive at the last degraded "
            "prediction's readout")

    # -- per-tile computation ------------------------------------------------

    def _moments(self, f, Xq):
        """Local expert moments — isinstance dispatch is what lets every
        PoE/BCM/CBNN aggregation serve dense and sparse fleets from the
        same engine (the shapes differ, the (M, Nt) contract does not)."""
        with jax.named_scope("serve/local_moments"):
            if isinstance(f, SparseExperts):
                return sparse_moments_cached(
                    f.log_theta, f.Z, f.Lmm, f.LB, f.c, Xq,
                    stream_mean=self.stream_mean)
            return local_moments_cached(f.log_theta, f.Xp, f.L, f.alpha, Xq,
                                        stream_mean=self.stream_mean)

    def _mask(self, f, Xq):
        """CBNN participation mask (eq. 39) from dense or sparse factors —
        both score forms equal sigma_f^2 - var_i, so eta_nn thresholds are
        directly comparable across expert representations."""
        with jax.named_scope("serve/local_moments"):
            if isinstance(f, SparseExperts):
                return _mask_from_scores(
                    sparse_scores(f.log_theta, f.Z, f.Lmm, f.LB, Xq),
                    self.eta_nn)
            return cbnn_mask_cached(f.log_theta, f.Xp, f.L, Xq,
                                    self.eta_nn)[0]

    def _terms(self, f: FittedExperts, Xq):
        with jax.named_scope("serve/local_moments"):
            return npae_terms_cached(f.log_theta, f.Xp, f.L, f.alpha, Xq,
                                     Kcross=f.Kcross)

    def _tile(self, method: str, f, fa, fc, Xq, chaos=None):
        A, pv = self.A, f.prior_var
        nn = method.startswith("nn_")
        base = method[3:] if nn else method
        mask = None
        if nn:
            mask = self._mask(f, Xq)
        red = {}
        dac_fn = None

        def degrade(mu, var, m):
            """Chaos payload stage: inject the plan's NaN corruption, then
            SCRUB — non-finite per-agent payloads are zeroed, excluded
            from the participation mask, and counted — so corruption can
            never reach the aggregation arithmetic silently."""
            mu = jnp.where(chaos["corrupt"][:, None], jnp.nan, mu)
            ok = jnp.isfinite(mu) & jnp.isfinite(var)
            eligible = chaos["payload"][:, None] > 0
            red["scrubbed"] = jnp.sum(jnp.any(~ok & eligible, axis=1)
                                      ).astype(mu.dtype)
            m2 = chaos["payload"][:, None] * ok.astype(mu.dtype)
            if m is not None:
                m2 = m2 * jnp.broadcast_to(m, mu.shape).astype(mu.dtype)
            return jnp.where(ok, mu, 0.0), jnp.where(ok, var, pv), m2

        if chaos is not None:
            dac_fn = lambda w0, A_, iters: dac_masked_sums(
                w0, A_, chaos["alive_seq"], chaos["readout"],
                chaos["n_relay"], edge_seq=chaos.get("edge_seq"))

        if base in _DAC_CORES:
            mu, var = self._moments(f, Xq)
            if chaos is not None:
                mu, var, mask = degrade(mu, var, mask)
            with jax.named_scope("serve/aggregate"):
                mean, v, info = _DAC_CORES[base](
                    mu, var, pv, A, iters=self.dac_iters, mask=mask,
                    dac_fn=dac_fn)
            red["dac_residual"] = info["dac_residuals"][-1]
            if self.diagnostics:
                # full per-round trajectory; max-reduced elementwise over
                # tiles (worst tile per round), so shape stays (dac_iters,)
                red["dac_residuals"] = info["dac_residuals"]
        elif base == "grbcm":
            mu_a, var_a = self._moments(fa, Xq)
            mu_c, var_c = self._moments(fc, Xq)
            if chaos is not None:
                # the communication expert is a serving-host dataset, not a
                # fleet member — only the augmented experts take faults
                mu_a, var_a, mask = degrade(mu_a, var_a, mask)
            with jax.named_scope("serve/aggregate"):
                mean, v, info = dec_grbcm_from_moments(
                    mu_a, var_a, mu_c[0], var_c[0], A, iters=self.dac_iters,
                    mask=mask, dac_fn=dac_fn)
            red["dac_residual"] = info["dac_residuals"][-1]
            if self.diagnostics:
                red["dac_residuals"] = info["dac_residuals"]
        elif method == "nn_npae":
            mu, kA, CA = self._terms(f, Xq)
            A_dale, readout = A, None
            if chaos is not None:
                mu, _, mask = degrade(mu, jnp.zeros_like(mu) + pv, mask)
                A_dale, readout = chaos["A_live"], chaos["readout"]
            with jax.named_scope("serve/aggregate"):
                mean, v, info = dec_nn_npae_from_terms(
                    mask, mu, kA, CA, pv, A_dale, dale_iters=self.dale_iters,
                    jitter=self.npae_jitter, readout=readout)
            red["dale_residual"] = info["dale_residual"]
        elif method in ("npae", "npae_star"):
            mu, kA, CA = self._terms(f, Xq)
            if chaos is not None:
                mu, _, mask = degrade(mu, jnp.zeros_like(mu) + pv, mask)
            core = (dec_npae_from_terms if method == "npae"
                    else partial(dec_npae_star_from_terms,
                                 pm_iters=self.pm_iters))
            with jax.named_scope("serve/aggregate"):
                mean, v, info = core(
                    mu, kA, CA, pv, A, jor_iters=self.jor_iters,
                    dac_iters=self.dac_iters, jitter=self.npae_jitter,
                    with_residuals=self.diagnostics, mask=mask,
                    dac_fn=dac_fn)
            red["dac_residual"] = info["dac_residuals"][-1]
            red["jor_residual"] = info["jor_residual"]
            if self.diagnostics:
                red["dac_residuals"] = info["dac_residuals"]
                red["jor_residuals"] = info["jor_residuals"]
        elif method == "npae_sparse":
            # low-rank NPAE: cross-covariance through the pseudo-points,
            # solved by the SAME aggregation core as the exact family
            with jax.named_scope("serve/local_moments"):
                mu, kA, CA = npae_terms_lowrank(f.log_theta, f.Z, f.Lmm,
                                                f.LB, f.c, Xq)
            with jax.named_scope("serve/aggregate"):
                mean, v = agg.npae(mu, kA, CA, pv, jitter=self.npae_jitter)
        elif method == "cen_npae":
            mu, kA, CA = self._terms(f, Xq)
            with jax.named_scope("serve/aggregate"):
                mean, v = agg.npae(mu, kA, CA, pv)
        elif method == "cen_grbcm":
            mu_a, var_a = self._moments(fa, Xq)
            mu_c, var_c = self._moments(fc, Xq)
            with jax.named_scope("serve/aggregate"):
                mean, v = agg.grbcm(mu_a, var_a, mu_c[0], var_c[0])
        elif method in ("cen_poe", "cen_gpoe", "cen_bcm", "cen_rbcm"):
            mu, var = self._moments(f, Xq)
            fn = getattr(agg, method[4:])
            args = (mu, var, pv) if method in ("cen_bcm", "cen_rbcm") \
                else (mu, var)
            with jax.named_scope("serve/aggregate"):
                mean, v = fn(*args)
        else:
            raise ValueError(f"unknown prediction method {method!r}")

        perq = {"mean": mean, "var": v}
        if mask is not None:
            perq["mask_t"] = mask.T                       # query axis leads
        return perq, red

    # -- serving entry point -------------------------------------------------

    def _run(self, method, f, fa, fc, Xs, chaos=None):
        # executes at TRACE time only: jit replays the compiled program on
        # cache hits without re-entering this body, so the counter advances
        # exactly once per new (method, query geometry) — the scheduler's
        # zero-recompile-after-warmup contract is asserted against it
        self._trace_count += 1
        self._traces_total.inc(engine="replicated", method=method)
        return map_query_tiles(
            lambda Xq: self._tile(method, f, fa, fc, Xq, chaos=chaos),
            Xs, self.chunk)

    @property
    def jit_cache_misses(self) -> int:
        """Number of traces so far == distinct (method, query geometry)
        pairs served. Flat across requests => every dispatch reused a
        compiled program."""
        return self._trace_count

    def set_diagnostics(self, flag: bool):
        """Toggle consensus-diagnostics capture: when on, `predict`'s info
        carries the FULL per-round DAC residual trajectory ("dac_residuals",
        worst tile per round) alongside the final scalars. The flag is
        baked into traces, so toggling drops the compiled cache — leave it
        off on serving paths and flip it for TraceRecorder runs."""
        flag = bool(flag)
        if flag != self.diagnostics:
            self.diagnostics = flag
            self._compiled.clear()

    def warm_slots(self, method: str, slots, *, input_dim: int | None = None,
                   dtype=None, fault_plan=None):
        """Pre-trace `method` for every query-batch geometry in `slots`
        so a serving scheduler packing requests into those slots never
        compiles on the request path. Pass the serving `fault_plan` to
        also warm the degraded-consensus traces it will dispatch to."""
        D = self.fitted.Xp.shape[-1] if input_dim is None else int(input_dim)
        dt = self.fitted.Xp.dtype if dtype is None else dtype
        for s in slots:
            try:
                out = self.predict(method, jnp.zeros((int(s), D), dt),
                                   fault_plan=fault_plan)
            except ConsensusDiverged:
                # the degraded trace is compiled before the host-side result
                # guard fires; a divergence on the synthetic warm batch is
                # not a serving failure
                continue
            jax.block_until_ready(out[0])

    def _chaos_arrays(self, plan):
        """Derive the traced fault arrays + degradation metadata for a
        consensus-faulty FaultPlan (host side, cached per plan).

        readout = the largest connected component of live agents at the
        final round; payload = its members that were ALSO alive at round 0
        (only they contribute local models). Passing these as traced
        ARGUMENTS keeps one compiled degraded program per (method,
        geometry) shared by every plan."""
        cached = self._chaos_cache.get(plan)
        if cached is not None:
            return cached
        M = self.fitted.num_agents
        dt = self.fitted.Xp.dtype
        alive = plan.alive_schedule(M, self.dac_iters)      # (iters, M)
        final = alive[-1] > 0.0
        if not final.any():
            raise ConsensusDiverged(
                "fault plan drops every agent before readout")
        labels = connected_components(self.A, alive=final)
        uniq, counts = np.unique(labels[final], return_counts=True)
        comp = final & (labels == uniq[np.argmax(counts)])  # ties -> lowest
        payload = (alive[0] > 0.0) & comp
        if not payload.any():
            raise ConsensusDiverged(
                "no surviving agent holds a round-0 payload")
        # live-subgraph adjacency for DALE (nn_npae), with self-loops on
        # EVERY zero-degree node (dead ones too): avg = (A@Q)/deg must stay
        # finite everywhere — a single NaN row poisons the matmul (0*NaN)
        A_live = np.asarray(self.A, dtype=np.float64) * np.outer(final, final)
        iso = np.flatnonzero(A_live.sum(axis=1) == 0)
        A_live[iso, iso] = 1.0
        chaos = {
            "alive_seq": jnp.asarray(alive, dt),
            "readout": jnp.asarray(comp, dt),
            "payload": jnp.asarray(payload, dt),
            "corrupt": jnp.asarray(plan.corrupt_mask(M)),
            "n_relay": jnp.asarray(float(payload.sum()), dt),
            "A_live": jnp.asarray(A_live, dt),
        }
        edge = plan.edge_schedule(M, self.dac_iters)
        if edge is not None:
            chaos["edge_seq"] = jnp.asarray(edge, dt)
        meta = {"degraded": True,
                "alive_agents": int(final.sum()),
                "excluded_agents": int(M - payload.sum()),
                "n_components": int(uniq.size)}
        self._chaos_cache[plan] = (chaos, meta)
        return chaos, meta

    def predict(self, method: str, Xs, fault_plan=None):
        """Serve one query batch -> (mean (Nt,), var (Nt,), info).

        info carries the worst-tile consensus residuals, and the CBNN mask
        (M, Nt) for nn_* methods.

        `fault_plan` (repro.chaos.FaultPlan) injects the plan's consensus
        faults and serves over the surviving subgraph. The result is then
        either honestly DEGRADED — finite, computed over the largest live
        component, flagged with info["degraded"]=True and the component
        census — or a typed `ConsensusDiverged` (non-finite output, or a
        consensus residual above `degraded_tol`); never silently wrong.
        A consensus-free plan (stragglers/fail-injection only) dispatches
        to the exact traces: bitwise identical to fault_plan=None.
        """
        if method not in self.METHODS:
            raise ValueError(f"unknown prediction method {method!r}; "
                             f"one of {self.METHODS}")
        if ("grbcm" in method and (self.fitted_aug is None
                                   or self.fitted_comm is None)):
            raise ValueError("grbcm methods need fitted_aug and fitted_comm")
        sparse = isinstance(self.fitted, SparseExperts)
        if sparse and method in self._DENSE_ONLY:
            raise ValueError(
                f"{method} needs the dense O(M^2 Ni^2) cross-Gram and is "
                f"not servable from sparse pseudo-representation experts; "
                f"use 'npae_sparse' (the low-rank NPAE path)")
        if method == "npae_sparse" and not sparse:
            raise ValueError(
                "npae_sparse serves from SparseExperts only — fit with "
                "FleetConfig(sparse_m=...) (or fit_sparse_experts) to build "
                "the pseudo-representation factors")
        chaos = meta = None
        if fault_plan is not None and not fault_plan.consensus_free:
            if method.startswith("cen_"):
                raise ValueError(
                    f"{method}: centralized references do not run consensus "
                    f"and cannot serve a fault plan with consensus faults")
            if method == "npae_sparse":
                raise ValueError(
                    "npae_sparse runs exact collectives (no averaging "
                    "consensus) and cannot serve a fault plan with "
                    "consensus faults")
            chaos, meta = self._chaos_arrays(fault_plan)
        run = self._compiled.get(method)
        if run is None:
            run = jax.jit(partial(self._run, method))
            self._compiled[method] = run
        if chaos is None:
            perq, red = run(self.fitted, self.fitted_aug, self.fitted_comm,
                            Xs)
        else:
            perq, red = run(self.fitted, self.fitted_aug, self.fitted_comm,
                            Xs, chaos)
        info = dict(red)
        mask_t = perq.pop("mask_t", None)
        if mask_t is not None:
            info["mask"] = mask_t.T
        mean, var = perq["mean"], perq["var"]
        if chaos is not None:
            scrubbed = int(info.pop("scrubbed", 0))
            # guard the NETWORK consensus residuals (DAC/DALE) — the part
            # degradation perturbs. The per-query JOR solve is the same
            # masked math as the exact path and its residual is
            # data-scale-dependent; it stays reported in info, unguarded.
            residual = max((float(info[k]) for k in
                            ("dac_residual", "dale_residual") if k in info),
                           default=0.0)
            finite = (bool(np.isfinite(np.asarray(mean)).all())
                      and bool(np.isfinite(np.asarray(var)).all()))
            if not finite or not np.isfinite(residual) \
                    or residual > self.degraded_tol:
                self._diverged_total.inc(method=method)
                raise ConsensusDiverged(
                    f"{method}: degraded consensus did not converge "
                    f"(residual={residual:.3e}, tol={self.degraded_tol:.1e},"
                    f" finite={finite}) under fault plan {fault_plan!r}")
            self._degraded_total.inc(method=method)
            self._scrubbed_gauge.set(scrubbed)
            self._alive_gauge.set(meta["alive_agents"])
            info.update(meta)
            info["scrubbed_agents"] = scrubbed
        return mean, var, info

    def swap_experts(self, fitted: FittedExperts,
                     fitted_aug: FittedExperts | None = None,
                     fitted_comm: FittedExperts | None = None):
        """Hot-swap the served factors WITHOUT recompilation.

        The experts pytree is an argument of every compiled program, so a
        same-structure, same-shape replacement (the streaming case:
        `OnlineExperts.to_fitted()` after observe/evict events) reuses the
        jit cache. Raises if the structure/shapes changed — that is a
        membership change; use `rewire`.
        """
        def spec(t):
            leaves, treedef = jax.tree.flatten(t)
            return treedef, [(a.shape, jnp.asarray(a).dtype) for a in leaves]

        for name, new, old in (("fitted", fitted, self.fitted),
                               ("fitted_aug", fitted_aug, self.fitted_aug),
                               ("fitted_comm", fitted_comm,
                                self.fitted_comm)):
            if new is None:
                continue
            if old is not None and spec(new) != spec(old):
                raise ValueError(
                    f"swap_experts: {name} structure/shapes changed (agent "
                    f"membership or window geometry) — use rewire()")
        self.fitted = fitted
        if fitted_aug is not None:
            self.fitted_aug = fitted_aug
        if fitted_comm is not None:
            self.fitted_comm = fitted_comm

    def rewire(self, A, fitted: FittedExperts | None = None,
               fitted_aug: FittedExperts | None = None,
               fitted_comm: FittedExperts | None = None):
        """Apply a membership/topology change (core.online.join / leave):
        new adjacency and optionally a new fleet. Drops every compiled
        program — the consensus protocols bake A (and M) into the trace,
        so this is also what re-syncs DAC/JOR/DALE to the new graph."""
        experts = fitted if fitted is not None else self.fitted
        if experts.num_agents != A.shape[0]:
            raise ValueError(
                f"rewire: {experts.num_agents} fitted agents vs "
                f"adjacency for {A.shape[0]}")
        self.A = A
        if fitted is not None:
            self.fitted = fitted
        if fitted_aug is not None:
            self.fitted_aug = fitted_aug
        if fitted_comm is not None:
            self.fitted_comm = fitted_comm
        self._compiled.clear()
        self._chaos_cache.clear()   # masks/readout are derived from A and M

    def posterior_means_streamed(self, Xs):
        """Per-agent streamed posterior means (M, Nt) via the fused
        Gram-matvec kernel — the O(Ni + Nt) mean-only hot path."""
        f = self.fitted
        w = f.c if isinstance(f, SparseExperts) else f.alpha
        return stream_means(f.log_theta, f.Xp, w, Xs)
