"""GPFleet: the single agent-facing facade for the whole fleet lifecycle.

    cfg = FleetConfig(num_agents=8, trainer="dec-apx", method="rbcm")
    fleet = GPFleet(cfg).fit(Xp, yp)        # ADMM training + factor caching
    mean, var, info = fleet.predict(Xs)     # jit-cached, query-tiled serving
    fleet.save("ckpt/")                     # fitted factors + config + graph
    ...
    fleet = GPFleet.load("ckpt/")           # fresh process: serve WITHOUT
    mean2, var2, _ = fleet.predict(Xs)      # refitting, bit-identical

Lifecycle verbs and the subsystems they drive (all pre-existing — the
facade adds dispatch and state management, never new numerics):

  fit()        trainer registry -> the §4 ADMM loops -> `fit_experts`
               (grBCM communication/augmented datasets built when the
               trainer or method needs them; `config.sparse_m` caches
               O(Ni m^2) sparse pseudo-representations — `core.sparse` —
               instead of the dense O(Ni^2) factors)
  predict()    method registry -> `PredictionEngine` (replicated) /
               `ShardedEngine` (agent-sharded mesh; `predict_routed` when
               config.routed) — compiled programs cached per method
  observe()    `core.online` sliding-window experts: O(W^2) rank-1 factor
               updates hot-swapped into the engine, zero recompiles
               (`evict_oldest()` drops one agent's oldest point; both
               count `gp_online_evictions_total`)
  join()/leave()  dynamic membership: window state + consensus graph +
               engine rewire in one step
  shard()      move a fitted fleet onto the agent-sharded engine in place
  save()/load()   `checkpoint.io` round trip of FittedExperts + FleetConfig
               + consensus graph (+ online window state)
  to_server()  a one-tenant `launch.scheduler.ServingScheduler` over this
               fleet (continuous slot batching; `submit`/`stats` keep the
               v1 FrontDoor surface). Multi-tenant serving registers many
               fleets on one scheduler via `ServingScheduler.add_fleet`.
  metrics()    `repro.obs` default-registry snapshot + a fleet-shape block
               (docs/observability.md); `fit(trace=TraceRecorder())`
               records per-iteration training diagnostics the same way.

Capability validation happens at CONSTRUCTION (fleet/registry.py
`validate_config`): a sharded NPAE-family fleet or a routed non-nn_* fleet
is rejected with a clear error before any array work.
"""
from __future__ import annotations

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.io import restore, save_checkpoint
from ..obs import default_registry
from ..core.consensus import (complete_graph, connected_components,
                              cycle_graph, is_connected, path_graph,
                              random_connected_graph)
from ..core.gp import augment, communication_dataset, pack
from ..core.online import (OnlineExperts, evict_oldest, from_batch, join,
                           leave, observe_fleet, refit)
from ..core.prediction import (FittedExperts, PredictionEngine, ShardedEngine,
                               fit_experts)
from ..core.sparse import (SparseExperts, fit_sparse_experts,
                           select_inducing)
from ..launch.scheduler import ServingScheduler
from .config import FleetConfig
from .registry import get_method, get_trainer, validate_config

_FLEET_MANIFEST = "fleet.json"
_FORMAT_VERSION = 1


class FleetDegraded(RuntimeError):
    """A prediction came back in DEGRADED mode (dropped agents, network
    partition, scrubbed payloads) and the caller did not opt in with
    `predict(..., allow_degraded=True)`. The degradation census is on
    `.info`; the (finite, flagged) result itself is on `.result`."""

    def __init__(self, message: str, info: dict | None = None,
                 result=None):
        super().__init__(message)
        self.info = info or {}
        self.result = result


def _build_graph(cfg: FleetConfig):
    if cfg.graph == "path":
        return path_graph(cfg.num_agents)
    if cfg.graph == "cycle":
        return cycle_graph(cfg.num_agents)
    if cfg.graph == "complete":
        return complete_graph(cfg.num_agents)
    return random_connected_graph(cfg.num_agents, cfg.graph_p,
                                  seed=cfg.graph_seed)


class GPFleet:
    """Config-driven facade over training, serving, streaming, persistence.

    Construction validates the config against the registries and builds the
    consensus graph; `fit` (or `load`) populates the fitted state; every
    serving verb dispatches through the lazily built engine. The underlying
    engines/free functions remain public — the facade is sugar plus
    lifecycle glue, not a wall.
    """

    def __init__(self, config: FleetConfig | None = None, *, A=None,
                 mesh=None):
        cfg = config if config is not None else FleetConfig()
        validate_config(cfg)
        self.config = cfg
        self.A = A if A is not None else _build_graph(cfg)
        if self.A.shape[0] != cfg.num_agents:
            raise ValueError(f"adjacency for {self.A.shape[0]} agents vs "
                             f"config.num_agents={cfg.num_agents}")
        self.mesh = mesh
        # fitted state (populated by fit / load)
        self.log_theta = None          # consensus hyperparameters (K,)
        self.thetas = None             # per-agent trained thetas (M, K)
        self.train_info = None
        self.fitted: FittedExperts | None = None
        self.fitted_aug: FittedExperts | None = None
        self.fitted_comm: FittedExperts | None = None
        self._online_state: OnlineExperts | None = None
        # host mirror of the window counts: the evictions are counted on
        # the host, with no device read on the event path
        self._counts_host: np.ndarray | None = None
        self._comm_data = None         # (Xc, yc, Xa, ya) when built
        self._engine = None
        self._ingest = None
        self._evict = None
        self._last_degraded = None     # census of the last degraded predict

    # -- properties ----------------------------------------------------------

    @property
    def num_agents(self) -> int:
        return self.config.num_agents

    @property
    def is_fitted(self) -> bool:
        return self.fitted is not None

    @property
    def window_counts(self):
        """(M,) real observations per agent's sliding window, or None for
        batch (non-online) fleets."""
        return None if self._online_state is None \
            else self._online_state.count

    @property
    def engine(self):
        """The serving engine (built on first use, cached until the fleet
        changes shape: refit, shard, rewire)."""
        if self._engine is None:
            self._engine = self._build_engine()
        return self._engine

    def _require_fitted(self, verb: str):
        if self.fitted is None:
            raise RuntimeError(f"{verb} needs a fitted fleet — call fit() "
                               f"or load() first")

    # -- fit -----------------------------------------------------------------

    def _needs_comm_data(self, train: bool) -> bool:
        """Communication/augmented datasets are built only when consumed:
        by an augmented-data trainer that will actually run, or by a
        grbcm-family serving method."""
        return ((train and get_trainer(self.config.trainer)
                 .needs_augmented_data)
                or get_method(self.config.method).needs_augmented_data)

    def _build_comm_data(self, Xp, yp, key):
        Xc, yc = communication_dataset(key, Xp, yp)
        Xa, ya = augment(Xp, yp, Xc, yc)
        self._comm_data = (Xc, yc, Xa, ya)
        return self._comm_data

    def fit(self, Xp, yp, *, key=None, log_theta0=None, grad_fn=None,
            train: bool = True, trace=None) -> "GPFleet":
        """Train hyperparameters (trainer registry) and cache the serving
        factors. Returns self (chainable).

        Xp (M, Ni, D), yp (M, Ni) — M must equal config.num_agents.
        `key` seeds the grBCM communication dataset when the trainer or
        method needs one (default PRNGKey(0): deterministic).
        `train=False` skips training and serves from `log_theta0` (default:
        config.theta0) — the "true hyperparameters known" scenario.
        `trace` (a `repro.obs.TraceRecorder`) switches the trainer's
        diagnostics mode on (`diag=True`: per-iteration NLL, primal/dual
        residuals, theta trajectory carried through the scan) and records
        the resulting info dict on the recorder after the fit.
        """
        cfg = self.config
        Xp, yp = jnp.asarray(Xp), jnp.asarray(yp)
        if Xp.shape[0] != cfg.num_agents:
            raise ValueError(
                f"data for {Xp.shape[0]} agents vs config.num_agents="
                f"{cfg.num_agents}; set FleetConfig(num_agents=...) to the "
                f"fleet you partitioned")
        if Xp.shape[-1] != cfg.input_dim:
            raise ValueError(f"data input_dim {Xp.shape[-1]} vs config."
                             f"input_dim={cfg.input_dim}")
        if key is None:
            key = jax.random.PRNGKey(0)
        lt0 = jnp.asarray(log_theta0) if log_theta0 is not None else pack(
            list(cfg.theta0[:-2]), cfg.theta0[-2],
            cfg.theta0[-1]).astype(Xp.dtype)

        spec = get_trainer(cfg.trainer)
        Xc = yc = Xa = ya = None
        if self._needs_comm_data(train):
            Xc, yc, Xa, ya = self._build_comm_data(Xp, yp, key)
        if not train:
            self.log_theta = lt0
            self.thetas = jnp.broadcast_to(
                lt0, (cfg.num_agents, lt0.shape[0]))
            self.train_info = {}
        else:
            Xt, yt = (Xa, ya) if spec.needs_augmented_data else (Xp, yp)
            self.log_theta, self.thetas, self.train_info = spec.run(
                cfg, lt0, Xt, yt, self.A, mesh=self.mesh, grad_fn=grad_fn,
                diag=trace is not None)
            if trace is not None:
                trace.record(cfg.trainer, self.train_info,
                             num_agents=cfg.num_agents, method=cfg.method)
        self._cache_factors(Xp, yp)
        return self

    def _fit_sparse(self, lt, Xp, yp, Z=None):
        cfg = self.config
        if Z is None:
            Z = select_inducing(Xp, cfg.sparse_m, cfg.inducing_init)
        return jax.jit(partial(fit_sparse_experts,
                               jitter=cfg.jitter))(lt, Xp, yp, Z)

    def _cache_factors(self, Xp, yp):
        """Factorize the trained fleet once (fit_experts / sparse
        pseudo-representations / online windows) and invalidate the
        engine."""
        cfg, lt = self.config, self.log_theta
        if cfg.online:
            self._online_state = from_batch(lt, Xp, yp, window=cfg.window,
                                            jitter=cfg.jitter)
            self._counts_host = np.array(self._online_state.count)
            self.fitted = self._online_state.to_fitted()
        elif cfg.sparse_m is not None:
            # the fact-sparse trainer jointly optimized the inducing sets;
            # reuse THOSE so serving sees the Z the bound was tightened over
            Z = self.train_info.get("Z") \
                if isinstance(self.train_info, dict) else None
            self.fitted = self._fit_sparse(lt, Xp, yp, Z)
        else:
            self.fitted = jax.jit(partial(
                fit_experts, jitter=cfg.jitter,
                cache_cross=cfg.cache_cross))(lt, Xp, yp)
        if get_method(cfg.method).needs_augmented_data:
            Xc, yc, Xa, ya = self._comm_data
            if cfg.sparse_m is not None:
                self.fitted_aug = self._fit_sparse(lt, Xa, ya)
                self.fitted_comm = self._fit_sparse(lt, Xc[None], yc[None])
            else:
                self.fitted_aug = jax.jit(fit_experts)(lt, Xa, ya)
                self.fitted_comm = jax.jit(fit_experts)(
                    lt, Xc[None], yc[None])
        self._engine = None

    # -- serving -------------------------------------------------------------

    def _build_engine(self):
        self._require_fitted("serving")
        cfg = self.config
        if cfg.sharded:
            if self.mesh is None:
                from ..launch.mesh import make_agent_mesh
                self.mesh = make_agent_mesh(cfg.num_agents,
                                            max_devices=cfg.max_shard_devices)
            return ShardedEngine(self.fitted, self.mesh, chunk=cfg.chunk,
                                 dac_iters=cfg.dac_iters, eta_nn=cfg.eta_nn,
                                 consensus=cfg.consensus,
                                 npae_jitter=cfg.npae_jitter,
                                 fitted_aug=self.fitted_aug,
                                 fitted_comm=self.fitted_comm,
                                 stream_mean=cfg.stream_mean)
        return PredictionEngine(self.fitted, self.A, chunk=cfg.chunk,
                                dac_iters=cfg.dac_iters,
                                jor_iters=cfg.jor_iters,
                                dale_iters=cfg.dale_iters,
                                pm_iters=cfg.pm_iters, eta_nn=cfg.eta_nn,
                                npae_jitter=cfg.npae_jitter,
                                fitted_aug=self.fitted_aug,
                                fitted_comm=self.fitted_comm,
                                stream_mean=cfg.stream_mean)

    def predict(self, Xs, method: str | None = None, *, fault_plan=None,
                allow_degraded: bool = False):
        """Serve one query batch -> (mean (Nt,), var (Nt,), info).

        `method` overrides config.method for this call (must satisfy the
        same capability constraints); `cen_*` centralized references pass
        through to the replicated engine.

        `fault_plan` (repro.chaos.FaultPlan) injects the plan's consensus
        faults: the engine serves over the surviving subgraph and flags the
        result with info["degraded"]=True (see PredictionEngine.predict).
        Degraded results are returned only under `allow_degraded=True`;
        otherwise the (finite, flagged) result is wrapped in a typed
        `FleetDegraded` so a caller can never mistake a partial-fleet
        answer for a healthy one. Consensus divergence always raises
        `ConsensusDiverged` regardless of `allow_degraded`.
        """
        self._require_fitted("predict")
        cfg = self.config
        method = method if method is not None else cfg.method
        method = method.replace("-", "_")   # CLI convention ("npae-sparse")
        if fault_plan is not None and not fault_plan.consensus_free \
                and cfg.sharded:
            raise ValueError(
                "fault plans with consensus faults serve on the replicated "
                "engine only (ShardedEngine consensus runs on the device "
                "ring, which has no degraded mode)")
        if not method.startswith("cen_"):
            spec = get_method(method)
            if (cfg.sharded and not spec.shardable) or \
                    (cfg.sparse_m is not None and not spec.sparse) or \
                    (spec.family == "sparse" and cfg.sparse_m is None):
                validate_config(cfg.replace(method=method))  # clear error
            if spec.needs_augmented_data and self.fitted_aug is None:
                raise ValueError(
                    f"method {method!r} needs the grBCM augmented/"
                    f"communication experts; fit with "
                    f"FleetConfig(method={method!r}) so they are built")
        else:
            if cfg.sharded:
                raise ValueError("centralized cen_* references serve on "
                                 "the replicated engine only")
            if "grbcm" in method and self.fitted_aug is None:
                raise ValueError(
                    f"method {method!r} needs the grBCM augmented/"
                    f"communication experts; fit with a grbcm method "
                    f"configured so they are built")
        if cfg.routed and method.startswith("nn_"):
            return self.engine.predict_routed(method, Xs)
        if fault_plan is None:
            return self.engine.predict(method, Xs)
        mean, var, info = self.engine.predict(method, Xs,
                                              fault_plan=fault_plan)
        if info.get("degraded"):
            self._last_degraded = {k: info[k] for k in
                                   ("alive_agents", "excluded_agents",
                                    "n_components", "scrubbed_agents")}
            if not allow_degraded:
                raise FleetDegraded(
                    f"prediction served in degraded mode "
                    f"({info['alive_agents']}/{self.num_agents} agents "
                    f"alive, {info['scrubbed_agents']} scrubbed) — pass "
                    f"allow_degraded=True to accept flagged partial-fleet "
                    f"results", info=info, result=(mean, var))
        return mean, var, info

    def shard(self, mesh=None, *, routed: bool | None = None) -> "GPFleet":
        """Move serving onto the agent-sharded engine (in place).

        Validates method capability first; `routed` switches CBNN query
        routing on/off at the same time. Returns self.
        """
        cfg = self.config.replace(
            sharded=True,
            routed=self.config.routed if routed is None else routed)
        validate_config(cfg)
        self.config = cfg
        if mesh is not None:
            self.mesh = mesh
        self._engine = None
        return self

    def slot_geometry(self, method: str | None = None) -> tuple[int, int]:
        """(align, max_slot) for serving schedulers packing this fleet:
        slots are multiples of the engine chunk up to the method registry's
        `max_slot` capability (NPAE-family per-query (M, M) solves cap out
        earlier than the flat-tiling DAC family)."""
        cfg = self.config
        method = method if method is not None else cfg.method
        base = method[4:] if method.startswith("cen_") else method
        return int(cfg.chunk), int(get_method(base).max_slot)

    @property
    def jit_cache_misses(self) -> int:
        """The serving engine's trace count (distinct compiled programs).
        Flat across requests => zero recompiles; 0 before first serve."""
        return 0 if self._engine is None else self._engine.jit_cache_misses

    def health(self) -> dict:
        """Point-in-time fleet health: shape, consensus-graph connectivity,
        degraded/diverged serving totals (from the engine's `repro.obs`
        counters), and the census of the last degraded prediction. Cheap —
        host-side graph analysis only, no device work — and safe to poll
        from a watchdog or a /healthz handler."""
        labels = connected_components(self.A)
        h = {
            "num_agents": self.num_agents,
            "is_fitted": self.is_fitted,
            "sharded": self.config.sharded,
            "graph_connected": bool(is_connected(self.A)),
            "graph_components": int(len(set(labels.tolist()))),
            "degraded_predictions": 0.0,
            "diverged_predictions": 0.0,
            "last_degraded": self._last_degraded,
        }
        eng = self._engine
        if eng is not None and hasattr(eng, "_degraded_total"):
            h["degraded_predictions"] = sum(
                v for _, v in eng._degraded_total.collect())
            h["diverged_predictions"] = sum(
                v for _, v in eng._diverged_total.collect())
        return h

    def metrics(self) -> dict:
        """Observability snapshot: the process-wide `repro.obs` default
        registry (counters/gauges/histograms — serving schedulers and the
        engines' trace counters write here when metrics are enabled) plus a
        `fleet` block describing THIS fleet (shape, method, engine trace
        count). Prometheus-format export of the same registry comes from
        `repro.obs.prometheus_text()` / `serve_gp --metrics-port`."""
        snap = default_registry().snapshot()
        snap["fleet"] = {
            "num_agents": self.config.num_agents,
            "trainer": self.config.trainer,
            "method": self.config.method,
            "sharded": self.config.sharded,
            "is_fitted": self.is_fitted,
            "jit_cache_misses": self.jit_cache_misses,
        }
        return snap

    def to_server(self, batch: int = 256, *, max_wait_ms: float = 2.0,
                  method: str | None = None, queue_depth: int = 1024,
                  continuous: bool = True, warm: bool = True,
                  admission: str = "block", deadline_policy: str = "drop"
                  ) -> ServingScheduler:
        """A started one-tenant `ServingScheduler` over this fleet: submit
        (Nq, D) requests, get Futures of (mean, var); use as a context
        manager to drain on exit. `continuous=True` (default) serves the
        quantized slot ladder up to `batch` rows — partial loads run
        right-sized compiled programs; `continuous=False` reproduces the
        v1 fixed-batch FrontDoor geometry. `warm=True` pre-compiles every
        slot so the request path never traces."""
        self._require_fitted("to_server")
        sched = ServingScheduler(max_wait_ms=max_wait_ms)
        sched.add_fleet("default", self, method=method, max_slot=int(batch),
                        continuous=continuous, queue_depth=queue_depth,
                        admission=admission, deadline_policy=deadline_policy,
                        warm=warm)
        return sched

    # -- streaming / membership ----------------------------------------------

    def _require_online(self, verb: str) -> OnlineExperts:
        self._require_fitted(verb)
        if self._online_state is None:
            raise RuntimeError(
                f"{verb} needs a streaming fleet — construct with "
                f"FleetConfig(online=True) before fit()")
        return self._online_state

    def observe(self, xs, ys) -> "GPFleet":
        """Ingest one observation per agent (xs (M, D), ys (M,)) through the
        O(W^2) rank-1 factor updates and hot-swap the engine's served
        factors — zero recompiles. Every agent whose window is full evicts
        its oldest point first (counted in `gp_online_evictions_total`).
        Returns self."""
        state = self._require_online("observe")
        if self._ingest is None:
            self._ingest = jax.jit(observe_fleet)
        self._online_state = self._ingest(state, xs, ys)
        self._count_evictions(int(np.sum(self._counts_host >= state.window)))
        self._counts_host = np.minimum(self._counts_host + 1, state.window)
        self._swap_online()
        return self

    def evict_oldest(self, agent: int) -> "GPFleet":
        """Agent `agent` drops its oldest observation (a no-op on an empty
        window) and the engine's served factors are hot-swapped. Returns
        self."""
        state = self._require_online("evict_oldest")
        agent = int(agent)
        if not 0 <= agent < state.num_agents:
            raise ValueError(f"agent {agent} not in fleet of "
                             f"{state.num_agents}")
        if self._evict is None:
            self._evict = jax.jit(evict_oldest)
        self._online_state = self._evict(state, agent)
        if self._counts_host[agent] > 0:
            self._count_evictions(1)
            self._counts_host[agent] -= 1
        self._swap_online()
        return self

    @staticmethod
    def _count_evictions(n: int):
        if n:
            default_registry().counter(
                "gp_online_evictions_total",
                "agent-events that evicted a window's oldest point "
                "(observe on a full window, evict_oldest)").inc(n)

    def _swap_online(self):
        self.fitted = self._online_state.to_fitted()
        if self._engine is not None:
            self._engine.swap_experts(self.fitted)

    def drift(self, *, grad_fn=None, iters: int | None = None) -> dict:
        """Re-run the configured decentralized trainer on the LIVE sliding
        windows and hot-swap the retrained factors into the serving engine
        — the drift-adaptation loop: stream with `observe`, periodically
        `drift` so the hyperparameters track the data the windows hold now.

        Training uses the filled window prefix shared by every agent
        (`min(window_counts)` observations; sentinel slots never enter the
        likelihood), warm-starts from the current consensus theta, and
        `iters` caps this epoch's ADMM budget (default config.admm_iters).
        The refreshed window factors are refit at the new theta and swapped
        in place (`swap_experts`): same shapes, ZERO recompiles — serving
        never retraces across a drift epoch. Returns the trainer info dict.
        """
        state = self._require_online("drift")
        n = int(np.min(self._counts_host))
        if n < 2:
            raise RuntimeError(
                f"drift needs >= 2 observations in every agent's window "
                f"(min count is {n}) — stream more data with observe() "
                f"first")
        spec = get_trainer(self.config.trainer)
        if spec.needs_augmented_data:
            raise ValueError(
                f"trainer {self.config.trainer!r} needs augmented/"
                f"communication datasets, which sliding windows do not "
                f"carry — streaming fleets drift with a plain-data trainer")
        cfg = self.config if iters is None \
            else self.config.replace(admm_iters=int(iters))
        Xt, yt = state.Xw[:, :n], state.yw[:, :n]
        self.log_theta, self.thetas, info = spec.run(
            cfg, self.log_theta, Xt, yt, self.A, mesh=self.mesh,
            grad_fn=grad_fn)
        self._online_state = refit(state._replace(
            log_theta=self.log_theta.astype(state.log_theta.dtype)))
        self._swap_online()             # the window counts are unchanged
        return info

    def join(self, X_new=None, y_new=None, neighbors=None) -> "GPFleet":
        """One agent joins the streaming fleet (window seeded from X_new /
        y_new); consensus graph attached, engine re-traced on the new M."""
        state = self._require_online("join")
        if self.config.sharded:
            raise ValueError("membership changes serve on the replicated "
                             "engine (ShardedEngine shards are fixed at "
                             "construction)")
        self._online_state, self.A = join(state, self.A, X_new, y_new,
                                          neighbors=neighbors)
        n_new = 0 if X_new is None else min(len(X_new), state.window)
        self._counts_host = np.append(self._counts_host, n_new)
        self._after_membership_change()
        return self

    def leave(self, agent: int) -> "GPFleet":
        """Agent `agent` leaves; former neighbors are re-chained so the
        consensus graph stays connected."""
        state = self._require_online("leave")
        if self.config.sharded:
            raise ValueError("membership changes serve on the replicated "
                             "engine (ShardedEngine shards are fixed at "
                             "construction)")
        self._online_state, self.A = leave(state, self.A, agent)
        self._counts_host = np.delete(self._counts_host, int(agent))
        self._after_membership_change()
        return self

    def _after_membership_change(self):
        self.fitted = self._online_state.to_fitted()
        self.config = self.config.replace(
            num_agents=self._online_state.num_agents)
        if self._engine is not None:
            self._engine.rewire(self.A, fitted=self.fitted)

    # -- persistence ---------------------------------------------------------

    def _state_tree(self):
        tree = {"A": self.A, "log_theta": self.log_theta,
                "thetas": self.thetas, "fitted": self.fitted}
        if self.fitted_aug is not None:
            tree["fitted_aug"] = self.fitted_aug
        if self.fitted_comm is not None:
            tree["fitted_comm"] = self.fitted_comm
        if self._online_state is not None:
            tree["count"] = self._online_state.count
            tree["jitter"] = self._online_state.jitter
        return tree

    def save(self, ckpt_dir: str, step: int = 0) -> str:
        """Persist the fitted fleet: factors + config + consensus graph (+
        online window state). A fresh process `GPFleet.load`s it and serves
        bit-identical predictions WITHOUT refitting."""
        self._require_fitted("save")
        tree = self._state_tree()
        path = save_checkpoint(ckpt_dir, step, tree)
        # leaf shapes/dtypes live in checkpoint.io's manifest.json (written
        # by save_checkpoint above); fleet.json adds only what io cannot
        # know — the config and which optional components exist
        manifest = {
            "format": _FORMAT_VERSION,
            "config": self.config.to_dict(),
            "step": step,
            "components": {
                "fitted_aug": self.fitted_aug is not None,
                "fitted_comm": self.fitted_comm is not None,
                "fitted_kcross": self.fitted.Kcross is not None,
                "aug_kcross": (self.fitted_aug is not None
                               and self.fitted_aug.Kcross is not None),
                "online": self._online_state is not None,
                "sparse": isinstance(self.fitted, SparseExperts),
                "aug_sparse": isinstance(self.fitted_aug, SparseExperts),
                "comm_sparse": isinstance(self.fitted_comm, SparseExperts),
            },
        }
        # atomic publish: fleet.json is the load() entry point, so it is
        # written LAST and via tmp+rename — a crash mid-save leaves either
        # the previous complete checkpoint or a directory load() rejects,
        # never a half-written manifest over fresh arrays
        mpath = os.path.join(ckpt_dir, _FLEET_MANIFEST)
        tmp = mpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, mpath)
        return path

    @staticmethod
    def _template(ckpt_dir: str, manifest) -> dict:
        """ShapeDtypeStruct tree matching the saved state — what
        checkpoint.io.restore validates the stored leaves against.

        The tree STRUCTURE comes from fleet.json's component map; the leaf
        shapes/dtypes come from checkpoint.io's manifest.json (the single
        copy of the leaf specs, written by save_checkpoint)."""
        comp = manifest["components"]
        with open(os.path.join(ckpt_dir, "manifest.json")) as f:
            io_manifest = json.load(f)
        if io_manifest.get("step") != manifest["step"]:
            raise ValueError(
                f"checkpoint manifests disagree: fleet.json is for step "
                f"{manifest['step']} but manifest.json describes step "
                f"{io_manifest.get('step')} (mixed checkpoint directory?)")

        def fe(kcross):
            return FittedExperts(0, 0, 0, 0, 0, Kcross=0 if kcross else None)

        def se():
            return SparseExperts(0, 0, 0, 0, 0, 0)

        # sparse flags default False: checkpoints written before the sparse
        # subsystem load unchanged
        tree = {"A": 0, "log_theta": 0, "thetas": 0,
                "fitted": se() if comp.get("sparse", False)
                else fe(comp["fitted_kcross"])}
        if comp["fitted_aug"]:
            tree["fitted_aug"] = se() if comp.get("aug_sparse", False) \
                else fe(comp["aug_kcross"])
        if comp["fitted_comm"]:
            tree["fitted_comm"] = se() if comp.get("comm_sparse", False) \
                else fe(False)
        if comp["online"]:
            tree["count"] = 0
            tree["jitter"] = 0
        paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = io_manifest["leaves"]
        leaves = []
        for kp, _ in paths:
            key = jax.tree_util.keystr(kp)
            if key not in specs:
                raise ValueError(f"checkpoint manifest is missing leaf "
                                 f"{key!r} (corrupted or truncated "
                                 f"checkpoint?)")
            leaves.append(jax.ShapeDtypeStruct(
                tuple(specs[key]["shape"]), specs[key]["dtype"]))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    @classmethod
    def load(cls, ckpt_dir: str, *, mesh=None, config: FleetConfig | None
             = None) -> "GPFleet":
        """Reconstruct a fitted fleet from `save()` output: no refitting,
        served predictions are bit-identical to the saving process.

        `config` overrides the persisted config (e.g. flip `sharded=True`
        to serve a replicated-saved fleet on a mesh) — overrides are
        validated against the registries like any other config.
        """
        mpath = os.path.join(ckpt_dir, _FLEET_MANIFEST)
        if not os.path.exists(mpath):
            raise FileNotFoundError(
                f"{mpath!r} not found — not a GPFleet.save() checkpoint")
        with open(mpath) as f:
            manifest = json.load(f)
        if manifest.get("format", 0) > _FORMAT_VERSION:
            raise ValueError(
                f"fleet checkpoint format {manifest['format']} is newer "
                f"than this code ({_FORMAT_VERSION})")
        saved_cfg = FleetConfig.from_dict(manifest["config"])
        cfg = config if config is not None else saved_cfg
        tree = restore(ckpt_dir, cls._template(ckpt_dir, manifest),
                       step=manifest["step"])
        tree = jax.tree.map(jnp.asarray, tree)
        fleet = cls(cfg, A=tree["A"], mesh=mesh)
        fleet.log_theta = tree["log_theta"]
        fleet.thetas = tree["thetas"]
        fleet.train_info = {}
        fleet.fitted = tree["fitted"]
        fleet.fitted_aug = tree.get("fitted_aug")
        fleet.fitted_comm = tree.get("fitted_comm")
        if manifest["components"]["online"]:
            f = fleet.fitted
            fleet._online_state = OnlineExperts(
                f.log_theta, f.Xp, f.yp, f.L, f.alpha, tree["count"],
                tree["jitter"])
            fleet._counts_host = np.array(tree["count"])
        if (get_method(cfg.method).needs_augmented_data
                and fleet.fitted_aug is None):
            raise ValueError(
                f"checkpoint has no augmented/communication experts but "
                f"method {cfg.method!r} needs them; refit with the grbcm "
                f"method configured")
        return fleet
