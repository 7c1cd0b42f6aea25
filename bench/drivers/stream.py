"""Streaming mix: fleet-wide observation events, back to back.

Set-up fills every agent's sliding window (W = the agent's share of the
training data) at the true hyperparameters, builds the serving engine so
that each event hot-swaps the served factors, and runs one event to
compile. Each event is one `GPFleet.observe`: every agent appends one new
point of the same field and evicts its oldest, ended by
`block_until_ready` on the factors. Events depend on the factor the last
one left, so they run back to back; `observe_ms` is the window's wall time
over its events.

Traffic keys: pool (new points made per agent; events past it reuse them
in order).
"""
from __future__ import annotations

import time

import jax
import numpy as np
from repro.fleet import FleetConfig, GPFleet

from bench import control, fields, reference


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 seconds: float):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.counts, self.notes, self.spans = {}, {}, []

    def setup(self):
        cfg = self.cfg
        M, n = int(cfg["num_agents"]), int(cfg["n_train"])
        pool = int(self.traffic["pool"])
        X, y = fields.make_field(self.seed, n + M * pool, cfg)
        Xp, yp = fields.stripe_partition(X[:n], y[:n], M)
        self.W = n // M
        self.Xp, self.yp = np.asarray(Xp), np.asarray(yp)
        self.pool_x = np.asarray(X[n:]).reshape(M, pool, -1)
        self.pool_y = np.asarray(y[n:]).reshape(M, pool)
        self.fleet = GPFleet(FleetConfig(
            num_agents=M, input_dim=int(cfg["input_dim"]),
            theta0=tuple(cfg["true_theta"]), graph=cfg["graph"],
            online=True, window=self.W, dac_iters=int(cfg["dac_iters"]),
            jitter=float(cfg["jitter"])))
        self.fleet.fit(Xp, yp, train=False)
        self.fleet.engine                 # observe swaps the served factors
        self.events = 0
        self._observe()

    def _observe(self):
        e = self.events % self.pool_x.shape[1]
        self.fleet.observe(self.pool_x[:, e], self.pool_y[:, e])
        jax.block_until_ready((self.fleet.fitted.L, self.fleet.fitted.alpha))
        self.events += 1

    def window(self, seconds: float) -> dict:
        e0 = self.events
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            while True:
                with jax.profiler.TraceAnnotation("bench_observe"):
                    self._observe()
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        events = self.events - e0
        cfg = self.cfg
        self.counts.update(
            attempted=events, failed=0, events=events,
            agents=int(cfg["num_agents"]), window=self.W,
            dim=int(cfg["input_dim"]), window_s=wall)
        return {"observe_ms": wall / events * 1e3}

    def collect(self):
        f = self.fleet.fitted
        self.L = np.asarray(f.L)
        self.alpha = np.asarray(f.alpha)
        self.Xw = np.asarray(f.Xp)
        del self.fleet

    def expected_window(self):
        """The last W points each agent has seen, oldest first."""
        P = self.pool_x.shape[1]
        idx = np.arange(self.events) % P
        Xs = np.concatenate([self.Xp, self.pool_x[:, idx]], axis=1)
        ys = np.concatenate([self.yp, self.pool_y[:, idx]], axis=1)
        return Xs[:, -self.W:], ys[:, -self.W:]

    def control(self):
        """Put the control (bench/control.py) in the program's place: the
        windows the events must leave, factored at 'high'."""
        Xw, yw = self.expected_window()
        self.Xw = Xw
        self.L, self.alpha = control.window_factor(
            Xw, yw, [float(t) for t in self.cfg["true_theta"]],
            float(self.cfg["jitter"]))

    def compare(self, limits: dict):
        """[(name, value, limit)]: points in the wrong window slot (exact),
        and the widest gap of the factor and of the weights, relative to
        the reference's largest entry."""
        Xw, yw = self.expected_window()
        misplaced = float(np.sum(np.any(self.Xw != Xw, axis=-1)))
        theta = [float(t) for t in self.cfg["true_theta"]]
        L, alpha = reference.window_factor(Xw, yw, theta,
                                           float(self.cfg["jitter"]))
        factor_gap = float(np.max(np.abs(self.L - L)) / np.max(np.abs(L)))
        alpha_gap = float(np.max(np.abs(self.alpha - alpha))
                          / np.max(np.abs(alpha)))
        return [("misplaced_points", misplaced, limits["misplaced_points"]),
                ("factor_gap", factor_gap, limits["factor_gap"]),
                ("alpha_gap", alpha_gap, limits["alpha_gap"])]
