"""Training mix: complete decentralized training runs, back to back.

Each run is `GPFleet.fit` with the configured trainer for the mix's ADMM
iterations from the configuration's theta0 on the same seeded data,
followed by the serving factorization, ended by `block_until_ready` on
the factors. Set-up builds the one fleet object and runs one fit through
it (compiling every program); the window runs further fits through the
same object until `--seconds` have passed, finishing the fit that crosses
the line. `train_s` is the window's wall time over the fits it completed.

Traffic keys: trainer, admm_iters.
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
        cfg, tr = self.cfg, self.traffic
        M, n = int(cfg["num_agents"]), int(cfg["n_train"])
        X, y = fields.make_field(self.seed, n, cfg)
        self.Xp, self.yp = fields.stripe_partition(X, y, M)
        self.fleet = GPFleet(FleetConfig(
            num_agents=M, input_dim=int(cfg["input_dim"]),
            theta0=tuple(cfg["theta0"]), graph=cfg["graph"],
            trainer=tr["trainer"], rho=float(cfg["rho"]),
            kappa=float(cfg["kappa"]), admm_iters=int(tr["admm_iters"]),
            jitter=float(cfg["jitter"])))
        self._fit()

    def _fit(self):
        self.fleet.fit(self.Xp, self.yp)
        jax.block_until_ready((self.fleet.thetas, self.fleet.fitted.L,
                               self.fleet.fitted.alpha))

    def window(self, seconds: float) -> dict:
        fits = 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            while True:
                with jax.profiler.TraceAnnotation("bench_fit"):
                    self._fit()
                fits += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        cfg = self.cfg
        M, dim = int(cfg["num_agents"]), int(cfg["input_dim"])
        self.counts.update(
            attempted=fits, failed=0, fits=fits,
            iters=fits * int(self.traffic["admm_iters"]), agents=M,
            n=int(cfg["n_train"]) // M, dim=dim, window_s=wall)
        return {"train_s": wall / fits}

    def collect(self):
        f = self.fleet
        self.thetas = np.asarray(f.thetas)
        self.L = np.asarray(f.fitted.L)
        self.alpha = np.asarray(f.fitted.alpha)
        self.Xp, self.yp = np.asarray(self.Xp), np.asarray(self.yp)
        finite = all(np.all(np.isfinite(a))
                     for a in (self.thetas, self.L, self.alpha))
        self.counts["failed"] = 0 if finite else self.counts["attempted"]
        del self.fleet

    def _reference(self, linalg=None):
        cfg = self.cfg
        if cfg["graph"] != "path":
            raise ValueError("the training reference runs on a path graph")
        return reference.dec_apx(
            self.Xp, self.yp, [float(t) for t in cfg["theta0"]],
            reference.path_adjacency(int(cfg["num_agents"])),
            rho=float(cfg["rho"]), kappa=float(cfg["kappa"]),
            iters=int(self.traffic["admm_iters"]),
            train_jitter_rel=float(cfg["train_jitter_rel"]),
            serve_jitter=float(cfg["jitter"]), linalg=linalg)

    def control(self):
        """Put the control (bench/control.py) in the program's place: the
        reference's training and factors at 'high'."""
        self.thetas, _, self.L, self.alpha = self._reference(control.LINALG)

    def compare(self, limits: dict):
        """[(name, value, limit)]: the widest gap of the per-agent trained
        log hyperparameters, and of the serving factor and weights
        relative to the reference's largest entry."""
        th, _, L, alpha = self._reference()
        theta_gap = float(np.max(np.abs(self.thetas - th)))
        factor_gap = float(np.max(np.abs(self.L - L)) / np.max(np.abs(L)))
        alpha_gap = float(np.max(np.abs(self.alpha - alpha))
                          / np.max(np.abs(alpha)))
        self.notes.update(theta=np.exp(th.mean(axis=0)).tolist())
        return [("theta_gap", theta_gap, limits["theta_gap"]),
                ("factor_gap", factor_gap, limits["factor_gap"]),
                ("alpha_gap", alpha_gap, limits["alpha_gap"])]
