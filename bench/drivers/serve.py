"""Serving mix: open-loop Poisson requests through the fleet's scheduler.

Set-up fits the fleet at the true hyperparameters (no training: serving
is what this mix measures), registers it as the one tenant of a
`ServingScheduler` exactly as `GPFleet.to_server` does (the scheduler is
built directly only to hand it an in-memory span log) with
`admission="reject"`, so that the generator never blocks, and serves a
warm-up burst. The window submits every request on its due time, waits
for every answer (up to a minute past the close), and times each from its
due time; a rejected, dropped or failed request is a miss, timed to the
end of that wait.

Traffic keys: method, slot, rate_per_s, rows [lo, hi], queue_depth,
max_wait_ms, check_rows (query rows compared with the reference).
"""
from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np
from repro.fleet import FleetConfig, GPFleet
from repro.launch.scheduler import SchedulerSaturated, ServingScheduler

from bench import control, fields, reference, timeline


class _Spans:
    """In-memory span sink: the scheduler's per-request stage record."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def emit(self, event: dict):
        with self._lock:
            self.events.append(event)


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 seconds: float):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds = float(seconds)
        self.counts, self.notes, self.spans = {}, {}, []

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        cfg, tr = self.cfg, self.traffic
        M, n = int(cfg["num_agents"]), int(cfg["n_train"])
        X, y = fields.make_field(self.seed, n, cfg)
        Xp, yp = fields.stripe_partition(X, y, M)
        self.fleet = GPFleet(FleetConfig(
            num_agents=M, input_dim=int(cfg["input_dim"]),
            theta0=tuple(cfg["true_theta"]), graph=cfg["graph"],
            method=tr["method"], chunk=int(tr["slot"]),
            dac_iters=int(cfg["dac_iters"]), jitter=float(cfg["jitter"])))
        self.fleet.fit(Xp, yp, train=False)
        jax.block_until_ready(self.fleet.fitted.alpha)
        self.Xp, self.yp = np.asarray(Xp), np.asarray(yp)

        self.span_sink = _Spans()
        self.sched = ServingScheduler(max_wait_ms=float(tr["max_wait_ms"]),
                                      span_log=self.span_sink)
        self.sched.add_fleet("default", self.fleet, max_slot=int(tr["slot"]),
                             queue_depth=int(tr["queue_depth"]),
                             admission="reject", warm=True)

        self.make_timeline(float(tr["rate_per_s"]))

        # warm-up burst: one request of each kind the window sends (within
        # a slot, and across two slots: carry and stitch)
        rng = fields.np_rng(self.seed, 3)
        lo, hi = cfg["domain"]
        dim, slot = int(cfg["input_dim"]), int(tr["slot"])
        for r in (1, slot, min(2 * slot - 1, int(tr["rows"][1]))):
            Xq = rng.uniform(lo, hi, (r, dim)).astype(np.float32)
            self.sched.add_request(Xq).result(timeout=600)
        self.span_sink.events.clear()

    def make_timeline(self, rate: float):
        """The window's requests: the same arrivals for every seed, the
        same sizes in the seed's order, and query points uniform over the
        training domain."""
        cfg = self.cfg
        rng = fields.np_rng(self.seed, 1)
        self.due = timeline.arrivals(rate, self.seconds)
        lo, hi = cfg["domain"]
        rows = timeline.sizes(rng, len(self.due), *self.traffic["rows"])
        dim = int(cfg["input_dim"])
        self.requests = [rng.uniform(lo, hi, (int(r), dim))
                         .astype(np.float32) for r in rows]

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        n = len(self.due)
        done = [None] * n
        outs = [None] * n
        late = np.zeros(n)
        futs = []
        st = self.sched.stats
        b0, q0, p0 = st.batches, st.queries, st.padded_queries
        traces0 = self.fleet.jit_cache_misses
        gc_ms, gc_t = [], [0.0]

        def on_gc(phase, info):
            # the collector's pauses in the window, to tell them from
            # other host stalls of the generator
            if phase == "start":
                gc_t[0] = time.perf_counter()
            else:
                gc_ms.append((time.perf_counter() - gc_t[0]) * 1e3)

        def finished(i, fut):
            t = time.perf_counter()
            if fut.exception() is None:
                done[i] = t
                outs[i] = fut.result()

        gc.callbacks.append(on_gc)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            for i in range(n):
                wait = t0 + self.due[i] - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench_sleep"):
                        time.sleep(wait)
                late[i] = time.perf_counter() - (t0 + self.due[i])
                try:
                    with jax.profiler.TraceAnnotation("bench_submit"):
                        fut = self.sched.add_request(self.requests[i])
                except SchedulerSaturated:
                    continue
                fut.add_done_callback(lambda f, i=i: finished(i, f))
                futs.append(fut)
            with jax.profiler.TraceAnnotation("bench_wait"):
                limit = t0 + seconds + 60.0
                for fut in futs:
                    try:
                        fut.exception(timeout=max(0.0, limit
                                                  - time.perf_counter()))
                    except Exception:
                        pass
        t_end = time.perf_counter()
        gc.callbacks.remove(on_gc)

        due_abs = t0 + self.due
        self.outs = outs
        missed = sum(d is None for d in done)
        self.counts.update(
            attempted=n, failed=missed, batches=st.batches - b0,
            rows=st.queries - q0, padded_rows=st.padded_queries - p0,
            agents=int(self.cfg["num_agents"]),
            n=int(self.cfg["n_train"]) // int(self.cfg["num_agents"]),
            dim=int(self.cfg["input_dim"]), slot=int(self.traffic["slot"]),
            dac_rounds=int(self.cfg["dac_iters"]), window_s=t_end - t0)
        self.spans = list(self.span_sink.events)
        self.notes.update(
            generator_late_ms_p95=float(np.percentile(late, 95) * 1e3),
            generator_late_ms_max=float(late.max() * 1e3),
            generator_late_max_at_s=float(self.due[int(late.argmax())]),
            offered_rps=n / seconds, rejected=st.rejected,
            batches=self.counts["batches"],
            recompiles=self.fleet.jit_cache_misses - traces0,
            gc_collections=len(gc_ms),
            gc_pause_ms_max=max(gc_ms, default=0.0),
            gc_pause_ms_total=sum(gc_ms, 0.0))
        # a request never answered counts as answered when the run stopped
        # waiting (a minute past the close): past any latency limit, and a
        # finite number in the result line
        return {"serve_p95_ms": timeline.tail_ms(
            due_abs, [limit if d is None else d for d in done], 95.0)}

    # -- comparison -----------------------------------------------------------

    def collect(self):
        """Draw the requests to compare (the longest, then others in the
        seed's order, up to check_rows rows), then free the server."""
        self.sched.close()
        rng = fields.np_rng(self.seed, 2)
        served = [i for i, o in enumerate(self.outs) if o is not None]
        order = ([max(served, key=lambda i: len(self.requests[i]))]
                 if served else [])
        order += [i for i in rng.permutation(served) if i not in order]
        pick, rows = [], 0
        for i in order:
            if rows >= int(self.traffic["check_rows"]):
                break
            pick.append(i)
            rows += len(self.requests[i])
        self.check_idx = pick
        self.counts["checked_rows"] = rows
        del self.fleet, self.sched

    def control(self):
        """Put the control (bench/control.py) in the program's place: the
        compared requests answered by the reference at 'high'."""
        if not self.check_idx:
            return
        Xq = np.concatenate([self.requests[i] for i in self.check_idx])
        mean, var = control.rbcm(self.Xp, self.yp, Xq,
                                 [float(t) for t in self.cfg["true_theta"]],
                                 float(self.cfg["jitter"]))
        at = 0
        for i in self.check_idx:
            r = len(self.requests[i])
            self.outs[i] = (mean[at:at + r], var[at:at + r])
            at += r

    def compare(self, limits: dict):
        """[(name, value, limit)]: the 99th percentile over the compared
        rows of the served mean's gap, in reference posterior standard
        deviations, and the widest gap of the served variance, relative to
        the reference's."""
        if not self.check_idx:
            return [("mean_gap_p99", float("inf"), limits["mean_gap_p99"]),
                    ("var_gap", float("inf"), limits["var_gap"])]
        Xq = np.concatenate([self.requests[i] for i in self.check_idx])
        mean = np.concatenate([np.asarray(self.outs[i][0], np.float64)
                               for i in self.check_idx])
        var = np.concatenate([np.asarray(self.outs[i][1], np.float64)
                              for i in self.check_idx])
        theta = [float(t) for t in self.cfg["true_theta"]]
        m_ref, v_ref = reference.rbcm(self.Xp, self.yp, Xq, theta,
                                      float(self.cfg["jitter"]))
        mg = np.abs(mean - m_ref) / np.sqrt(v_ref)
        vg = np.abs(var - v_ref) / v_ref
        self.notes["mean_gap_max"] = float(mg.max())
        return [("mean_gap_p99", float(np.percentile(mg, 99)),
                 limits["mean_gap_p99"]),
                ("var_gap", float(vg.max()), limits["var_gap"])]
