"""Scheduler slot spans, the queue wait split by cause, the worker's
profiler annotations, and the device scopes of each layer.

  * every dispatched slot emits one `slot` event whose `slot_ms` stages
    tile the slot's host wall time, and request events name the slots
    they rode (`slot_ids`);
  * `queue_split_ms` splits each request's `queue` stage into the time
    spent behind an in-flight slot and the idle rest, summing exactly;
  * the `scheduler/*` annotations pair off with the slot events on one
    constant clock offset;
  * the ADMM step, a serving slot and an observe event carry their
    layers' `jax.named_scope` names in their lowered programs.
"""
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.consensus.graph import path_graph
from repro.core.online.experts import from_batch, observe_fleet
from repro.core.training.admm_decentralized import train_dec_apx_gp
from repro.fleet import FleetConfig, GPFleet
from repro.launch.scheduler import ServingScheduler
from repro.obs import MetricsRegistry, SpanLog, read_spans

STAGES = ("pack", "dispatch", "device_wait", "fetch", "deliver")


class Sink:
    """In-memory span log."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def emit(self, event):
        with self._lock:
            self.events.append(event)

    def of(self, kind):
        return [e for e in self.events if e["event"] == kind]


def echo_predict(Xs):
    Xs = np.asarray(Xs)
    return Xs.sum(axis=-1), np.ones(Xs.shape[0])


def sleepy_predict(seconds):
    def predict(Xs):
        time.sleep(seconds)
        return echo_predict(Xs)
    return predict


def scheduler(sink, **kw):
    return ServingScheduler(registry=MetricsRegistry(), span_log=sink, **kw)


# ---------------------------------------------------------------------------
# slot events
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 4, 10])
def test_slot_ms_tiles_each_slots_wall_time(rows):
    sink = Sink()
    sched = scheduler(sink, autostart=False)
    sched.add_tenant("t", sleepy_predict(0.02), slots=(4,))
    fut = sched.add_request(np.ones((rows, 2)), tenant="t")
    walls = []
    while True:
        t0 = time.perf_counter_ns()
        if not sched.step(force=True):
            break
        walls.append((t0, time.perf_counter_ns()))
    fut.result(timeout=10)
    sched.close()
    slots = sink.of("slot")
    assert len(slots) == len(walls) == -(-rows // 4)
    for ev, (a, b) in zip(slots, walls):
        assert set(ev["slot_ms"]) == set(STAGES)
        assert "stages_ms" not in ev
        assert a <= ev["t0_ns"] <= b
        wall_ms = (b - a) * 1e-6
        assert sum(ev["slot_ms"].values()) == pytest.approx(wall_ms,
                                                            rel=0.05)
        assert ev["slot_ms"]["dispatch"] >= 15.0     # the engine's sleep


@pytest.mark.parametrize("sizes", [(10,), (3, 3, 3), (1, 7, 4, 9, 2)])
def test_request_slot_ids_name_the_slots_it_rode(sizes):
    sink = Sink()
    sched = scheduler(sink, autostart=False)
    sched.add_tenant("t", echo_predict, slots=(4,))
    futs = [sched.add_request(np.ones((n, 2)), tenant="t") for n in sizes]
    while sched.step(force=True):
        pass
    for f in futs:
        f.result(timeout=10)
    sched.close()
    slots = {e["slot_id"]: e for e in sink.of("slot")}
    reqs = sink.of("request")
    assert len(reqs) == len(sizes)
    for r in reqs:
        assert r["slots"] == len(r["slot_ids"])
        for sid in r["slot_ids"]:
            assert r["seq"] in slots[sid]["seqs"]
        rode = {sid for sid, s in slots.items() if r["seq"] in s["seqs"]}
        assert rode == set(r["slot_ids"])
        if len(sizes) == 1:                      # rides alone
            assert sum(slots[s]["rows"] for s in r["slot_ids"]) == r["rows"]
    assert sum(s["rows"] for s in slots.values()) == sum(sizes)
    assert all(s["slot"] == 4 for s in slots.values())


def test_read_spans_returns_requests_unless_asked_for_every_event(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    with SpanLog(path) as log:
        sched = scheduler(log)
        sched.add_tenant("t", echo_predict, slots=(4,))
        sched.add_request(np.ones((6, 2)), tenant="t").result(timeout=10)
        sched.close()
    reqs = read_spans(path)
    every = read_spans(path, event=None)
    assert [e["event"] for e in reqs] == ["request"]
    assert {e["event"] for e in every} == {"request", "slot"}
    assert read_spans(path, event="slot") == \
        [e for e in every if e["event"] == "slot"]
    assert all("stages_ms" not in e for e in every if e["event"] == "slot")


# ---------------------------------------------------------------------------
# the queue wait split by cause
# ---------------------------------------------------------------------------

def test_queue_split_sums_to_the_queue_stage_under_load():
    sink = Sink()
    sched = scheduler(sink)
    sched.add_tenant("t", sleepy_predict(0.002), slots=(4, 8))

    def client(i):
        for k in range(15):
            n = 1 + (i + k) % 11
            sched.add_request(np.ones((n, 2)), tenant="t").result(timeout=30)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    sched.close()
    reqs = sink.of("request")
    assert len(reqs) == 60
    for r in reqs:
        split = r["queue_split_ms"]
        assert split["behind_slot"] >= 0.0
        assert split["idle"] >= -1e-9
        assert split["behind_slot"] + split["idle"] == pytest.approx(
            r["stages_ms"]["queue"], abs=1e-9)
    assert max(r["queue_split_ms"]["behind_slot"] for r in reqs) > 0.0


def test_queue_wait_behind_an_in_flight_slot_is_behind_slot():
    sink = Sink()
    entered, release = threading.Event(), threading.Event()

    def blocking(Xs):
        entered.set()
        release.wait(10)
        return echo_predict(Xs)

    sched = scheduler(sink)
    sched.add_tenant("t", blocking, slots=(4,))
    first = sched.add_request(np.ones((4, 2)), tenant="t")
    assert entered.wait(10)
    second = sched.add_request(np.full((4, 2), 2.0), tenant="t")
    time.sleep(0.05)
    release.set()
    first.result(timeout=10)
    second.result(timeout=10)
    sched.close()
    r = next(e for e in sink.of("request") if e["seq"] == 2)
    split = r["queue_split_ms"]
    assert split["behind_slot"] >= 45.0
    assert split["idle"] < split["behind_slot"]


def test_queue_wait_on_the_batching_timer_is_idle():
    sink = Sink()
    reg = MetricsRegistry()
    sched = ServingScheduler(registry=reg, span_log=sink, max_wait_ms=30.0)
    sched.add_tenant("t", echo_predict, slots=(8,))
    sched.add_request(np.ones((1, 2)), tenant="t").result(timeout=10)
    sched.close()
    (r,) = sink.of("request")
    assert r["queue_split_ms"]["behind_slot"] == 0.0
    assert r["queue_split_ms"]["idle"] >= 25.0
    hist = reg.histogram("gp_queue_idle_seconds")
    assert hist.count(tenant="t") == 1
    assert hist.sum(tenant="t") == pytest.approx(
        r["queue_split_ms"]["idle"] * 1e-3)


# ---------------------------------------------------------------------------
# the worker's annotations on the profiler's clock
# ---------------------------------------------------------------------------

def test_pack_annotations_pair_with_slot_events_on_one_clock(tmp_path):
    from jax.profiler import ProfileData
    sink = Sink()
    sched = scheduler(sink, autostart=False)
    sched.add_tenant("t", echo_predict, slots=(4,))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(6):
            sched.add_request(np.ones((3 + i, 2)), tenant="t")
        while sched.pending():       # a step with nothing to pack also
            sched.step(force=True)   # leaves a `scheduler/pack` span
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    sched.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("scheduler/"):
                    names.setdefault(e.name, []).append(e.start_ns)
    slots = sink.of("slot")
    assert len(slots) == 9
    for stage in STAGES:
        assert len(names[f"scheduler/{stage}"]) == len(slots), stage
    packs = sorted(names["scheduler/pack"])
    offsets = [s["t0_ns"] - p for s, p in zip(slots, packs)]
    assert max(offsets) - min(offsets) < 1e6        # ns


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

M, N, D = 3, 32, 2
LT = jnp.log(jnp.asarray([1.2, 0.3, 1.3, 0.1], jnp.float32))


def _data():
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.uniform(0, 2, (M, N, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(M, N)), jnp.float32))


def _admm_step():
    Xp, yp = _data()
    return train_dec_apx_gp.lower(LT, Xp, yp, path_graph(M), iters=2)


def _serving_slot():
    Xp, yp = _data()
    fleet = GPFleet(FleetConfig(num_agents=M, input_dim=D,
                                theta0=(1.2, 0.3, 1.3, 0.1), method="rbcm",
                                chunk=16, dac_iters=4))
    fleet.fit(Xp, yp, train=False)
    eng = fleet.engine
    Xs = jnp.zeros((16, D), jnp.float32)
    eng.predict("rbcm", Xs)
    return eng._compiled["rbcm"].lower(eng.fitted, eng.fitted_aug,
                                       eng.fitted_comm, Xs)


def _observe_event():
    Xp, yp = _data()
    state = from_batch(LT, Xp, yp)
    return jax.jit(observe_fleet).lower(state, jnp.zeros((M, D)),
                                        jnp.zeros((M,)))


@pytest.mark.parametrize("lower,scopes", [
    (_admm_step, ("admm/cholesky", "admm/inverse", "admm/nll_grad",
                  "admm/update")),
    (_serving_slot, ("serve/local_moments", "serve/aggregate",
                     "dac/round")),
    (_observe_event, ("online/evict", "online/append", "online/alpha")),
], ids=["admm", "serve", "observe"])
def test_each_layer_program_carries_its_scopes(lower, scopes):
    lowered = lower()
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    # scopes are debug info only: the program text without it names none
    plain = lowered.as_text()
    assert not any(scope in plain for scope in scopes)
