"""The per-layer readers of the program's span events, on a constructed
event list (no chip needed)."""
from types import SimpleNamespace

import pytest

from bench import harness


def _request(seq, queue, behind, outcome="ok"):
    return {"event": "request", "span": "request", "tenant": "default",
            "seq": seq, "outcome": outcome, "rows": 8, "slots": 1,
            "slot_ids": [seq], "e2e_ms": queue + 5.0,
            "stages_ms": {"queue": queue, "pack": 0.5, "dispatch": 1.0,
                          "device": 3.0, "stitch": 0.5},
            "queue_split_ms": {"behind_slot": behind,
                               "idle": queue - behind}}


def _slot(slot_id, rows, slot, **ms):
    stages = dict(pack=0.25, dispatch=1.0, device_wait=9.0, fetch=0.5,
                  deliver=0.25)
    stages.update(ms)
    return {"event": "slot", "tenant": "default", "slot_id": slot_id,
            "rows": rows, "slot": slot, "seqs": [slot_id],
            "t0_ns": 1000 * slot_id, "slot_ms": stages}


def _events():
    # 20 answered requests: queue 1..20 ms, of which 0.5 ms behind a slot
    reqs = [_request(i, float(i), 0.5) for i in range(1, 21)]
    reqs.append(_request(21, 500.0, 0.0, outcome="deadline_dropped"))
    slots = [_slot(1, 256, 256), _slot(2, 128, 256, dispatch=3.0),
             _slot(3, 200, 256, fetch=1.5)]
    return reqs, slots


def _read(name, spans):
    return harness.metric_reader(name)(SimpleNamespace(
        spans=spans, counts={}, trace=None, cfg={}, traffic={}))


def test_queue_wait_reads_the_same_events_beside_slot_events():
    reqs, slots = _events()
    mixed = reqs[:5] + slots[:1] + reqs[5:12] + slots[1:] + reqs[12:]
    alone = _read("queue_wait_p95_ms.serve", reqs)
    assert _read("queue_wait_p95_ms.serve", mixed) == alone
    assert _read("queue_wait_p95_ms.serve", reqs + slots) == alone


@pytest.mark.parametrize("name,want", [
    # mean of 2.0, 4.0 and 3.0 ms: slot time without the device wait
    ("slot_host_ms.serve", 3.0),
    # nearest-rank p95 of idle 0.5..19.5 over the answered requests
    ("queue_idle_p95_ms.serve", 18.5),
    # (256 + 128 + 200) / (3 * 256)
    ("slot_fill.serve", 100.0 * 584 / 768),
])
def test_slot_and_split_readers_on_a_constructed_window(name, want):
    reqs, slots = _events()
    assert _read(name, reqs + slots) == pytest.approx(want)


@pytest.mark.parametrize("name", ["slot_host_ms.serve",
                                  "queue_idle_p95_ms.serve",
                                  "slot_fill.serve"])
def test_readers_find_nothing_in_request_spans_without_slots(name):
    """A program whose scheduler emits no slot events and no queue split
    gives these metrics no value, rather than an error or a zero."""
    reqs, _ = _events()
    for r in reqs:
        del r["queue_split_ms"]
    assert _read(name, reqs) is None
    assert _read(name, []) is None
