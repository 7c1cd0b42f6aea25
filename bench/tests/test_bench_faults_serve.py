"""The serve cell's comparison at a small size on the CPU: a sound run is
correct, each fault planted under the timed path makes it incorrect, and
the control (the reference at 'high' in the program's place) reads
several times what the sound run does. At this size the control's
readings lie under the cell's limits, which are set at the cell's size;
there it is incorrect on the chip (bench/chip_faults.py)."""
import pytest

from bench import faults
from bench.tests import tiny

WORKLOAD = "paper-n32400-m4.serve"


def test_sound_run_is_correct():
    r = tiny.run(WORKLOAD)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["serve"]))
def test_planted_fault_is_caught(fault):
    r = tiny.run(WORKLOAD, fault=faults.FAULTS["serve"][fault])
    assert not r["correct"], r["checks"]


def test_control_reads_far_above_the_sound_run():
    sound = tiny.run(WORKLOAD)["checks"]
    ctl = tiny.run(WORKLOAD, control=True)["checks"]
    ratio = max(ctl[k]["value"] / sound[k]["value"] for k in sound
                if sound[k]["value"] > 0)
    assert ratio >= 5, (sound, ctl)
