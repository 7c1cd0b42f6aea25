"""The open-loop timeline and the tail taken from due times."""
import math

import numpy as np
import pytest

from bench import fields, timeline


def test_every_seed_same_arrivals_and_work_sizes_in_its_order():
    a = timeline.arrivals(50.0, 10.0)
    np.testing.assert_array_equal(a, timeline.arrivals(50.0, 10.0))
    assert len(a) == 500 and a[-1] <= 10.0 and np.all(np.diff(a) > 0)
    s1 = timeline.sizes(fields.np_rng(2**33 + 5, 1), 400, 1, 511)
    s2 = timeline.sizes(fields.np_rng(2**33 + 5, 1), 400, 1, 511)
    s3 = timeline.sizes(fields.np_rng(5, 1), 400, 1, 511)
    np.testing.assert_array_equal(s1, s2)
    assert not np.array_equal(s1, s3)            # another order ...
    np.testing.assert_array_equal(np.sort(s1), np.sort(s3))   # same work
    assert s1.min() >= 1 and s1.max() <= 511


def test_field_replays_from_the_seed():
    cfg = {"input_dim": 2, "domain": [0.0, 2.0],
           "true_theta": [1.2, 0.3, 1.3, 0.1], "rff_features": 64}
    X1, y1 = fields.make_field(2**33 + 5, 64, cfg)
    X2, y2 = fields.make_field(2**33 + 5, 64, cfg)
    X3, _ = fields.make_field(5, 64, cfg)        # high bits matter
    np.testing.assert_array_equal(np.asarray(X1), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(X1), np.asarray(X3))


def test_tail_counts_misses_and_is_timed_from_due():
    due = np.arange(100) * 0.01
    done = due + 0.005
    assert timeline.tail_ms(due, done) == pytest.approx(5.0)
    # a late result is charged from its due time
    late = done.copy()
    late[:10] += 1.0
    assert timeline.tail_ms(due, late) == pytest.approx(1005.0)
    # one rejected request (no completion) in twenty raises the p95
    done20 = list(due[:20] + 0.005)
    base = timeline.tail_ms(due[:20], done20)
    done20[3] = None
    assert timeline.tail_ms(due[:20], done20) == pytest.approx(5.0)
    done20[4] = None
    assert math.isinf(timeline.tail_ms(due[:20], done20)) and base == \
        pytest.approx(5.0)
