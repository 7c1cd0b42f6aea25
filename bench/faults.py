"""Faults planted under the timed path, to show that `correct` catches them.

Each fault is a context manager that breaks one step of the program for
the runs made inside it, by replacing a function of the program while the
block lasts. The benchmark's own runs never plant one; the tests under
bench/tests do, at a small size on the CPU, and the same faults are read
on the chip at the cells' sizes (bench/chip_faults.py).

  serve   answer_altered  each agent's posterior mean shifted by 0.05
          half_batch      the second half of every slot answered with
                          the first half's answers
  train   state_unchanged the ADMM update returns its input state
          half_batch      each agent's gradient from half of its points
          no_exchange     the neighbour exchange left out (no edges)
  stream  state_unchanged an observation leaves the windows as they were
          answer_altered  the updated factor's first diagonal entry
                          scaled by 1.01 where the update produces it
"""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def serve_answer_altered():
    from repro.core.prediction import engine
    real = engine.local_moments_cached

    def altered(*a, **k):
        mu, var = real(*a, **k)
        return mu + 0.05, var
    with mock.patch.object(engine, "local_moments_cached", altered):
        yield


@contextlib.contextmanager
def serve_half_batch():
    from repro.core.prediction import engine
    real = engine.PredictionEngine.predict

    def half(self, method, Xs, **k):
        import jax.numpy as jnp
        mean, var, info = real(self, method, Xs, **k)
        n = Xs.shape[0]
        idx = jnp.arange(n) % max(1, n // 2)
        return mean[idx], var[idx], info
    with mock.patch.object(engine.PredictionEngine, "predict", half):
        yield


@contextlib.contextmanager
def train_state_unchanged():
    from repro.core.training import admm_decentralized as ad

    def unchanged(thetas, p, *a, **k):
        return thetas, p
    with mock.patch.object(ad, "dec_apx_update", unchanged):
        yield


@contextlib.contextmanager
def train_half_batch():
    from repro.core.training import admm_decentralized as ad
    real = ad.make_local_grad

    def halved(*a, **k):
        prepare, grad = real(*a, **k)

        def prepare_half(Xp, yp):
            n = Xp.shape[-2] // 2
            return prepare(Xp[..., :n, :], yp[..., :n])
        return prepare_half, grad
    with mock.patch.object(ad, "make_local_grad", halved):
        yield


@contextlib.contextmanager
def train_no_exchange():
    import jax.numpy as jnp
    from repro.core.training import admm_decentralized as ad

    def isolated(A, dtype):
        z = jnp.zeros(A.shape, dtype)
        return z, jnp.zeros(A.shape[0], dtype)
    with mock.patch.object(ad, "_graph_terms", isolated):
        yield


@contextlib.contextmanager
def stream_state_unchanged():
    from repro.fleet import fleet

    def unchanged(state, xs, ys):
        return state
    with mock.patch.object(fleet, "observe_fleet", unchanged):
        yield


@contextlib.contextmanager
def stream_answer_altered():
    from repro.fleet import fleet
    real = fleet.observe_fleet

    def altered(state, xs, ys):
        out = real(state, xs, ys)
        return out._replace(L=out.L.at[:, 0, 0].multiply(1.01))
    with mock.patch.object(fleet, "observe_fleet", altered):
        yield


FAULTS = {
    "serve": {"answer_altered": serve_answer_altered,
              "half_batch": serve_half_batch},
    "train": {"state_unchanged": train_state_unchanged,
              "half_batch": train_half_batch,
              "no_exchange": train_no_exchange},
    "stream": {"state_unchanged": stream_state_unchanged,
               "answer_altered": stream_answer_altered},
}
