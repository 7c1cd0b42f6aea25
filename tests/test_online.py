"""Online/streaming GP subsystem: incremental numerics and live-fleet
serving equivalence.

Acceptance gates:
  - rank-1 cholupdate/downdate == full refactorization (<= 1e-6 float64,
    <= 1e-4 float32) over randomized observe/evict sequences;
  - after K interleaved observe/evict events, OnlineExperts factors match
    a fresh fit_experts on the equivalent window through EVERY
    PredictionEngine method (all 13 decentralized + centralized refs);
  - membership changes (join/leave) keep the consensus graph connected and
    the engine serving;
  - factor hot-swap (swap_experts) reuses compiled programs;
  - NPAE cross-covariance caching (fit_experts cache_cross) is exact and
    memory-guarded;
  - stripe_partition signals dropped points.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.consensus import (attach_agent, complete_graph, is_connected,
                                  path_graph, remove_agent)
from repro.core.gp import pack, stripe_partition
from repro.core.online import (evict_oldest, from_batch, init_online, join,
                               leave, observe, observe_fleet, refit)
from repro.core.prediction import (PredictionEngine, fit_experts,
                                   npae_terms_cached)
from repro.data import gp_sample_field, random_inputs
from repro.kernels import ops, ref

TRUE_LT = pack([1.2, 0.3], 1.3, 0.1)
M, W, D = 4, 12, 2
NT = 9
CHUNK = 4
ITERS = 120


# ---------------------------------------------------------------------------
# rank-1 Cholesky update/downdate kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [5, 37, 130])
def test_cholupdate_matches_refactorization_f64(n):
    rng = np.random.default_rng(n)
    B = rng.standard_normal((n, n))
    A = B @ B.T + n * np.eye(n)
    x = rng.standard_normal(n)
    L = np.linalg.cholesky(A)
    up = ops.cholupdate(jnp.asarray(L), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(up),
                               np.linalg.cholesky(A + np.outer(x, x)),
                               atol=1e-6)
    down = ops.cholupdate(up, jnp.asarray(x), downdate=True)
    np.testing.assert_allclose(np.asarray(down), L, atol=1e-6)


def test_cholupdate_f32_and_pallas_interpret():
    n = 48
    rng = np.random.default_rng(0)
    B = rng.standard_normal((n, n))
    A = B @ B.T + n * np.eye(n)
    x = rng.standard_normal(n)
    ref_up = np.linalg.cholesky(A + np.outer(x, x))
    L32 = jnp.asarray(np.linalg.cholesky(A), jnp.float32)
    x32 = jnp.asarray(x, jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.cholupdate(L32, x32)), ref_up,
                               atol=1e-4)
    # Pallas kernel (interpret mode on CPU), incl. pad-to-tile no-op cols
    up_p = ops.cholupdate(L32, x32, use_pallas=True, interpret=True, bk=16)
    np.testing.assert_allclose(np.asarray(up_p), ref_up, atol=1e-4)


def test_cholupdate_zero_vector_is_noop_and_shift_evicts():
    n = 24
    rng = np.random.default_rng(1)
    B = rng.standard_normal((n, n))
    A = B @ B.T + n * np.eye(n)
    L = jnp.asarray(np.linalg.cholesky(A))
    noop = ops.cholupdate(L, jnp.zeros(n))
    np.testing.assert_allclose(np.asarray(noop), np.asarray(L), atol=0)
    # evict: drop the first point, the result moved up-left, the last slot
    # a fresh sentinel
    out = ops.cholupdate_evict(L, 3.0)
    np.testing.assert_allclose(np.asarray(out)[:n - 1, :n - 1],
                               np.linalg.cholesky(np.asarray(A)[1:, 1:]),
                               atol=1e-8)
    evec = 3.0 * (np.arange(n) == n - 1)
    assert np.array_equal(np.asarray(out)[n - 1], evec)
    assert np.array_equal(np.asarray(out)[:, n - 1], evec)


@pytest.mark.parametrize("n", [16, 20, 37])
def test_cholupdate_evict_pallas_skips_column_zero_exactly(n):
    """The evict kernel (interpret mode) at windows that fill their last
    panel (16 rows) or leave it ragged: its sweep starts at column 1, so
    the result is bit for bit the plain kernel's update of the trailing
    block with x = L[1:, 0]; the last row and column are the fresh slot;
    a False flag hands the factor back untouched."""
    rng = np.random.default_rng(n)
    B = rng.standard_normal((n, n))
    A = B @ B.T + n * np.eye(n)
    L = jnp.asarray(np.linalg.cholesky(A), jnp.float32)
    out = np.asarray(ops.cholupdate_evict(L, 2.5, use_pallas=True,
                                          interpret=True, bk=16))
    trailing = ops.cholupdate(L[1:, 1:], L[1:, 0], use_pallas=True,
                              interpret=True, bk=16)
    assert np.array_equal(out[:n - 1, :n - 1], np.asarray(trailing))
    np.testing.assert_allclose(out[:n - 1, :n - 1],
                               np.linalg.cholesky(A[1:, 1:]), rtol=1e-5,
                               atol=1e-5)
    evec = np.where(np.arange(n) == n - 1, np.float32(2.5), 0.0)
    assert np.array_equal(out[n - 1], evec)
    assert np.array_equal(out[:, n - 1], evec)
    keep = ops.cholupdate_evict(L, 2.5, jnp.asarray(False), use_pallas=True,
                                interpret=True, bk=16)
    assert np.array_equal(np.asarray(keep), np.asarray(L))


# ---------------------------------------------------------------------------
# sliding-window experts: randomized event sequences vs refit
# ---------------------------------------------------------------------------

def _stream_state(dtype=jnp.float64, events=70, seed=0):
    lt = TRUE_LT.astype(dtype)
    state = init_online(lt, M, W, D, dtype=dtype)
    rng = np.random.default_rng(seed)
    obs = jax.jit(observe)
    ev = jax.jit(evict_oldest)
    for _ in range(events):
        a = int(rng.integers(0, M))
        if rng.random() < 0.25:
            state = ev(state, a)
        else:
            state = obs(state, a,
                        jnp.asarray(rng.standard_normal(D), dtype),
                        jnp.asarray(rng.standard_normal(), dtype))
    return state


def test_randomized_observe_evict_matches_refit_f64():
    state = _stream_state(events=90)
    ref_state = refit(state)
    np.testing.assert_allclose(np.asarray(state.L), np.asarray(ref_state.L),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(state.alpha),
                               np.asarray(ref_state.alpha), atol=1e-6)


def test_randomized_observe_evict_matches_refit_f32():
    state = _stream_state(dtype=jnp.float32, events=60)
    ref_state = refit(state)
    np.testing.assert_allclose(np.asarray(state.L), np.asarray(ref_state.L),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(state.alpha),
                               np.asarray(ref_state.alpha), atol=1e-4)


def test_observe_fleet_matches_refit():
    state = _stream_state(events=30)
    rng = np.random.default_rng(7)
    ingest = jax.jit(observe_fleet)
    for _ in range(2 * W):                     # wrap every window
        state = ingest(state, jnp.asarray(rng.standard_normal((M, D))),
                       jnp.asarray(rng.standard_normal(M)))
    assert np.all(np.asarray(state.count) == W)
    ref_state = refit(state)
    np.testing.assert_allclose(np.asarray(state.L), np.asarray(ref_state.L),
                               atol=1e-6)


WP = 20      # ragged against the evict kernel's 16-row panels at this width


def _mixed_windows():
    """float32 fleet of M windows of WP slots: agents 0 and 1 full, agent
    2 filling, agent 3 empty; factors from scratch."""
    rng = np.random.default_rng(11)
    state = init_online(TRUE_LT.astype(jnp.float32), M, WP, D,
                        dtype=jnp.float32)
    counts = np.array([WP, WP, 7, 0])
    valid = np.arange(WP)[None, :] < counts[:, None]
    Xw = np.where(valid[..., None], rng.uniform(0.0, 1.0, (M, WP, D)),
                  np.asarray(state.Xw))
    yw = np.where(valid, rng.standard_normal((M, WP)), 0.0)
    return refit(state._replace(
        Xw=jnp.asarray(Xw, jnp.float32), yw=jnp.asarray(yw, jnp.float32),
        count=jnp.asarray(counts, jnp.int32)))


@pytest.fixture
def pallas_evict(monkeypatch):
    """Route core.online's evict through the Pallas kernel in interpret
    mode (callers trace fresh functions, so no cached jnp trace is
    reused)."""
    import functools

    from repro.core.online import experts
    monkeypatch.setattr(experts, "cholupdate_evict", functools.partial(
        ops.cholupdate_evict, use_pallas=True, interpret=True))


def _observe_events(state, events=3):
    rng = np.random.default_rng(5)
    ingest = jax.jit(lambda s, x, y: observe_fleet(s, x, y))
    for _ in range(events):
        state = ingest(state,
                       jnp.asarray(rng.uniform(0.0, 1.0, (M, D)),
                                   jnp.float32),
                       jnp.asarray(rng.standard_normal(M), jnp.float32))
    return state


def _assert_same_window(got, want, tol):
    for field in ("Xw", "yw", "count"):
        assert np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want, field))), field
    np.testing.assert_allclose(np.asarray(got.L), np.asarray(want.L),
                               atol=tol)
    # alpha = C^-1 y reaches 1 / sigma_eps^2 = 100: compare relative to it
    want_alpha = np.asarray(want.alpha)
    np.testing.assert_allclose(np.asarray(got.alpha), want_alpha,
                               atol=tol * np.max(np.abs(want_alpha)))


def test_observe_fleet_pallas_interpret_mixed_windows(request):
    """observe_fleet through the evict kernel (interpret mode) on a fleet
    with full and filling windows at a ragged width: the full windows
    slide, the others only append, and the factors match the jnp path
    and a refit."""
    state = _mixed_windows()
    jnp_out = _observe_events(state)
    request.getfixturevalue("pallas_evict")
    pallas_out = _observe_events(state)
    assert np.asarray(pallas_out.count).tolist() == [WP, WP, 10, 3]
    _assert_same_window(pallas_out, jnp_out, 1e-5)
    _assert_same_window(pallas_out, refit(pallas_out), 1e-4)
    # the full windows hold the last WP points; sentinels follow the rest
    assert np.all(np.abs(np.asarray(pallas_out.Xw[2, 10:])) >= 1e6)


def test_observe_and_evict_oldest_pallas_interpret(request):
    """Single-agent observe / evict_oldest (a real cond, traced agent
    index) through the evict kernel match the jnp path and a refit: a
    full window slides, a filling one appends, an empty one is left
    alone."""
    state = _mixed_windows()
    x, y = jnp.asarray([0.3, 0.6], jnp.float32), jnp.asarray(0.4, jnp.float32)

    def events(st):
        obs = jax.jit(lambda s, a: observe(s, a, x, y))
        ev = jax.jit(lambda s, a: evict_oldest(s, a))
        st = obs(st, 0)            # full: evict + append
        st = obs(st, 2)            # filling: append only
        st = ev(st, 1)             # full -> WP - 1
        st = ev(st, 3)             # empty: no-op
        return ev(st, 2)

    jnp_out = events(state)
    request.getfixturevalue("pallas_evict")
    pallas_out = events(state)
    assert np.asarray(pallas_out.count).tolist() == [WP, WP - 1, 7, 0]
    _assert_same_window(pallas_out, jnp_out, 1e-5)
    _assert_same_window(pallas_out, refit(pallas_out), 1e-4)
    np.testing.assert_array_equal(np.asarray(pallas_out.L[3]),
                                  np.asarray(state.L[3]))


def test_evict_on_empty_window_is_noop():
    state = init_online(TRUE_LT, M, W, D)
    out = evict_oldest(state, 1)
    assert int(out.count[1]) == 0
    np.testing.assert_allclose(np.asarray(out.L), np.asarray(state.L),
                               atol=0)
    np.testing.assert_allclose(np.asarray(out.alpha), 0.0, atol=0)


def test_window_slides_to_last_w_points():
    """Observing 2W points leaves exactly the newest W, in age order."""
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((2 * W, D))
    ys = rng.standard_normal(2 * W)
    state = init_online(TRUE_LT, 1, W, D)
    obs = jax.jit(observe)
    for k in range(2 * W):
        state = obs(state, 0, jnp.asarray(xs[k]), jnp.asarray(ys[k]))
    np.testing.assert_allclose(np.asarray(state.Xw[0]), xs[W:], atol=0)
    f = fit_experts(TRUE_LT, jnp.asarray(xs[None, W:]),
                    jnp.asarray(ys[None, W:]))
    np.testing.assert_allclose(np.asarray(state.L[0]), np.asarray(f.L[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(state.alpha[0]),
                               np.asarray(f.alpha[0]), atol=1e-6)


# ---------------------------------------------------------------------------
# serving equivalence: online factors through every PredictionEngine method
# ---------------------------------------------------------------------------

def _full_window_state():
    """Stream until every window is full (wraps past W)."""
    key = jax.random.PRNGKey(0)
    X = random_inputs(key, M * (W + 5))
    _, y = gp_sample_field(jax.random.PRNGKey(1), X, TRUE_LT)
    Xp = X.reshape(M, W + 5, D)
    yp = y.reshape(M, W + 5)
    state = init_online(TRUE_LT, M, W, D)
    obs = jax.jit(observe)
    for k in range(W + 5):
        for a in range(M):
            state = obs(state, a, Xp[a, k], yp[a, k])
    return state


@pytest.fixture(scope="module")
def engines_online():
    from repro.core.gp import augment, communication_dataset

    state = _full_window_state()
    f_on = state.to_fitted()
    f_ref = fit_experts(TRUE_LT, state.Xw, state.yw)
    Xc, yc = communication_dataset(jax.random.PRNGKey(5), state.Xw, state.yw)
    Xa, ya = augment(state.Xw, state.yw, Xc, yc)
    fa = fit_experts(TRUE_LT, Xa, ya)
    fc = fit_experts(TRUE_LT, Xc[None], yc[None])

    def build(f, A):
        return PredictionEngine(f, A, chunk=CHUNK, dac_iters=ITERS,
                                jor_iters=300, dale_iters=500, pm_iters=40,
                                eta_nn=0.1, fitted_aug=fa, fitted_comm=fc)

    A, Ac = path_graph(M), complete_graph(M)
    return state, {"on": build(f_on, A), "ref": build(f_ref, A),
                   "on_c": build(f_on, Ac), "ref_c": build(f_ref, Ac)}


@pytest.mark.parametrize("method", sorted(
    m for m in PredictionEngine.METHODS if m != "npae_sparse"))
def test_online_factors_serve_every_method(engines_online, method):
    # npae_sparse excluded: it serves from SparseExperts only, and the
    # sparse family is not online-safe (registry flag; validate_config
    # rejects sparse_m + online)
    """Full-window online factors == fresh fit_experts on the same window
    through every decentralized method and centralized reference."""
    _, eng = engines_online
    key = "on_c" if "npae" in method else "on"
    ref_key = "ref_c" if "npae" in method else "ref"
    Xs = random_inputs(jax.random.PRNGKey(2), NT)
    m1, v1, _ = eng[key].predict(method, Xs)
    m2, v2, _ = eng[ref_key].predict(method, Xs)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)


@pytest.mark.parametrize("method", ["poe", "rbcm", "nn_poe", "npae",
                                    "cen_npae"])
def test_partial_windows_serve_like_valid_subset(method):
    """Sentinel slots are invisible: a half-full fleet predicts exactly
    like fit_experts on only the valid points."""
    c = W // 2
    key = jax.random.PRNGKey(9)
    X = random_inputs(key, M * c)
    _, y = gp_sample_field(jax.random.PRNGKey(10), X, TRUE_LT)
    Xp, yp = X.reshape(M, c, D), y.reshape(M, c)
    state = from_batch(TRUE_LT, Xp, yp, window=W)
    assert np.all(np.asarray(state.count) == c)
    A = complete_graph(M) if "npae" in method else path_graph(M)
    e_on = PredictionEngine(state.to_fitted(), A, chunk=CHUNK,
                            dac_iters=ITERS, jor_iters=300, eta_nn=0.1)
    e_ref = PredictionEngine(fit_experts(TRUE_LT, Xp, yp), A, chunk=CHUNK,
                             dac_iters=ITERS, jor_iters=300, eta_nn=0.1)
    Xs = random_inputs(jax.random.PRNGKey(11), NT)
    m1, v1, _ = e_on.predict(method, Xs)
    m2, v2, _ = e_ref.predict(method, Xs)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)


def test_swap_experts_keeps_compiled_programs(engines_online):
    state, eng = engines_online
    e = eng["on"]
    Xs = random_inputs(jax.random.PRNGKey(4), NT)
    m1, _, _ = e.predict("poe", Xs)
    compiled = e._compiled["poe"]
    state2 = observe(state, 0, jnp.asarray([0.3, 0.4]), jnp.asarray(1.5))
    e.swap_experts(state2.to_fitted())
    m2, _, _ = e.predict("poe", Xs)
    assert e._compiled["poe"] is compiled
    assert not np.allclose(np.asarray(m1), np.asarray(m2))
    e.swap_experts(state.to_fitted())          # restore for other tests
    with pytest.raises(ValueError):
        small = init_online(TRUE_LT, M - 1, W, D).to_fitted()
        e.swap_experts(small)                  # membership change -> rewire


# ---------------------------------------------------------------------------
# dynamic membership
# ---------------------------------------------------------------------------

def test_graph_attach_and_remove_keep_connectivity():
    A = path_graph(5)
    A2 = attach_agent(A, (4,))
    assert A2.shape == (6, 6) and is_connected(A2)
    assert float(A2[5, 4]) == 1.0 and float(A2[4, 5]) == 1.0
    # removing an interior (cut) vertex re-chains its neighbors
    A3 = remove_agent(A2, 2)
    assert A3.shape == (5, 5) and is_connected(A3)
    with pytest.raises(ValueError):
        attach_agent(A, (9,))


def test_join_and_leave_rewire_live_fleet():
    state = _full_window_state()
    A = path_graph(M)
    eng = PredictionEngine(state.to_fitted(), A, chunk=CHUNK,
                           dac_iters=ITERS)
    Xs = random_inputs(jax.random.PRNGKey(2), NT)
    eng.predict("rbcm", Xs)

    kj = jax.random.PRNGKey(21)
    Xj = random_inputs(kj, W)
    _, yj = gp_sample_field(jax.random.fold_in(kj, 1), Xj, TRUE_LT)
    state2, A2 = join(state, A, Xj, yj)
    assert state2.num_agents == M + 1 and is_connected(A2)
    eng.rewire(A2, fitted=state2.to_fitted())
    m_join, v_join, _ = eng.predict("rbcm", Xs)
    # the joined fleet == a fleet built from scratch with the same windows
    e_ref = PredictionEngine(
        fit_experts(TRUE_LT, state2.Xw, state2.yw), A2, chunk=CHUNK,
        dac_iters=ITERS)
    m_ref, v_ref, _ = e_ref.predict("rbcm", Xs)
    np.testing.assert_allclose(np.asarray(m_join), np.asarray(m_ref),
                               atol=1e-6)

    state3, A3 = leave(state2, A2, 1)
    assert state3.num_agents == M and is_connected(A3)
    eng.rewire(A3, fitted=state3.to_fitted())
    m_leave, _, _ = eng.predict("rbcm", Xs)
    assert np.all(np.isfinite(np.asarray(m_leave)))
    with pytest.raises(ValueError):
        leave(state3, A3, M + 3)


def test_joiner_without_data_warms_up():
    state = init_online(TRUE_LT, 2, W, D)
    A = path_graph(2)
    state, A = join(state, A)
    assert state.num_agents == 3 and int(state.count[2]) == 0
    state = observe(state, 2, jnp.asarray([0.1, 0.2]), jnp.asarray(0.5))
    assert int(state.count[2]) == 1
    ref_state = refit(state)
    np.testing.assert_allclose(np.asarray(state.L), np.asarray(ref_state.L),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# NPAE cross-covariance cache
# ---------------------------------------------------------------------------

def test_cache_cross_terms_exact_and_guarded():
    key = jax.random.PRNGKey(0)
    X = random_inputs(key, M * W)
    _, y = gp_sample_field(jax.random.PRNGKey(1), X, TRUE_LT)
    Xp, yp = X.reshape(M, W, D), y.reshape(M, W)
    f = fit_experts(TRUE_LT, Xp, yp, cache_cross=True)
    assert f.Kcross.shape == (M, M, W, W)
    Xs = random_inputs(jax.random.PRNGKey(2), NT)
    plain = npae_terms_cached(TRUE_LT, f.Xp, f.L, f.alpha, Xs)
    cached = npae_terms_cached(TRUE_LT, f.Xp, f.L, f.alpha, Xs,
                               Kcross=f.Kcross)
    for a, b in zip(plain, cached):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-12)
    # engine consumes the cache transparently
    Ac = complete_graph(M)
    e_cached = PredictionEngine(f, Ac, chunk=CHUNK, jor_iters=300,
                                dac_iters=ITERS)
    e_plain = PredictionEngine(fit_experts(TRUE_LT, Xp, yp), Ac, chunk=CHUNK,
                               jor_iters=300, dac_iters=ITERS)
    m1, v1, _ = e_cached.predict("npae", Xs)
    m2, v2, _ = e_plain.predict("npae", Xs)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-8)
    # memory-estimate guard fires at trace time
    with pytest.raises(ValueError, match="cross_cache_limit_mb"):
        fit_experts(TRUE_LT, Xp, yp, cache_cross=True,
                    cross_cache_limit_mb=0.001)


# ---------------------------------------------------------------------------
# stripe_partition dropped-count signal
# ---------------------------------------------------------------------------

def test_stripe_partition_warns_on_dropped_points():
    X = random_inputs(jax.random.PRNGKey(0), 10)
    y = jnp.arange(10.0)
    with pytest.warns(UserWarning, match="dropping 1 trailing"):
        Xp, yp = stripe_partition(X, y, 3)
    assert Xp.shape == (3, 3, 2) and yp.shape == (3, 3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")         # exact split: no warning
        Xp, yp = stripe_partition(X[:9], y[:9], 3)
    assert Xp.shape == (3, 3, 2)


# ---------------------------------------------------------------------------
# randomized membership interleavings + mid-stream persistence (ISSUE 9)
# ---------------------------------------------------------------------------

from repro.fleet import FleetConfig, GPFleet  # noqa: E402


def _stream_fleet(seed=0, num_agents=4):
    cfg = FleetConfig(num_agents=num_agents, input_dim=2, online=True,
                      window=8, chunk=4, dac_iters=30, method="rbcm",
                      theta0=(0.8, 0.8, 1.0, 0.2))
    rng = np.random.default_rng(seed)
    Xp = rng.uniform(0.0, 1.0, (num_agents, 5, 2))
    yp = rng.standard_normal((num_agents, 5))
    return GPFleet(cfg).fit(Xp, yp, train=False), rng


@pytest.mark.parametrize("seed", [0, 1])
def test_randomized_membership_interleaving_stays_healthy(seed):
    """Any observe/leave/join/predict interleaving leaves the consensus
    graph connected and every prediction finite with positive variance."""
    fleet, rng = _stream_fleet(seed)
    Xs = rng.uniform(0.0, 1.0, (6, 2))
    for _ in range(10):
        m = fleet.num_agents
        op = rng.choice(["observe", "observe", "leave", "join", "predict"])
        if op == "observe":
            fleet.observe(rng.uniform(0.0, 1.0, (m, 2)),
                          rng.standard_normal(m))
        elif op == "leave" and m > 2:
            fleet.leave(int(rng.integers(m)))
        elif op == "join" and m < 6:
            fleet.join(rng.uniform(0.0, 1.0, (3, 2)),
                       rng.standard_normal(3))
        elif op == "predict":
            mean, var, _ = fleet.predict(Xs)
            assert np.isfinite(np.asarray(mean)).all()
            assert np.isfinite(np.asarray(var)).all()
            assert (np.asarray(var) > 0.0).all()
        assert is_connected(fleet.A)
        assert fleet.num_agents == fleet._online_state.num_agents
    mean, var, _ = fleet.predict(Xs)
    assert np.isfinite(np.asarray(mean)).all()
    h = fleet.health()
    assert h["graph_connected"] and h["graph_components"] == 1


def test_online_evictions_counter_tracks_window_counts():
    """gp_online_evictions_total counts, on the host, every agent-event
    that evicted: an observe on a full window, an evict_oldest on a
    non-empty one, across filling, full, join, leave and evict events. It
    equals what the device's window counts imply, and the fleet's mirror
    of the counts stays equal to them."""
    from repro.obs import default_registry

    counter = default_registry().counter("gp_online_evictions_total")
    v0 = counter.value()
    rng = np.random.default_rng(4)
    cfg = FleetConfig(num_agents=3, input_dim=2, online=True, window=4,
                      chunk=4, dac_iters=30, method="rbcm",
                      theta0=(0.8, 0.8, 1.0, 0.2))
    fleet = GPFleet(cfg).fit(rng.uniform(0.0, 1.0, (3, 2, 2)),
                             rng.standard_normal((3, 2)), train=False)
    expected = 0

    def counts():
        return np.asarray(fleet.window_counts)

    def observe_all():
        nonlocal expected
        expected += int(np.sum(counts() >= 4))
        m = fleet.num_agents
        fleet.observe(rng.uniform(0.0, 1.0, (m, 2)), rng.standard_normal(m))

    def evict(agent):
        nonlocal expected
        expected += int(counts()[agent] > 0)
        fleet.evict_oldest(agent)

    for _ in range(4):                  # 2 -> 4 slots: filling, then full
        observe_all()
    fleet.join(rng.uniform(0.0, 1.0, (6, 2)), rng.standard_normal(6))
    fleet.join()                        # an empty joiner warms up
    observe_all()
    fleet.leave(1)                      # counts (4, 4, 4, 1)
    evict(0)
    evict(3)
    for _ in range(4):
        evict(2)
    evict(2)                            # empty by now: not an eviction
    observe_all()                       # counts (4, 4, 1, 1)
    # 0 + 0 + 3 + 3 while filling, 4 after the joins, 1 + 1 + 4 evicts,
    # 1 from the last observe (agent 1's window was still full)
    assert expected == 17
    assert np.array_equal(fleet._counts_host, counts())
    assert counter.value() - v0 == expected
    observe_all()                       # counts (4, 4, 2, 2): drift-ready
    fleet.drift(iters=2)
    observe_all()
    assert expected == 17 + 2 + 2
    assert np.array_equal(fleet._counts_host, counts())
    assert counter.value() - v0 == expected


def test_save_load_mid_stream_is_bitwise(tmp_path):
    """save() -> load() in the middle of a stream round-trips the window
    state bit for bit, and the loaded fleet continues the stream with
    bitwise-identical served predictions."""
    fleet, rng = _stream_fleet(3)
    for _ in range(4):
        fleet.observe(rng.uniform(0.0, 1.0, (4, 2)),
                      rng.standard_normal(4))
    Xs = rng.uniform(0.0, 1.0, (6, 2))
    mean0, var0, _ = fleet.predict(Xs)

    fleet.save(str(tmp_path))
    loaded = GPFleet.load(str(tmp_path))
    a, b = fleet._online_state, loaded._online_state
    for field in ("log_theta", "Xw", "yw", "L", "alpha", "count", "jitter"):
        assert np.array_equal(np.asarray(getattr(a, field)),
                              np.asarray(getattr(b, field))), field
    mean1, var1, _ = loaded.predict(Xs)
    assert np.array_equal(np.asarray(mean0), np.asarray(mean1))
    assert np.array_equal(np.asarray(var0), np.asarray(var1))

    # continue the stream on BOTH fleets with the same data: still bitwise
    xs, ys = rng.uniform(0.0, 1.0, (4, 2)), rng.standard_normal(4)
    fleet.observe(xs, ys)
    loaded.observe(xs, ys)
    m2, v2, _ = fleet.predict(Xs)
    m3, v3, _ = loaded.predict(Xs)
    assert np.array_equal(np.asarray(m2), np.asarray(m3))
    assert np.array_equal(np.asarray(v2), np.asarray(v3))
