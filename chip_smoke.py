#!/usr/bin/env python3
"""Bring-up smoke of the GP fleet on a TPU: fit -> serve -> stream -> sparse.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the agent-sharded path on four chips

Drives the main path once through the entry points users call — `GPFleet`
and its `ServingScheduler` front door (`GPFleet.to_server`) — in float32,
on seeded synthetic fields, and fails (non-zero exit, no result line) when
JAX finds no TPU or when any check fails:

  dense      M=16 agents x Ni=4096 points (D=2): a few dec-apx ADMM
             iterations (fused Pallas `nll_grad`), then `rbcm` and
             `nn_rbcm` served to 4096 queries as ragged requests
  streaming  M=16 windows of W=2048: `observe` events, each evicting the
             oldest point through the Pallas rank-1 `cholupdate`
  sparse     M=4 agents x 100,000 points, m=256 inducing points, fitted
             through `kmn_stats`/`rbf_gram`; `npae_sparse` and `nn_rbcm`
  --chips 4  `GPFleet.shard()` on a 4-device agent mesh (rbcm, routed
             nn_rbcm, npae_sparse) against the replicated engine, and the
             `dec-apx-sharded` trainer against simulated `dec-apx`

Each phase checks that its outputs are finite, that every kernel's chip
output matches its `kernels/ref.py` oracle, that the programs it compiled
hold a Pallas kernel (`tpu_custom_call`: the kernels ran, not the jnp
fallbacks), and its own reference (RMSE to the latent field, a fresh
Cholesky, the replicated engine). Seconds and memory are printed for
information; they are not a benchmark. The last line of standard output is
one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# -- sizes -------------------------------------------------------------------
TRUE_THETA = (1.2, 0.3, 1.3, 0.1)   # (l_1, l_2, sigma_f, sigma_eps)
RFF_FEATURES = 1024                 # random-Fourier draw of the latent field
DENSE_M, DENSE_NI, NQ = 16, 4096, 4096
ADMM_ITERS = 3
WINDOW, OBSERVE_EVENTS = 2048, 3
SPARSE_M, SPARSE_NI, SPARSE_INDUCING = 4, 100_000, 256
SLOT = 256                          # scheduler slot = engine chunk
SHARD_M, SHARD_NI = 16, 2048        # --chips 4 dense fleet
TRAIN_M, TRAIN_NI, TRAIN_ITERS = 4, 2048, 5   # --chips 4 trainer parity

# -- bounds ------------------------------------------------------------------
# RMSE of the served mean to the seeded latent field (sigma_f = 1.3: a fleet
# that learned nothing scores about 1.3). Each bound is 1.1x the RMSE of a
# CPU run of this script's phases at these sizes and seed 0 (jnp reference
# paths, float32): the chip computes the same float32 algorithm, and 10%
# is room for its different summation orders. CPU RMSEs: dense rbcm and
# nn_rbcm 0.007142, sparse npae_sparse 0.002483, sparse nn_rbcm 0.002559.
RMSE_BOUND = {
    "dense/rbcm": 0.00786,
    "dense/nn_rbcm": 0.00786,
    "sparse/npae_sparse": 0.00273,
    "sparse/nn_rbcm": 0.00281,
}


def require(ok, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def close(name, got, want, atol, scale=1.0):
    """|got - want| <= atol * scale, elementwise."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want) / scale))
    require(np.all(np.isfinite(got)), f"{name}: non-finite output")
    require(err <= atol, f"{name}: max scaled error {err:.3e} > {atol:.1e}")
    print(f"  check {name}: max scaled error {err:.3e} <= {atol:.1e}")


def finite(name, *arrays):
    import numpy as np
    for a in arrays:
        require(np.all(np.isfinite(np.asarray(a))), f"{name}: non-finite")


_COMPILE_S = [0.0]


@contextlib.contextmanager
def phase(name: str, **shapes):
    """Print the phase's shapes, wall and compile seconds and the device's
    peak bytes (information only)."""
    import jax
    print(f"phase {name}: " + ", ".join(f"{k}={v}" for k, v in
                                        shapes.items()), flush=True)
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    yield
    wall, comp = time.perf_counter() - t0, _COMPILE_S[0] - c0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: passed; {wall:.1f} s wall, of which {comp:.1f} s "
          f"compiling (informational, not a benchmark); peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


@contextlib.contextmanager
def pallas_programs(name: str):
    """Dump every program compiled in the block (JAX writes the module it
    hands the compiler, on a persistent-cache hit too); require a Pallas
    kernel (`tpu_custom_call`) in at least one of them."""
    import jax
    with tempfile.TemporaryDirectory() as ir:
        jax.config.update("jax_dump_ir_to", ir)
        try:
            yield
        finally:
            jax.config.update("jax_dump_ir_to", "")
        hits = sorted(p.name.split("_", 2)[2].removesuffix("_compile.mlir")
                      for p in Path(ir).glob("*.mlir")
                      if "tpu_custom_call" in p.read_text())
    require(hits, f"{name}: no program compiled here holds a Pallas kernel")
    print(f"  check {name}: tpu_custom_call in {', '.join(hits)}")


def field(key, n_train: int, n_query: int):
    """Seeded latent GP field f: noisy training samples (X, y) and query
    points with their noise-free values (Xq, fq)."""
    import jax
    import jax.numpy as jnp
    from repro.core.gp import pack
    from repro.data import gp_sample_field, random_inputs
    X = random_inputs(key, n_train + n_query, dtype=jnp.float32)
    f, y = gp_sample_field(jax.random.fold_in(key, 1), X,
                           pack(list(TRUE_THETA[:2]), *TRUE_THETA[2:]),
                           rff_features=RFF_FEATURES)
    return X[:n_train], y[:n_train], X[n_train:], f[n_train:]


def serve(fleet, method: str, Xq):
    """Serve Xq through the scheduler front door as ragged requests."""
    import numpy as np
    Xq = np.asarray(Xq)
    rng = np.random.default_rng(0)
    cuts = np.cumsum(rng.integers(1, 2 * SLOT, size=len(Xq)))
    cuts = [0] + [int(c) for c in cuts[cuts < len(Xq)]] + [len(Xq)]
    with fleet.to_server(batch=SLOT, method=method) as srv:
        futs = [srv.add_request(Xq[a:b]) for a, b in zip(cuts, cuts[1:])]
        outs = [f.result(timeout=600) for f in futs]
    mean = np.concatenate([np.asarray(m) for m, _ in outs])
    var = np.concatenate([np.asarray(v) for _, v in outs])
    print(f"  served {method}: {len(futs)} ragged requests, "
          f"{len(Xq)} queries")
    return mean, var


def check_rmse(name: str, mean, var, fq):
    import numpy as np
    finite(name, mean, var)
    require(np.all(np.asarray(var) > 0), f"{name}: non-positive variance")
    rmse = float(np.sqrt(np.mean((np.asarray(mean, np.float64)
                                  - np.asarray(fq, np.float64)) ** 2)))
    bound = RMSE_BOUND[name]
    require(rmse <= bound, f"{name}: RMSE {rmse:.4f} > bound {bound}")
    print(f"  check {name}: RMSE to latent field {rmse:.6f} <= {bound}")


# -- phases ------------------------------------------------------------------

def dense_phase(key):
    import jax
    import jax.numpy as jnp
    from repro.core.gp import diff2_stack, stripe_partition, unpack
    from repro.core.gp.nll import inner_from_cov
    from repro.core.training import cov_from_cache
    from repro.fleet import FleetConfig, GPFleet
    from repro.kernels import ops, ref

    X, y, Xq, fq = field(key, DENSE_M * DENSE_NI, NQ)
    Xp, yp = stripe_partition(X, y, DENSE_M)
    cfg = FleetConfig(num_agents=DENSE_M, theta0=TRUE_THETA,
                      trainer="dec-apx", admm_iters=ADMM_ITERS,
                      method="rbcm", stream_mean=True, chunk=SLOT)
    with phase("dense fit", agents=DENSE_M, points_per_agent=DENSE_NI,
               admm_iters=ADMM_ITERS):
        with pallas_programs("dense fit"):
            fleet = GPFleet(cfg).fit(Xp, yp)
            jax.block_until_ready(fleet.fitted.alpha)
        finite("dense fit", fleet.thetas, fleet.fitted.L, fleet.fitted.alpha)
        lt = fleet.log_theta
        d2u = diff2_stack(Xp[0])
        C, _ = cov_from_cache(lt, d2u)
        inner = inner_from_cov(C, yp[0])
        g = ops.nll_grad_fused(lt, d2u, inner, use_pallas=True)
        g_ref = ref.nll_grad_fused_ref(lt, d2u, inner)
        # f32 sums of N^2 = 16.7M terms in two summation orders, relative
        # to each component's sum of absolute terms (the gradient is linear
        # in `inner` and every dC/dlog theta entry is >= 0); near the
        # optimum the sums cancel, so max|g| is no scale for their roundoff
        close("nll_grad vs ref", g, g_ref, 1e-4,
              scale=ref.nll_grad_fused_ref(lt, d2u, jnp.abs(inner)))
    with phase("dense serve", queries=NQ, slot=SLOT):
        with pallas_programs("dense serve"):
            for method in ("rbcm", "nn_rbcm"):
                mean, var = serve(fleet, method, Xq)
                check_rmse(f"dense/{method}", mean, var, fq)
        ls, sf, _ = unpack(lt)
        alpha0 = fleet.fitted.alpha[0]
        mv = ops.rbf_matvec(Xq, Xp[0], alpha0, ls, sf, use_pallas=True)
        mv_ref = ref.rbf_matvec_ref(Xq, Xp[0], alpha0, ls, sf)
        mag = ref.rbf_matvec_ref(Xq, Xp[0], jnp.abs(alpha0), ls, sf)
        # f32 accumulation of 4096 terms, relative to sum_j |k_ij alpha_j|
        close("rbf_matvec vs ref", mv, mv_ref, 1e-4, scale=mag + 1e-30)
    return Xp, yp


def streaming_phase(Xp, yp):
    import jax
    import jax.numpy as jnp
    from repro.core.prediction import chol_factors
    from repro.fleet import FleetConfig, GPFleet
    from repro.kernels import ops, ref

    M = Xp.shape[0]
    cfg = FleetConfig(num_agents=M, theta0=TRUE_THETA, online=True,
                      window=WINDOW, method="rbcm")
    with phase("streaming", agents=M, window=WINDOW, events=OBSERVE_EVENTS):
        fleet = GPFleet(cfg).fit(Xp[:, :WINDOW], yp[:, :WINDOW],
                                 train=False)
        with pallas_programs("streaming observe"):
            for e in range(OBSERVE_EVENTS):  # full windows: evict + append
                fleet.observe(Xp[:, WINDOW + e], yp[:, WINDOW + e])
            jax.block_until_ready(fleet.fitted.L)
        f = fleet.fitted
        finite("streaming", f.L, f.alpha)
        L_ref, alpha_ref = chol_factors(fleet.log_theta, f.Xp, f.yp,
                                        cfg.jitter)
        L0 = L_ref[0]
        up = ops.cholupdate_evict(L0, 1.0, use_pallas=True)
        up_ref = ref.cholupdate_ref(L0, L0[:, 0], shift=1)
        scale = float(jnp.max(jnp.abs(L_ref)))
        # f32 factors of a W=2048 window: incremental (3 evict/append
        # sweeps) vs a fresh Cholesky, relative to the factor's largest
        # entry
        close("online factor vs fresh Cholesky", f.L, L_ref, 1e-3,
              scale=scale)
        n = WINDOW - 1
        close("cholupdate vs ref", up[:n, :n], up_ref[:n, :n], 1e-4,
              scale=scale)


def sparse_phase(key):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.gp import stripe_partition, unpack
    from repro.fleet import FleetConfig, GPFleet
    from repro.kernels import ops, ref

    X, y, Xq, fq = field(key, SPARSE_M * SPARSE_NI, NQ)
    Xp, yp = stripe_partition(X, y, SPARSE_M)
    cfg = FleetConfig(num_agents=SPARSE_M, theta0=TRUE_THETA,
                      sparse_m=SPARSE_INDUCING, method="npae_sparse",
                      stream_mean=True, chunk=SLOT)
    with phase("sparse", agents=SPARSE_M, points_per_agent=SPARSE_NI,
               inducing=SPARSE_INDUCING, queries=NQ):
        with pallas_programs("sparse fit"):
            fleet = GPFleet(cfg).fit(Xp, yp, train=False)
            jax.block_until_ready(fleet.fitted.c)
        finite("sparse fit", fleet.fitted.LB, fleet.fitted.c)
        for method in ("npae_sparse", "nn_rbcm"):
            mean, var = serve(fleet, method, Xq)
            check_rmse(f"sparse/{method}", mean, var, fq)
        ls, sf, _ = unpack(fleet.log_theta)
        Z0 = fleet.fitted.Z[0]
        Lmm0 = fleet.fitted.Lmm[0]
        K = ops.rbf_gram(Z0, Xp[0, :4096], ls, sf, use_pallas=True)
        K_ref = ref.rbf_gram_ref(Z0, Xp[0, :4096], ls, sf)
        # the fit's form: panels whitened by the agent's Kmm factor, against
        # one whitening solve of the whole Kmn. Kmn comes from the kernel
        # checked just below: L^-1 amplifies kernel roundoff (a CPU run: a
        # 5e-7 kernel difference became 5.8e-2 of the absolute-term sums),
        # so an independent Kmn would measure Kmm's conditioning, not this
        # op's panelling, padding and accumulation
        B, b = ops.kmn_stats(Z0, Xp[0], yp[0], ls, sf, Lmm0, use_pallas=True)
        A = jax.scipy.linalg.solve_triangular(
            Lmm0, ops.rbf_gram(Z0, Xp[0], ls, sf, use_pallas=True), lower=True)
        B_ref, b_ref = A @ A.T, A @ yp[0]
        # Gram entries: f32 distance expansion, relative to sigma_f^2
        close("rbf_gram vs ref", K, K_ref, 1e-4, scale=float(sf) ** 2)
        # whitened statistics: f32 sums over 100k points in panels vs one
        # product, relative to the sums of absolute terms
        close("kmn_stats B vs ref", B, B_ref, 1e-4,
              scale=np.abs(A) @ np.abs(A).T)
        close("kmn_stats b vs ref", b, b_ref, 1e-4,
              scale=np.abs(A) @ np.abs(np.asarray(yp[0])))


def four_chip_phase(key):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.gp import stripe_partition
    from repro.fleet import FleetConfig, GPFleet
    from repro.launch.mesh import make_agent_mesh

    mesh = make_agent_mesh(SHARD_M)
    require(mesh.devices.size == 4, f"agent mesh has {mesh.devices.size} "
            f"devices, not 4")

    X, y, Xq, _ = field(key, SHARD_M * SHARD_NI, NQ)
    Xp, yp = stripe_partition(X, y, SHARD_M)
    # queries next to each agent's centroid: their best-scoring agent is
    # in the routed block, where CBNN routing is exact (eta_nn keeps only
    # the best agent)
    cents = jnp.mean(Xp, axis=1)
    Xr = jnp.concatenate([cents + 0.01 * jax.random.normal(
        jax.random.fold_in(key, 9 + i), cents.shape) for i in range(16)])
    cfg = FleetConfig(num_agents=SHARD_M, theta0=TRUE_THETA,
                      graph="complete", consensus="exact", eta_nn=1e9,
                      method="nn_rbcm", chunk=SLOT)
    with phase("4-chip dense serve", agents=SHARD_M,
               points_per_agent=SHARD_NI, queries=NQ, devices=4):
        fleet = GPFleet(cfg).fit(Xp, yp, train=False)
        rep = {m: fleet.predict(Xq, method=m) for m in ("rbcm", "nn_rbcm")}
        rep_r = fleet.predict(Xr, method="nn_rbcm")
        fleet.shard(mesh)
        for m in ("rbcm", "nn_rbcm"):
            mean, var, _ = fleet.predict(Xq, method=m)
            scale = float(np.max(np.abs(np.asarray(rep[m][0]))))
            # sharded exact ring sums vs replicated complete-graph DAC:
            # f32 reduction order
            close(f"sharded {m} mean vs replicated", mean, rep[m][0], 1e-4,
                  scale=scale)
            close(f"sharded {m} var vs replicated", var, rep[m][1], 1e-4,
                  scale=float(np.max(np.asarray(rep[m][1]))))
        fleet.shard(mesh, routed=True)
        mean, var, info = fleet.predict(Xr, method="nn_rbcm")
        close("routed nn_rbcm mean vs replicated", mean, rep_r[0], 1e-4,
              scale=float(np.max(np.abs(np.asarray(rep_r[0])))))
        close("routed nn_rbcm var vs replicated", var, rep_r[1], 1e-4,
              scale=float(np.max(np.asarray(rep_r[1]))))

    X, y, Xq, _ = field(jax.random.fold_in(key, 3), SPARSE_M * SPARSE_NI,
                        NQ)
    Xp, yp = stripe_partition(X, y, SPARSE_M)
    cfg = FleetConfig(num_agents=SPARSE_M, theta0=TRUE_THETA,
                      sparse_m=SPARSE_INDUCING, method="npae_sparse",
                      chunk=SLOT)
    with phase("4-chip sparse serve", agents=SPARSE_M,
               points_per_agent=SPARSE_NI, inducing=SPARSE_INDUCING,
               devices=4):
        fleet = GPFleet(cfg).fit(Xp, yp, train=False)
        mean_r, var_r, _ = fleet.predict(Xq)
        fleet.shard(make_agent_mesh(SPARSE_M))
        mean, var, _ = fleet.predict(Xq)
        # ring allgather + the same per-query NPAE solve on every shard
        close("sharded npae_sparse mean vs replicated", mean, mean_r, 1e-4,
              scale=float(np.max(np.abs(np.asarray(mean_r)))))
        close("sharded npae_sparse var vs replicated", var, var_r, 1e-4,
              scale=float(np.max(np.asarray(var_r))))

    X, y, _, _ = field(jax.random.fold_in(key, 4), TRAIN_M * TRAIN_NI, 1)
    Xp, yp = stripe_partition(X, y, TRAIN_M)
    base = FleetConfig(num_agents=TRAIN_M, theta0=(0.8, 0.5, 1.0, 0.2),
                       graph="cycle", admm_iters=TRAIN_ITERS)
    with phase("4-chip training", agents=TRAIN_M,
               points_per_agent=TRAIN_NI, iters=TRAIN_ITERS, devices=4):
        sim = GPFleet(base.replace(trainer="dec-apx")).fit(Xp, yp)
        shd = GPFleet(base.replace(trainer="dec-apx-sharded"),
                      mesh=make_agent_mesh(TRAIN_M)).fit(Xp, yp)
        # the same eq. 34 sweep on the same cycle graph: per-agent thetas
        # agree to f32 roundoff of the local gradients
        close("dec-apx-sharded thetas vs dec-apx", shd.thetas, sim.thetas,
              1e-4, scale=float(jnp.max(jnp.abs(sim.thetas))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit/serve/stream/sparse on one chip; 4: only "
                         "the agent-sharded path and its references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.launch.runtime import configure_runtime
    print(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {configure_runtime()}")
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: _COMPILE_S.__setitem__(
            0, _COMPILE_S[0] + secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        four_chip_phase(key)
    else:
        Xp, yp = dense_phase(key)
        streaming_phase(Xp, yp)
        del Xp, yp
        sparse_phase(jax.random.fold_in(key, 2))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
