"""Compile the main path's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jax and compiles for a described
`v5e:2x2` topology. Interpret mode (what the other kernel tests run) cannot
see tiling, lowering or VMEM limits; this file can. Each test compiles one
kernel in the form the fleet calls it, at the shapes `chip_smoke.py` runs,
and asserts that the program holds a Mosaic kernel (`tpu_custom_call`), so
the Pallas path — not the jnp reference — is what was compiled.

Only the fixture below describes the topology: the TPU runtime may be
loaded by one process at a time, so nothing here touches it at import.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    # the suite runs with x64 on (conftest); the chip path runs without it,
    # and Mosaic refuses the i64 index maps that x64 would give the kernels
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30    # fits v5e HBM


def test_nll_grad_vmapped_over_agents(one_chip, no_cache):
    """Training form: the fused gradient vmapped over M=16 agents, D=2,
    N=4096 (core.training.cache under train_dec_apx_gp)."""
    M, D, N = 16, 2, 4096

    def grads(lt, d2u, inner):
        return jax.vmap(lambda a, b: ops.nll_grad_fused(
            lt, a, b, use_pallas=True, interpret=False))(d2u, inner)
    _assert_kernel(_compile(grads, one_chip, (D + 2,), (M, D, N, N),
                            (M, N, N)))


def test_rbf_matvec_vmapped_over_agents(one_chip, no_cache):
    """Serving form: the streamed mean of 16 agents x 4096 points at 4096
    queries (core.prediction.local.stream_means)."""
    M, N, Nt, D = 16, 4096, 4096, 2

    def means(Xs, Xp, alpha, ls, sf):
        return jax.vmap(lambda Xi, ai: ops.rbf_matvec(
            Xs, Xi, ai, ls, sf, use_pallas=True, interpret=False))(Xp, alpha)
    _assert_kernel(_compile(means, one_chip, (Nt, D), (M, N, D), (M, N),
                            (D,), ()))


def test_kmn_stats_vmapped_over_agents(one_chip, no_cache):
    """Sparse fit form: m=256 inducing points over 100k points per agent
    in bn=4096 panels, each whitened by the agent's Kmm factor, vmapped
    over 4 agents (fit_sparse_experts)."""
    M, m, N, D = 4, 256, 100_000, 2

    def stats(Z, X, y, L, ls, sf):
        return jax.vmap(lambda Zi, Xi, yi, Li: ops.kmn_stats(
            Zi, Xi, yi, ls, sf, Li, bn=4096, use_pallas=True,
            interpret=False))(Z, X, y, L)
    _assert_kernel(_compile(stats, one_chip, (M, m, D), (M, N, D), (M, N),
                            (M, m, m), (D,), ()))


@pytest.mark.parametrize("W", [2048, 8192])
def test_cholupdate_evict_vmapped_over_agents(one_chip, no_cache, W):
    """Streaming form: the shifted rank-1 update of a full W-slot window
    (core.online evict), vmapped over 16 agents. W=2048 is the largest
    window the benchmarks run; W=8192 checks that the panel height shrinks
    to stay inside the VMEM budget."""
    M = 16

    def evict(L):
        return jax.vmap(lambda Li: ops.cholupdate_evict(
            Li, 1.0, use_pallas=True, interpret=False))(L)
    _assert_kernel(_compile(evict, one_chip, (M, W, W)))


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(([^)]*)\)")
_COMPUTATION = re.compile(r"^%([\w.\-]+) \((.*)\) -> .* \{$")


def _dims(s):
    return tuple(int(d) for d in s.split(",") if d)


def _factor_instructions(hlo, M, W):
    """The optimized entry computation's instructions that produce a whole
    (M, >= W-1, >= W-1) batch of factors, as (kernels, in-place updates,
    passes). An in-place update is a dynamic-update-slice or scatter of a
    small update, or a fusion rooted in one whose only whole-factor
    operand is the buffer it updates; every other instruction reads and
    writes whole factors."""
    shapes, roots, params, entry = {}, {}, {}, []
    comp = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            comp = "ENTRY"
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            params[comp] = [_dims(p) for p in
                            re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
            continue
        if line.startswith("}"):
            comp = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op, args = m.groups()
        shapes[name] = _dims(dims)
        if line.lstrip().startswith("ROOT"):
            roots[comp] = op
        if comp == "ENTRY":
            entry.append((name, _dims(dims), op, args, line))

    def whole(d):
        return len(d) == 3 and d[0] == M and min(d[1:]) >= W - 1

    def small(d):
        return int(jnp.prod(jnp.asarray(d))) <= M * (W + 8) * 8

    kernels, in_place, passes = [], [], []
    for name, dims, op, args, line in entry:
        if not whole(dims) or op in ("parameter", "bitcast", "copy-done",
                                     "get-tuple-element", "tuple"):
            continue
        operands = re.findall(r"%([\w.\-]+)", args)
        called = re.search(r"calls=%([\w.\-]+)", line)
        if op == "custom-call" and "tpu_custom_call" in line:
            kernels.append(name)
        elif op in ("dynamic-update-slice", "scatter") and small(
                shapes[operands[-1 if op == "scatter" else 1]]):
            in_place.append(name)
        elif (op == "fusion" and roots.get(called.group(1)) in
              ("dynamic-update-slice", "scatter")
              and sum(map(whole, params[called.group(1)])) == 1):
            in_place.append(name)
        else:
            passes.append(name)
    return kernels, in_place, passes


def test_observe_fleet_evict_makes_one_pass_in_and_one_out(
        one_chip, no_cache, monkeypatch):
    """The streaming cell's observe program (M=4 windows of W=8,100, D=2;
    the Pallas evict forced on) holds, at the whole-factor batch shape,
    the evict kernel, one pass into its transposed layout, one pass out,
    and in-place row writes (the append) — no slice, pad, select or
    second-layout copy of the factors around the sweep."""
    from repro.core.online import OnlineExperts, experts, observe_fleet

    monkeypatch.setattr(experts, "cholupdate_evict", functools.partial(
        ops.cholupdate_evict, use_pallas=True, interpret=False))
    M, W, D = 4, 8100, 2

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = OnlineExperts(spec((D + 2,)), spec((M, W, D)), spec((M, W)),
                          spec((M, W, W)), spec((M, W)),
                          spec((M,), jnp.int32), spec(()))
    with jax.enable_x64(False):
        compiled = jax.jit(lambda s, x, y: observe_fleet(s, x, y)).lower(
            state, spec((M, D)), spec((M,))).compile()
    kernels, in_place, passes = _factor_instructions(compiled.as_text(), M,
                                                     W)
    assert len(kernels) == 1 and "cholupdate" in kernels[0], kernels
    assert len(passes) <= 2, passes
    assert in_place, "the append's row write is no longer in place"
