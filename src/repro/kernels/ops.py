"""Jit'd public wrappers around the Pallas kernels: padding to tile-aligned
shapes, dtype handling, CPU interpret-mode fallback, and the pure-jnp path
used under pjit dry-runs (use_pallas=False).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .cholupdate import (cholupdate_evict_pallas, cholupdate_pallas,
                         panel_rows)
from .nll_grad import nll_grad_pallas
from .rbf_gram import rbf_gram_pallas
from .rbf_matvec import rbf_matvec_pallas
from .flash_attention import flash_attention_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tiles(x1, x2, lengthscales, bn: int, bm: int):
    """Scaled, padded kernel operands: a (N', 8k) and the transposed
    bt (8k, M'), tile sizes clamped to the inputs (ragged sizes pad)."""
    N, M = x1.shape[0], x2.shape[0]
    bn_ = min(bn, max(8, N)); bm_ = min(bm, max(8, M))
    a = _pad_to(_pad_to((x1 / lengthscales).astype(jnp.float32), 8, 1), bn_, 0)
    b = _pad_to(_pad_to((x2 / lengthscales).astype(jnp.float32), 8, 1), bm_, 0)
    return a, b.T, bn_, bm_


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("with_noise", "use_pallas", "interpret",
                                   "bn", "bm"))
def rbf_gram(x1, x2, lengthscales, sigma_f, noise=0.0, with_noise: bool = False,
             use_pallas: bool | None = None, interpret: bool | None = None,
             bn: int = 256, bm: int = 256):
    """Public RBF Gram op. x1 (N,D), x2 (M,D) -> (N,M).

    `with_noise=True` adds noise^2 on the global diagonal (square case)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.rbf_gram_ref(x1, x2, lengthscales, sigma_f,
                                noise if with_noise else 0.0)
    if interpret is None:
        interpret = not _on_tpu()
    N, M = x1.shape[0], x2.shape[0]
    a, bt, bn_, bm_ = _tiles(x1, x2, lengthscales, bn, bm)
    out = rbf_gram_pallas(a, bt, jnp.asarray(sigma_f) ** 2,
                          jnp.asarray(noise) ** 2, with_noise=with_noise,
                          dims=x1.shape[1], bn=bn_, bm=bm_,
                          interpret=interpret)
    return out[:N, :M]


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "interpret", "bq", "bk"))
def flash_attention(q, k, v, causal: bool = True, window: int | None = None,
                    use_pallas: bool | None = None, interpret: bool | None = None,
                    bq: int = 256, bk: int = 256):
    """Public attention op. q (B,H,Sq,D), k/v (B,KH,Sk,D)."""
    Sq, Sk = q.shape[2], k.shape[2]

    def _divisor_block(n, cap):
        b = min(cap, n)
        while n % b:
            b -= 1
        return b

    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        # chunked jnp flash (custom VJP): same memory behaviour as the TPU
        # kernel — O(S*chunk) transients, backward recomputes chunk scores
        from .flash_jnp import flash_attention_jnp
        return flash_attention_jnp(q, k, v, causal, window,
                                   _divisor_block(Sk, 1024))
    if interpret is None:
        interpret = not _on_tpu()
    bq_ = _divisor_block(Sq, bq)
    bk_ = _divisor_block(Sk, bk)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq_, bk=bk_, interpret=interpret)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bn", "bm"))
def rbf_matvec(x1, x2, v, lengthscales, sigma_f, use_pallas: bool | None = None,
               interpret: bool | None = None, bn: int = 256, bm: int = 256):
    """Fused k(X1,X2) @ v — O(N+M) memory (streaming prediction mean)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.rbf_matvec_ref(x1, x2, v, lengthscales, sigma_f)
    if interpret is None:
        interpret = not _on_tpu()
    a, bt, bn_, bm_ = _tiles(x1, x2, lengthscales, bn, bm)
    vp = _pad_to(v.astype(jnp.float32), bm_, 0)   # zero-pad: no contribution
    out = rbf_matvec_pallas(a, bt, vp, jnp.asarray(sigma_f) ** 2,
                            dims=x1.shape[1], bn=bn_, bm=bm_,
                            interpret=interpret)
    return out[:x1.shape[0]]


@partial(jax.jit, static_argnames=("bn", "use_pallas", "interpret"))
def kmn_stats(Z, X, y, lengthscales, sigma_f, L, bn: int = 4096,
              use_pallas: bool | None = None,
              interpret: bool | None = None):
    """Blocked whitened Titsias statistics A A^T (m, m) and A y (m,) for
    A = L^-1 Kmn, Kmn = k(Z, X) and `L` (m, m) lower-triangular (the
    Cholesky factor of Kmm) — the one O(N) pass of a sparse-expert fit
    (core.sparse.fit_sparse_experts).

    X (N, D) is streamed one (m, bn) kernel panel at a time through the
    same Gram math as `rbf_gram` (Pallas on TPU, jnp elsewhere), so
    transient memory is O(m bn) at any N. The padded tail reuses the
    zero-weight idiom of `rbf_matvec`: pad columns are multiplied by a 0
    weight before the panel products, so they contribute to neither
    statistic.

    Each panel is whitened before its products, so the sums accumulate
    well-scaled terms: whitening Kmn Knm after the sum squares Kmm's
    condition number into its roundoff (float32 at 100k points: garbage).
    """
    N, D = X.shape
    bn_ = min(bn, max(1, N))
    Xb = _pad_to(X, bn_, 0)
    wb = _pad_to(jnp.ones((N,), X.dtype), bn_, 0)
    yb = _pad_to(y.astype(X.dtype), bn_, 0)
    nblk = Xb.shape[0] // bn_
    blocks = (Xb.reshape(nblk, bn_, D), wb.reshape(nblk, bn_),
              yb.reshape(nblk, bn_))

    def body(carry, blk):
        B, b = carry
        Xi, wi, yi = blk
        Kb = rbf_gram(Z, Xi, lengthscales, sigma_f, use_pallas=use_pallas,
                      interpret=interpret) * wi[None, :]
        Kb = jax.scipy.linalg.solve_triangular(L, Kb, lower=True)
        return (B + Kb @ Kb.T, b + Kb @ yi), None

    m = Z.shape[0]
    init = (jnp.zeros((m, m), X.dtype), jnp.zeros((m,), X.dtype))
    (B, b), _ = jax.lax.scan(body, init, blocks)
    return B, b


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bn", "bm"))
def nll_grad_fused(log_theta, d2u, inner, K=None, use_pallas: bool | None = None,
                   interpret: bool | None = None, bn: int = 256,
                   bm: int = 256):
    """Fused trace-identity NLL gradient (paper eq. 4) in log-theta coords.

    Given the once-per-fit unscaled diff^2 stack `d2u` (D, N, N) from
    core.training.cache and the Cholesky-derived `inner` = C^-1 - alpha
    alpha^T (N, N) of the current iteration, returns dNLL/dlog_theta (D+2,)
    in ONE pass: K is rebuilt tile-by-tile in registers and all D+2 trace
    components accumulate without materializing the (D+2, N, N) derivative
    stack — O(N^2) gradient memory instead of O(D N^2), one read of
    d2u/inner instead of D+2.

    `K` optionally reuses an already-materialized kernel matrix on the jnp
    path (the caller computed it for the Cholesky anyway); the Pallas path
    ignores it — the in-register rebuild is cheaper than streaming another
    (N, N) operand from HBM.

    Like the other TPU kernels the Pallas path COMPUTES in float32, so the
    auto default only engages it for float32 inputs: float64 callers (x64
    training, where the 1e-6 fused-vs-autodiff equivalence is asserted)
    keep the dtype-exact jnp path unless they force use_pallas=True.
    """
    if use_pallas is None:
        use_pallas = _on_tpu() and d2u.dtype == jnp.float32
    if not use_pallas:
        return ref.nll_grad_fused_ref(log_theta, d2u, inner, K=K, bn=bn)
    if interpret is None:
        interpret = not _on_tpu()
    D, N = d2u.shape[0], d2u.shape[1]
    theta = jnp.exp(log_theta)
    ls, sigma_f, sigma_eps = theta[:-2], theta[-2], theta[-1]
    bn_ = min(bn, max(8, N)); bm_ = min(bm, max(8, N))
    d2p = _pad_to(_pad_to(d2u.astype(jnp.float32), bn_, 1), bm_, 2)
    innerp = _pad_to(_pad_to(inner.astype(jnp.float32), bn_, 0), bm_, 1)
    params = jnp.concatenate([(1.0 / ls**2), sigma_f[None] ** 2]) \
        .astype(jnp.float32).reshape(1, D + 1)
    rows = nll_grad_pallas(d2p, innerp, params, bn=bn_, bm=bm_,
                           interpret=interpret)
    sums = jnp.sum(rows, axis=0).astype(d2u.dtype)
    return jnp.concatenate([sums[:D] / ls**2, sums[D:D + 1],
                            (sigma_eps**2 * sums[D + 1:D + 2])])


def _panel(n: int, bk: int) -> int:
    """Panel height of the Pallas sweeps: the kernels double-buffer
    (bk, n) panels in VMEM, so the height is capped to their budget (the
    jnp path takes `bk` as given); a multiple of 8, or n itself."""
    return min(n, max(8, min(bk, panel_rows(n + 128), n) // 8 * 8))


@partial(jax.jit, static_argnames=("downdate", "use_pallas", "interpret",
                                   "bk"))
def cholupdate(L, x, downdate: bool = False, use_pallas: bool | None = None,
               interpret: bool | None = None, bk: int = 256):
    """Rank-1 Cholesky update/downdate chol(L L^T +/- x x^T) — O(n^2).

    L (n, n) lower-triangular, x (n,). The pure-jnp path keeps the input
    dtype (float64-safe); the Pallas path COMPUTES in float32 like the
    other TPU kernels but casts back to L.dtype — callers that persist the
    factor in a pytree (core/online) rely on the dtype being preserved.
    The Pallas path's passes over the factor are the transposes into and
    out of the kernel's layout (L^T; no padding: the grid takes a partial
    last panel).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.cholupdate_ref(L, x, downdate, bk)
    if interpret is None:
        interpret = not _on_tpu()
    sign = -1.0 if downdate else 1.0
    U = cholupdate_pallas(L.T.astype(jnp.float32), x, sign,
                          bk=_panel(L.shape[0], bk), interpret=interpret)
    return U.T.astype(L.dtype)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bk"))
def cholupdate_evict(L, fill, evict=True, use_pallas: bool | None = None,
                     interpret: bool | None = None, bk: int = 256):
    """Drop slot 0 of a factor: L (n, n) lower with L L^T = A in, out the
    factor of A[1:, 1:] moved one slot up-left, with slot n-1 a fresh
    slot whose row and column are e_{n-1} * fill — O(n^2), one rank-1
    update of the trailing block with the evicted column L[1:, 0].
    Where `evict` (a bool, traced is fine) is False, L comes back as it
    went in. The result keeps L.dtype.

    jnp path: ref.cholupdate_ref(shift=1), whose sweep writes the shift;
    the fresh slot is written over the stale last row and column, and
    `evict` selects between the result and L.

    Pallas path: one pass in, the sweep, one pass out. In: L^T, the
    kernel's layout. The kernel sweeps the whole factor from column 1
    (column 0 is skipped exactly, not rotated by a zero), writes each
    column one slot up-left, writes the fresh slot, and copies a factor
    whose `evict` is False through unchanged. Out: the transpose back. So
    the shift is written inside the kernel, and a vmapped fleet with
    windows both full and not is served by the same two passes, with no
    select over the factors.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    n = L.shape[0]
    if not use_pallas or n < 2:
        out = ref.cholupdate_ref(L, L[:, 0], bk=bk, shift=1)
        evec = (fill * (jnp.arange(n) == n - 1)).astype(L.dtype)
        out = out.at[n - 1].set(evec).at[:, n - 1].set(evec)
        return jnp.where(evict, out, L)
    if interpret is None:
        interpret = not _on_tpu()
    U = cholupdate_evict_pallas(L.T.astype(jnp.float32), fill, evict,
                                bk=_panel(n, bk), interpret=interpret)
    return U.T.astype(L.dtype)
