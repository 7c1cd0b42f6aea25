"""The control of `correct`: the plain reference put in the program's
place and computed one precision step below the configuration's.

The configurations state float32 with matrix products at 'highest'
precision; the next step down is 'high': every matrix product taken as
three bfloat16 products (bf16_3x: each operand split into a high and a
low bfloat16 part, hi*hi + hi*lo + lo*hi, float32 accumulation), all
other arithmetic float32. XLA's default-precision switch does not reach
its Cholesky and triangular-solve expansions, so the control writes them
blocked: every product of the O(n^3) work goes through `dot`, and only
the factorization and inverse of each diagonal block (under 1% of the
flops at the cells' sizes) are XLA's float32. The three products are
written out, not left to the backend's precision flag, so the control
computes the same on the CPU (the tests) as on the chip.

No run of the benchmark computes the control; `bench/chip_faults.py`
reads it on the chip, and the tests under bench/tests on the CPU.
"""
from __future__ import annotations

import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _bf16(x):
    # rounded to bfloat16 but kept float32: XLA may drop a round trip
    # through the bfloat16 type (excess precision), never this rounding
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def dot(a, b):
    """a @ b at 'high' precision (bf16_3x), float32 in and out."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return _HIGHEST(ah, bh) + (_HIGHEST(ah, bl) + _HIGHEST(al, bh))


def block_size(n: int) -> int:
    """Diagonal block of the blocked factorization: about n / 8, a power
    of two in [16, 256]."""
    return int(min(256, max(16, 2 ** math.floor(math.log2(max(n, 16) / 8)))))


def _pad(C, b):
    n = C.shape[-1]
    m = -(-n // b) * b
    if m == n:
        return C
    return jnp.eye(m, dtype=C.dtype).at[:n, :n].set(C)


def cholesky(C):
    """Right-looking blocked Cholesky of C (n, n), padded with an identity
    to whole blocks -> [(L_jj, L_jj^-1, panel below L_jj or None)]."""
    b = block_size(C.shape[-1])
    A = _pad(C, b)
    m = A.shape[-1]
    eye = jnp.eye(b, dtype=A.dtype)
    blocks = []
    for j in range(0, m, b):
        Ljj = jnp.linalg.cholesky(A[:b, :b])
        inv = jax.scipy.linalg.solve_triangular(Ljj, eye, lower=True)
        P = None
        if j + b < m:
            P = dot(A[b:, :b], inv.T)
            A = A[b:, b:] - dot(P, P.T)
        blocks.append((Ljj, inv, P))
    return blocks


def _pad_rows(B, blocks):
    m = len(blocks) * blocks[0][0].shape[0]
    return jnp.zeros((m,) + B.shape[1:], B.dtype).at[:B.shape[0]].set(B)


def forward(blocks, B):
    """L^-1 B for B (n, k)."""
    n = B.shape[0]
    R, out = _pad_rows(B, blocks), []
    for Ljj, inv, P in blocks:
        b = Ljj.shape[0]
        v = dot(inv, R[:b])
        out.append(v)
        if P is not None:
            R = R[b:] - dot(P, v)
    return jnp.concatenate(out, 0)[:n]


def backward(blocks, Z):
    """L^-T Z for Z (n, k)."""
    n = Z.shape[0]
    Z = _pad_rows(Z, blocks)
    b = blocks[0][0].shape[0]
    below = None
    for j in reversed(range(len(blocks))):
        _, inv, P = blocks[j]
        z = Z[j * b:(j + 1) * b]
        if P is not None:
            z = z - dot(P.T, below)
        x = dot(inv.T, z)
        below = x if below is None else jnp.concatenate([x, below], 0)
    return below[:n]


def matrix(blocks, n: int):
    """The factor L (n, n) of `cholesky`'s blocks."""
    cols, off = [], 0
    for Ljj, _, P in blocks:
        b = Ljj.shape[0]
        parts = ([jnp.zeros((off, b), Ljj.dtype)] if off else []) + [Ljj]
        if P is not None:
            parts.append(P)
        cols.append(jnp.concatenate(parts, 0))
        off += b
    return jnp.concatenate(cols, 1)[:n, :n]


def _factor_solve(C, y):
    blocks = cholesky(C)
    alpha = backward(blocks, forward(blocks, y[:, None]))[:, 0]
    return matrix(blocks, C.shape[-1]), alpha


def _inverse(C):
    blocks = cholesky(C)
    eye = jnp.eye(C.shape[-1], dtype=C.dtype)
    return backward(blocks, forward(blocks, eye))


# the linear algebra of bench.reference.dec_apx, at 'high'
LINALG = SimpleNamespace(name="high", dot=dot, inverse=_inverse,
                         factor_solve=_factor_solve)


def _kernel(A, B, theta):
    dim = A.shape[1]
    d2 = jnp.zeros((A.shape[0], B.shape[0]), jnp.float32)
    for d in range(dim):
        diff = (A[:, d, None] - B[None, :, d]) / theta[d]
        d2 = d2 + diff * diff
    return theta[dim] ** 2 * jnp.exp(-d2)


@functools.partial(jax.jit, static_argnums=(4,))
def _local(X, y, Xq, theta, jitter):
    n = X.shape[0]
    C = _kernel(X, X, theta) \
        + (theta[-1] ** 2 + jitter) * jnp.eye(n, dtype=jnp.float32)
    blocks = cholesky(C)
    alpha = backward(blocks, forward(blocks, y[:, None]))
    ks = _kernel(X, Xq, theta)
    v = forward(blocks, ks)
    var = jnp.maximum(theta[-2] ** 2 - jnp.sum(v * v, axis=0), 1e-12)
    return dot(ks.T, alpha)[:, 0], var


def rbcm(Xp, yp, Xq, theta, jitter: float):
    """`reference.rbcm` at 'high', float32, one agent at a time on the
    default device -> (mean, var) as numpy float32."""
    th = jnp.asarray(theta, jnp.float32)
    Xq = jnp.asarray(Xq, jnp.float32)
    pv = th[-2] ** 2
    s_mu = s_prec = s_beta = 0.0
    for X, y in zip(Xp, yp):
        mu, var = _local(jnp.asarray(X, jnp.float32),
                         jnp.asarray(y, jnp.float32), Xq, th, float(jitter))
        beta = 0.5 * (jnp.log(pv) - jnp.log(var))
        s_mu = s_mu + beta * mu / var
        s_prec = s_prec + beta / var
        s_beta = s_beta + beta
    prec = s_prec + (1.0 - s_beta) / pv
    return np.asarray(s_mu / prec), np.asarray(1.0 / prec)


@functools.partial(jax.jit, static_argnums=(3,))
def _window(X, y, theta, jitter):
    n = X.shape[0]
    C = _kernel(X, X, theta) \
        + (theta[-1] ** 2 + jitter) * jnp.eye(n, dtype=jnp.float32)
    return _factor_solve(C, y)


def window_factor(Xw, yw, theta, jitter: float):
    """`reference.window_factor` at 'high', float32 -> (L, alpha) numpy."""
    th = jnp.asarray(theta, jnp.float32)
    out = [_window(jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
                   th, float(jitter)) for X, y in zip(Xw, yw)]
    return (np.stack([np.asarray(o[0]) for o in out]),
            np.stack([np.asarray(o[1]) for o in out]))
