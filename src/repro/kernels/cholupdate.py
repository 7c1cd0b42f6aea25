"""Pallas TPU kernel: blocked rank-1 Cholesky update / downdate.

Given L lower-triangular with L L^T = A, computes L' with

  L' L'^T = A + sign * x x^T        (sign = +1 update, -1 downdate)

in O(n^2) — the streaming-GP primitive that replaces the O(n^3)
refactorization when an observation is appended to or evicted from an
agent's window (core/online). The column sweep is the LINPACK
Givens/hyperbolic-rotation recurrence; columns are processed in panels of
`bk` so each grid step owns one VMEM-resident panel while the rotated
rank-1 vector x is carried across panels in a VMEM scratch accumulator
(same sequential-grid + scratch-carry schedule as rbf_matvec's
accumulator).

Per column k:  r   = sqrt(L_kk^2 + sign * x_k^2)
               c,s = r / L_kk,  x_k / L_kk
               L'_{tail,k} = (L_{tail,k} + sign * s * x_tail) / c
               x_tail      = c * x_tail - s * L'_{tail,k}

TPU layout: the kernels work on the TRANSPOSED factor U = L^T, so column k
of L is row k of U — a lane-dense (1, n) vector, read and written with a
dynamic sublane index (`pl.ds`) on the VMEM refs. Column vectors (n, 1)
would pad every element to a 128-lane vreg, and the TPU lowering has no
dynamic lane slice. A panel is a (bk, n) row block of U; x is (1, n). The
caller hands U in and takes U' back (ops.py: the transposes are the only
passes over the factor outside the kernel). The grid is cdiv(n, bk): the
rows of a partial last panel past n read undefined values, find no lane at
or below their column (so they leave x alone), and their writes are
dropped.

Two kernels share the column step:

  cholupdate_pallas        L' = chol(L L^T + sign x x^T), in place.
  cholupdate_evict_pallas  drop slot 0 (core/online's evict of the oldest
      point): the factor of A[1:, 1:] is the update of the trailing block
      with x = L[1:, 0]. The sweep starts at column 1, so column 0 is
      skipped exactly, and writes each column one slot up-left: column k
      goes to row k-1 of the output, one lane to the left. A panel's last
      output row is the first column of the next panel, read from an
      8-row look-ahead block. The sweep writes a panel's columns to a VMEM
      scratch panel as they are, and the one-lane shift follows per panel,
      off the sweep's carried x. The last row and column become the fresh
      slot e_{n-1} * fill. A per-factor flag of 0 copies the panel through
      unshifted, so a vmapped fleet needs no select over the factors.

VMEM: the input and output panels are double-buffered, so a grid step holds
about 4 * bk * n * 4 bytes; `panel_rows` picks bk against
`VMEM_PANEL_BUDGET` (W = 2048 -> bk = 128, 4 MiB). The evict kernel adds
its scratch panel and look-ahead, (bk + 16) * n * 4 bytes: at W = 8,100,
bk = 88, about 15 MiB in all, under v5e's 16 MiB default scoped limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM bytes the double-buffered (bk, n) input and output panels may take:
# below v5e's 16 MiB default scoped-VMEM limit, with room for x and scratch
VMEM_PANEL_BUDGET = 12 * 2**20


def panel_rows(n: int, cap: int = 128) -> int:
    """Panel height bk (a multiple of 8, at most `cap`) whose four f32
    (bk, n) panel buffers fit VMEM_PANEL_BUDGET."""
    fit = VMEM_PANEL_BUDGET // (4 * 4 * max(n, 1))
    return max(8, min(cap, fit) // 8 * 8)


def _rotate(x_acc, sign, col, k, lanes):
    """Column k of the sweep: `col` (1, n) = L[:, k]^T in, the updated
    column out; the rotated x in `x_acc` moves past k."""
    x = x_acc[...]                                       # (1, n)
    at_k = lanes == k
    Lkk = jnp.sum(jnp.where(at_k, col, 0.0))
    xk = jnp.sum(jnp.where(at_k, x, 0.0))
    r = jnp.sqrt(jnp.maximum(Lkk * Lkk + sign * xk * xk, 1e-30))
    c = r / Lkk
    s = xk / Lkk
    below = lanes > k
    newcol = jnp.where(below, (col + sign * s * x) / c, col)
    newcol = jnp.where(at_k, r, newcol)
    x_acc[...] = jnp.where(below, c * x - s * newcol, x)
    return newcol


def _kernel(sign_ref, x_ref, u_ref, out_ref, x_acc, *, bk, n):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        x_acc[...] = x_ref[...]

    sign = sign_ref[0, 0]
    out_ref[...] = u_ref[...]                            # (bk, n) panel of U
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(t, carry):
        col = out_ref[pl.ds(t, 1), :]                    # (1, n) = L[:, k]^T
        out_ref[pl.ds(t, 1), :] = _rotate(x_acc, sign, col, j * bk + t,
                                          lanes)
        return carry

    jax.lax.fori_loop(0, bk, body, 0)


def cholupdate_pallas(U, x, sign, bk: int = 128, interpret: bool = False):
    """U (n, n) float32 = L^T (upper-triangular), x (n,). Returns the
    updated U' (n, n) float32."""
    n = U.shape[0]
    params = jnp.asarray(sign, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, n=n),
        grid=(pl.cdiv(n, bk),),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, n), lambda j: (0, 0)),
            pl.BlockSpec((bk, n), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, n), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params, x.reshape(1, n).astype(jnp.float32), U)


def _evict_kernel(flag_ref, fill_ref, u_ref, ahead_ref, out_ref, x_acc, buf,
                  *, bk, n, panels):
    j = pl.program_id(0)

    @pl.when(flag_ref[0, 0] == 0)
    def _keep():
        out_ref[...] = u_ref[...]

    @pl.when(flag_ref[0, 0] != 0)
    def _evict():
        @pl.when(j == 0)
        def _init():
            x_acc[...] = u_ref[pl.ds(0, 1), :]           # evicted column

        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

        def body(t, carry):
            # column k = j*bk + t + 1 to output row t (one slot up)
            buf[pl.ds(t, 1), :] = _rotate(
                x_acc, 1.0, u_ref[pl.ds(t + 1, 1), :], j * bk + t + 1, lanes)
            return carry

        jax.lax.fori_loop(0, bk - 1, body, 0)
        buf[pl.ds(bk - 1, 1), :] = _rotate(x_acc, 1.0,
                                           ahead_ref[pl.ds(0, 1), :],
                                           (j + 1) * bk, lanes)

        # each row one lane left (L's rows one slot up), a row group at a
        # time: here it stays off the sweep's critical path, where a shift
        # in each column's store queued behind the carried x
        rows = min(8, bk)

        def shift(g, carry):
            r = pl.multiple_of(g * rows, rows)
            out_ref[pl.ds(r, rows), pl.ds(0, n - 1)] = buf[pl.ds(r, rows),
                                                           pl.ds(1, n - 1)]
            return carry

        jax.lax.fori_loop(0, bk // rows, shift, 0)
        out_ref[:, pl.ds(n - 1, 1)] = jnp.zeros((bk, 1), jnp.float32)

        @pl.when(j == panels - 1)
        def _sentinel():
            row = n - 1 - (panels - 1) * bk
            out_ref[pl.ds(row, 1), :] = jnp.where(lanes == n - 1,
                                                  fill_ref[0, 0], 0.0)


def cholupdate_evict_pallas(U, fill, evict, bk: int = 128,
                            interpret: bool = False):
    """U (n, n) float32 = L^T, n >= 2; bk % 8 == 0 or bk == n. Where
    `evict` (a traced bool) holds, returns the transpose of the factor
    that holds chol(A[1:, 1:]) in slots 0..n-2 and e_{n-1} * fill in slot
    n-1; otherwise returns U unchanged."""
    n = U.shape[0]
    panels = pl.cdiv(n, bk)
    ahead = min(8, n)
    last_block = pl.cdiv(n, ahead) - 1
    return pl.pallas_call(
        functools.partial(_evict_kernel, bk=bk, n=n, panels=panels),
        grid=(panels,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((bk, n), lambda j: (j, 0)),
            pl.BlockSpec((ahead, n), lambda j: (
                jnp.minimum((j + 1) * (bk // ahead), last_block), 0)),
        ],
        out_specs=pl.BlockSpec((bk, n), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((bk, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(evict, jnp.int32).reshape(1, 1),
      jnp.asarray(fill, jnp.float32).reshape(1, 1), U, U)
