"""Operations and bytes that each computation NEEDS at its shapes.

Counted from the shapes, never from what an implementation streams: the
inputs a computation must read, the outputs it must write, and the
arithmetic of the plain algorithm. So any implementation reads at most
100% of its roofline, and work an implementation spends beyond the need
(re-reading an operand, a cached stack, a transpose) shows as a lower
share rather than a larger count. All counts are float32 (4 bytes).
"""
from __future__ import annotations

F32 = 4


def nll_grad(n: int, dim: int):
    """(flops, bytes) of one agent's fused NLL gradient (paper eq. 4):
    from X (n, D) and inner = C^-1 - alpha alpha^T (n, n), the D + 2
    trace components. Per matrix entry: the D squared differences (2D),
    their scaled sum (2D), exp and the sigma_f^2 scale (2), the product
    with inner (1), and the D + 1 weighted sums (2D + 1). Bytes: inner and
    X read, D + 2 values written. The cached diff^2 stacks are not needed
    (X makes them) and are not counted."""
    flops = (6 * dim + 4) * n * n
    nbytes = F32 * (n * n + n * dim + dim + 2)
    return flops, nbytes


def cholupdate(n: int):
    """(flops, bytes) of one rank-1 update of an n x n Cholesky factor:
    the factor's triangle read once and written once, and x read. Per
    entry below the diagonal the rotation costs 6 flops (3 for the new
    column, 3 for the carried vector)."""
    tri = n * (n + 1) // 2
    return 6 * tri, F32 * (2 * tri + n)


def admm_iteration(agents: int, n: int, dim: int) -> float:
    """Flops of one DEC-apx-GP iteration (eq. 34) over the fleet: per
    agent the covariance rebuild ((2D + 3) per entry), its Cholesky
    (n^3 / 3), the inverse from the factor (2 n^3 / 3), alpha alpha^T
    (2 per entry) and the gradient (`nll_grad`); the consensus update
    itself is O(M K) and not counted."""
    per_agent = (n ** 3 + (2 * dim + 5) * n * n
                 + nll_grad(n, dim)[0])
    return agents * per_agent


def factorization(agents: int, n: int, dim: int) -> float:
    """Flops of caching the serving factors: covariance, Cholesky, and
    alpha by two triangular solves, per agent."""
    return agents * (n ** 3 / 3 + (2 * dim + 3) * n * n + 2 * n * n)


def fit(agents: int, n: int, dim: int, iters: int) -> float:
    """Flops of one training run: `iters` ADMM iterations, then the
    serving factorization."""
    return iters * admm_iteration(agents, n, dim) \
        + factorization(agents, n, dim)


def observe_event(agents: int, window: int, dim: int):
    """(flops, bytes) of one fleet-wide observation on full windows of W
    points. Flops, per agent: the evicting rank-1 update of the
    (W-1)^2 block, the new kernel column ((2D + 3) W), the forward solve
    for the new row (W^2) and alpha by two triangular solves (2 W^2).
    Bytes, per agent: the factor's triangle read once and written once,
    the window's points read, the new row and alpha written; the solves
    can read the factor while it is updated, so they add no bytes."""
    w = window
    flops = cholupdate(w - 1)[0] + (2 * dim + 3) * w + 3 * w * w
    nbytes = F32 * (w * (w + 1) + w * dim + 2 * w)
    return agents * flops, agents * nbytes
