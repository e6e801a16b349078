"""Operations and bytes that the algorithm needs, from the call's shapes.

These are the yardstick of the roofline and utilization metrics. They
count what the mathematics requires, never how a kernel does it: the
SRHT of a row of width ``dim`` is a sign flip and a fast Walsh-Hadamard
transform at ``n = next_pow2(dim)`` (n + n log2 n operations) and a
subsample. Bytes are a lower bound: each input read once, each output
written once, float32. So a kernel that does the same work in another
way reads against the same count, and no share can pass 100%.
"""
from __future__ import annotations

import math

F32 = 4


def _pow2(dim: int) -> int:
    n = 1
    while n < dim:
        n *= 2
    return n


def srht(rows: int, dim: int, k: int) -> "tuple[float, float]":
    """S @ x for ``rows`` rows of width ``dim``: (ops, bytes)."""
    n = _pow2(dim)
    ops = rows * (n + n * math.log2(n))
    nbytes = F32 * (rows * dim + rows * k) + F32 * n + 4 * k
    return float(ops), float(nbytes)


def srht_t(rows: int, dim: int, k: int) -> "tuple[float, float]":
    """S^T @ y for ``rows`` rows of width ``k``: (ops, bytes)."""
    n = _pow2(dim)
    ops = rows * (n * math.log2(n) + n)
    nbytes = F32 * (rows * k + rows * dim) + F32 * n + 4 * k
    return float(ops), float(nbytes)


def flens_srht_calls(rows: int, clients: int, dim: int,
                     k: int) -> "list[tuple[float, float]]":
    """The SRHT applications of one FLeNS round: the clients' A_j S^T
    over their ``rows`` rows, S g_j, S S^T (S^T then S on k rows) and
    the server's S^T delta."""
    return [srht(rows, dim, k), srht(clients, dim, k), srht_t(k, dim, k),
            srht(k, dim, k), srht_t(1, dim, k)]


def flens_round_flops(rows: int, clients: int, dim: int, k: int) -> float:
    """Floating-point operations of one FLeNS round by the algorithm:
    local gradients at v, Hessian weights, A_j, the sketches, the Gram
    of A_j S^T, the aggregation, the k x k solve, S^T delta, and the
    guard's loss at w_next."""
    n = _pow2(dim)
    grads = rows * (4 * dim + 4)
    hess_weights = 3 * rows
    a = rows * (dim + 2)
    sketch = sum(ops for ops, _ in flens_srht_calls(rows, clients, dim, k))
    gram = 2 * rows * k * k
    aggregate = 2 * clients * (k * k + k)
    solve = 2 * k ** 3 / 3 + 2 * k * k
    step = n * math.log2(n) + 3 * dim
    guard_loss = rows * (2 * dim + 3) + 2 * clients
    return float(grads + hess_weights + a + sketch + gram + aggregate
                 + solve + step + guard_loss)


def eval_flops(rows: int, dim: int) -> float:
    """The driver's per-round evaluation: the loss and the gradient norm
    over ``rows`` rows."""
    return float(rows * (2 * dim + 3) + rows * (4 * dim + 4) + 2 * dim)


def min_time(ops: float, nbytes: float, peaks: dict) -> "tuple[float, str]":
    """The least time the chip needs, and which bound sets it."""
    t_ops = ops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
