"""The benchmark's operation and byte counts, at shapes small enough to
count by hand."""
import math

import pytest

from benchmarks.chip import counts

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_srht_counts_at_a_small_shape():
    # 8 rows of width 18 pad to n = 32: sign flip (32) + FWHT (32 * 5)
    ops, nbytes = counts.srht(8, 18, 10)
    assert ops == 8 * (32 + 32 * 5)
    # read x and write S x once, float32, plus the signs and row indices
    assert nbytes == 4 * (8 * 18 + 8 * 10) + 4 * 32 + 4 * 10


def test_srht_transpose_counts_at_a_small_shape():
    ops, nbytes = counts.srht_t(3, 68, 17)
    assert ops == 3 * (128 * 7 + 128)
    assert nbytes == 4 * (3 * 17 + 3 * 68) + 4 * 128 + 4 * 17


def test_srht_power_of_two_width_needs_no_padding():
    assert counts.srht(1, 16, 4)[0] == 16 + 16 * 4


def test_flens_srht_calls_are_the_rounds_sketches():
    calls = counts.flens_srht_calls(rows=50, clients=5, dim=18, k=10)
    assert calls == [counts.srht(50, 18, 10), counts.srht(5, 18, 10),
                     counts.srht_t(10, 18, 10), counts.srht(10, 18, 10),
                     counts.srht_t(1, 18, 10)]


def test_round_flops_term_by_term():
    rows, clients, dim, k = 40, 4, 18, 10
    sketch = sum(o for o, _ in counts.flens_srht_calls(rows, clients, dim, k))
    want = (rows * (4 * dim + 4)            # local gradients at v
            + 3 * rows                      # Hessian weights p(1-p)
            + rows * (dim + 2)              # A_j
            + sketch                        # the round's SRHTs
            + 2 * rows * k * k              # Gram of A_j S^T
            + 2 * clients * (k * k + k)     # aggregation
            + 2 * k ** 3 / 3 + 2 * k * k    # k x k solve
            + 32 * 5 + 3 * dim              # S^T delta and the step
            + rows * (2 * dim + 3) + 2 * clients)  # guard loss at w_next
    assert counts.flens_round_flops(rows, clients, dim, k) == pytest.approx(want)


def test_eval_flops():
    assert counts.eval_flops(10, 18) == 10 * (2 * 18 + 3) + 10 * (4 * 18 + 4) + 36


def test_min_time_names_its_bound():
    t, bound = counts.min_time(197e12, 1.0, PEAKS)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = counts.min_time(1.0, 819e9, PEAKS)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_srht_at_susy_size_is_memory_bound_and_small():
    ops, nbytes = counts.srht(5_000_000, 18, 10)
    t, bound = counts.min_time(ops, nbytes, PEAKS)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert math.isclose(nbytes, 4 * 5_000_000 * 28 + 4 * 32 + 40)
