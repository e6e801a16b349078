"""The seed a run drives the program with: the first from ``--seed`` on
whose compared rounds the sketch has full rank (``harness.run_seed``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, reference


def _ranks(cell, seed, rounds):
    k, dim = int(cell.config["sketch_k"]), int(cell.config["dim"])
    keys = jax.random.split(jax.random.PRNGKey(seed), rounds)
    return [int(np.linalg.matrix_rank(np.asarray(
        reference.srht_matrix(keys[t], k, dim, jnp.float32), np.float64)))
        for t in range(int(cell.traffic["compare_steps"]))]


@pytest.mark.parametrize("name, seed, want", [
    # SUSY's first sketch has rank 9 of 10: the next seed's has 10
    ("susy.sync-qint8-slow", 2112535922, 2112535923),
    # full rank: the seed itself, modulo 2**32
    ("susy.sync-qint8-slow", 4202815841, 4202815841),
    ("susy.sync-qint8-slow", 2**32 + 4202815841, 4202815841),
    # the async mix compares three commits: the third sketch has rank 16
    ("phishing.async-fedbuff", 3000000000, 3000000001),
    # the sync mix compares one round, whose sketch has full rank
    ("phishing.sync-qint8", 3000000000, 3000000000),
])
def test_the_run_seed_is_the_first_with_full_rank_compared_sketches(
        name, seed, want):
    cell = harness.load_cell(name)
    rounds = 7
    got = harness.run_seed(cell, seed, rounds)
    assert got == want
    k = int(cell.config["sketch_k"])
    assert _ranks(cell, got, rounds) == [k] * int(
        cell.traffic["compare_steps"])
    for skipped in range(seed % 2**32, got):
        assert min(_ranks(cell, skipped, rounds)) < k
