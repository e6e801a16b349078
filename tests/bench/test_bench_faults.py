"""A run whose timed path is broken underneath comes out not correct:
each fault is planted in the program for one harness run at a CPU
size."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.scheduler import FullParticipation
from repro.core.federated import FederatedProblem
from repro.core.flens import FLeNS

from _bench_common import CELLS, run
from _bench_common import cpu_harness  # noqa: F401 — a fixture


def _unchanged(orig):
    def round_(self, problem, state, key, comm=None):
        orig(self, problem, state, key, comm)  # bill its payloads
        return state
    return round_


def _half(orig):
    """The second half of the clients' data swapped for the first half's:
    the mean runs over the first half alone, at the round's own shapes."""
    def round_(self, problem, state, key, comm=None):
        idx = jnp.arange(problem.m) % max(1, problem.m // 2)
        sub = FederatedProblem(problem.X[idx], problem.y[idx],
                               problem.mask[idx], problem.lam,
                               problem.objective)
        return orig(self, sub, state, key, comm)
    return round_


def _altered(orig):
    """One coordinate of the round's answer moved by a tenth of the
    largest: a hundredth reads under the limits that the chip's sound
    runs set (PERF.md)."""
    def round_(self, problem, state, key, comm=None):
        out = dict(orig(self, problem, state, key, comm))
        w = out["w"]
        out["w"] = w.at[0].add(1e-1 * jnp.max(jnp.abs(w)))
        return out
    return round_


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_the_clients",
                              "answer_altered"])
def test_a_broken_round_is_not_correct(fault, name, tmp_path, cpu_harness,
                                       monkeypatch):
    monkeypatch.setattr(FLeNS, "round", fault(FLeNS.round))
    result = run(name, tmp_path)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_half_the_clients_left_out_of_the_schedule_is_not_correct(
        name, tmp_path, cpu_harness, monkeypatch):
    """The scheduler sends the model to the first half of the clients
    only; the round aggregates over those who delivered, and the run
    reports them truthfully. The reference follows the schedule the
    configuration asks for, so the run fails on who delivered or on
    the bytes billed."""
    def half(self, key, round_idx, m, channel, eligible=None):
        return np.arange(m) < m // 2

    monkeypatch.setattr(FullParticipation, "participants", half)
    result = run(name, tmp_path)
    assert result["correct"] is False
    checks = result["checks"]
    assert (checks["schedule_gap"]["value"] > 0
            or checks["bytes_gap"]["value"] > 0), checks


def test_wrong_bytes_are_not_correct(tmp_path, cpu_harness, monkeypatch):
    from repro.comm import codecs

    monkeypatch.setattr(codecs.QInt8Codec, "nbytes",
                        lambda self, shape, dtype: int(np.prod(shape)) + 8)
    result = run("phishing.sync-qint8", tmp_path)
    assert result["correct"] is False
    assert result["checks"]["bytes_gap"]["value"] > 0
