"""The schedule the reference follows comes from the configuration: all
clients in every synchronous round, and buffered asynchronous commits
whose staleness follows from when each client was last sent the model."""
import numpy as np

from benchmarks.chip import reference


def test_async_schedule_derives_staleness_and_broadcasts():
    members = np.array([[1, 1, 0, 0], [0, 0, 1, 1], [1, 0, 1, 0]], bool)
    sched, broken = reference.async_schedule(7, members, 2, "inverse")
    assert broken == 0
    want = np.array([[0, 0, np.nan, np.nan], [np.nan, np.nan, 1, 1],
                     [1, np.nan, 0, np.nan]])
    np.testing.assert_array_equal(sched.staleness, want)
    np.testing.assert_array_equal(sched.broadcasts, [4, 2, 2])
    assert reference.schedule_gap(sched, members, None, want) == 0
    # a commit that says its clients computed on another version
    wrong = want.copy()
    wrong[2, 0] = 0
    assert reference.schedule_gap(sched, members, None, wrong) == 1


def test_async_commits_of_the_wrong_size_break_the_rules():
    members = np.array([[1, 1, 1, 0], [0, 0, 0, 1]], bool)
    _, broken = reference.async_schedule(7, members, 2, "inverse")
    assert broken == 2


def test_sync_schedule_asks_for_every_client():
    sched = reference.sync_schedule(7, 3, 5)
    assert sched.delivered.all() and sched.staleness is None
    np.testing.assert_array_equal(sched.broadcasts, [5, 5, 5])
    half = np.arange(5) < 3
    got = np.stack([np.ones(5, bool), half, np.ones(5, bool)])
    assert reference.schedule_gap(sched, got, np.array([5, 5, 5]),
                                  None) == 1
    assert reference.schedule_gap(sched, sched.delivered,
                                  np.array([5, 3, 5]), None) == 1
    wire = reference.expected_bytes(sched, dim=4, k=2,
                                    codecs={"default": "identity"})
    up, down = 4 * (4 + 2 + 1), 4 * 4 + 8 + 4 * 4
    np.testing.assert_array_equal(wire, [5 * (up + down)] * 3)
