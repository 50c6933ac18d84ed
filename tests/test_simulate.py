"""Coverage for the simulate harness: trajectory recording (stride,
disabled), gained_free_space sign conventions, throttled replay, and the
movement throttle's byte-conservation ledger."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (EquilibriumConfig, GiB, Movement, MovementThrottle,
                        ThrottleConfig, equilibrium_balance, simulate,
                        simulate_throttled, small_test_cluster)


def _balanced_moves():
    initial = small_test_cluster()
    state = initial.copy()
    moves, _ = equilibrium_balance(state, EquilibriumConfig())
    assert len(moves) >= 4
    return initial, moves


def test_trajectory_stride_one_records_every_move():
    initial, moves = _balanced_moves()
    res = simulate(initial, moves, record_trajectory=True,
                   trajectory_stride=1)
    # index 0 is the initial state, one sample per move after that
    assert len(res.variance_trajectory) == len(moves) + 1
    assert res.variance_trajectory[0] == pytest.approx(res.variance_before)
    assert res.variance_trajectory[-1] == pytest.approx(res.variance_after)
    assert res.moved_bytes_trajectory[-1] == pytest.approx(res.moved_bytes)


def test_trajectory_stride_subsamples_but_keeps_last():
    initial, moves = _balanced_moves()
    stride = 3
    res = simulate(initial, moves, record_trajectory=True,
                   trajectory_stride=stride)
    full = simulate(initial, moves, record_trajectory=True,
                    trajectory_stride=1)
    # samples at i % stride == 0 plus the final move (always recorded)
    n_moves = len(moves)
    sampled = {i for i in range(n_moves) if i % stride == 0}
    sampled.add(n_moves - 1)
    assert len(res.variance_trajectory) == 1 + len(sampled)
    # the final state must be sampled regardless of stride alignment
    assert res.variance_trajectory[-1] == pytest.approx(
        full.variance_trajectory[-1])
    assert res.free_trajectory[-1] == pytest.approx(full.free_trajectory[-1])
    # subsampled points are a subset of the full trajectory
    for v in res.variance_trajectory:
        assert np.isclose(full.variance_trajectory, v).any()


def test_record_trajectory_false_leaves_none():
    initial, moves = _balanced_moves()
    res = simulate(initial, moves, record_trajectory=False)
    assert res.variance_trajectory is None
    assert res.free_trajectory is None
    assert res.moved_bytes_trajectory is None
    # scalar results still populated
    assert res.moves_applied == len(moves)
    assert res.moved_bytes == pytest.approx(sum(m.size for m in moves))


def test_gained_free_space_sign_conventions():
    """Balancing gains free space (positive); undoing a balanced plan
    gives back exactly the negated gain."""
    initial, moves = _balanced_moves()
    res = simulate(initial, moves, record_trajectory=False)
    assert res.gained_free_space > 0
    assert res.gained_free_space == pytest.approx(
        res.free_after - res.free_before)

    balanced = initial.copy()
    for mv in moves:
        balanced.apply(mv)
    inverse = [Movement(mv.pg, mv.slot, mv.dst_osd, mv.src_osd, mv.size)
               for mv in reversed(moves)]
    back = simulate(balanced, inverse, record_trajectory=False)
    assert back.gained_free_space < 0
    assert back.gained_free_space == pytest.approx(-res.gained_free_space,
                                                   rel=1e-9)


def test_throttled_replay_matches_untrottled_endpoint():
    initial, moves = _balanced_moves()
    plain = simulate(initial, moves, record_trajectory=False)
    throttled = simulate_throttled(
        initial, moves, ThrottleConfig(max_concurrent=3,
                                       device_bytes_per_tick=2.0 * 1024**4))
    assert throttled.moved_bytes == pytest.approx(plain.moved_bytes)
    assert throttled.variance_target == pytest.approx(plain.variance_after)
    assert throttled.variance_trajectory[-1] == pytest.approx(
        plain.variance_after, rel=1e-9)
    # the physical series is bracketed by the initial and final variance
    assert throttled.variance_trajectory[0] == pytest.approx(
        plain.variance_before, rel=1e-9)
    assert throttled.ticks == len(throttled.variance_trajectory) - 1
    # in-flight never exceeds the configured concurrency
    assert throttled.in_flight_trajectory.max() <= 3


# ---------------------------------------------------------------------------
# movement-throttle byte conservation (the fuzz harness's third oracle)


def test_retarget_mid_backfill_conserves_and_rereads():
    """Shard moved 1→2, re-targeted 1→3 while the first transfer was
    half done: the superseded transfer is cancelled whole, its partial
    progress is discarded, and the new transfer re-reads the full shard
    from the original holder."""
    q = MovementThrottle(ThrottleConfig(max_concurrent=2,
                                        device_bytes_per_tick=1.0 * GiB))
    q.enqueue([Movement((0, 0), 0, 1, 2, 3.0 * GiB)])
    q.tick()                                   # 1 GiB of 3 transferred
    assert q.transferred_bytes == pytest.approx(1.0 * GiB)
    q.enqueue([Movement((0, 0), 0, 2, 3, 3.0 * GiB)])   # retarget 2→3
    ledger = q.check_conservation()
    assert ledger["cancelled_bytes"] == pytest.approx(3.0 * GiB)
    assert ledger["discarded_bytes"] == pytest.approx(1.0 * GiB)
    # the live transfer restarted from zero, reading from holder 1
    (live,) = list(q.pending) + q.in_flight
    assert live.holder == 1 and live.remaining == pytest.approx(3.0 * GiB)
    while q.backlog_moves:
        q.tick()
        q.check_conservation()
    assert q.completed_bytes == pytest.approx(3.0 * GiB)
    assert q.completed_progress_bytes == pytest.approx(3.0 * GiB)
    # 1 GiB moved and thrown away, then the full 3 GiB re-read
    assert q.transferred_bytes == pytest.approx(4.0 * GiB)


def _check_throttle_conservation(seed, n_ops):
    """Seeded random op mix — enqueues (with shard collisions, so
    mid-backfill retargeting occurs), ticks, destination cancels, source
    losses — with the ledger checked after every op and after a full
    drain."""
    rng = np.random.default_rng((seed, 0x7407))
    q = MovementThrottle(ThrottleConfig(
        max_concurrent=int(rng.integers(1, 5)),
        device_bytes_per_tick=float(rng.uniform(0.5, 4.0)) * GiB))
    shards = [((0, i), s) for i in range(6) for s in range(2)]

    def rand_move():
        pg, slot = shards[int(rng.integers(len(shards)))]
        src, dst = (int(x) for x in rng.choice(10, size=2, replace=False))
        return Movement(pg, slot, src, dst,
                        float(rng.uniform(0.1, 3.0)) * GiB)

    for _ in range(n_ops):
        op = int(rng.integers(5))
        if op <= 1:
            q.enqueue([rand_move() for _ in range(int(rng.integers(1, 4)))],
                      src_holds=bool(rng.integers(2)))
        elif op == 2:
            q.tick()
        elif op == 3:
            q.cancel_to(int(rng.integers(10)))
        else:
            q.source_lost(int(rng.integers(10)))
        q.check_conservation()
    while q.backlog_moves:
        q.tick()
        q.check_conservation()
    ledger = q.check_conservation()
    assert ledger["enqueued_bytes"] == pytest.approx(
        ledger["completed_bytes"] + ledger["cancelled_bytes"])
    assert q.transferred_bytes == pytest.approx(
        q.completed_progress_bytes + q.discarded_bytes)


# deterministic spine (hypothesis is optional in the container image)
@pytest.mark.parametrize("seed,n_ops", [(0, 10), (1, 25), (2, 40), (3, 60),
                                        (7, 80), (13, 120),
                                        # a shard re-targeted back onto
                                        # its holder (once a stalled drain)
                                        (13, 10), (2, 120)])
def test_throttle_conservation_cases(seed, n_ops):
    _check_throttle_conservation(seed, n_ops)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), n_ops=st.integers(1, 120))
def test_throttle_conservation_property(seed, n_ops):
    _check_throttle_conservation(seed, n_ops)
