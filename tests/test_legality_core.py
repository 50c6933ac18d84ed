"""PR 4 tentpole coverage: the shared legality core and full-coverage
delta absorption.

* Property tests (hypothesis, via the optional-import shim): any mix of
  device-out flips, foreign movements, pool growth and device adds on a
  multi-pool / multi-class cluster absorbs into the warm batch carry with
  *zero* dense rebuilds and a continuation bit-identical to a cold
  rebuild of the mutated state.
* Regression anchors: the churn-heavy and cascading-failures lifecycles
  — the timelines PR 3 still paid dense rebuilds on — now build the
  dense mirror at most once.
* Legality-core sanity: the scalar and vector forms of each criterion
  agree, and the NumPy/JAX evaluations of the same expression are
  bit-identical (the property the engines' by-construction bit-identity
  rests on).
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (Device, EquilibriumConfig, Movement, TiB,
                        create_planner, small_test_cluster)
from repro.core import legality
from repro.core.equilibrium import _balance
from repro.core.equilibrium_batch import dense_rebuild_count
from repro.sim import run_scenario


def tup(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


# ---------------------------------------------------------------------------
# property: absorption ≡ cold rebuild under arbitrary known-delta mixes


def _apply_op(state, op, rng):
    kind = op % 4
    if kind == 0:                              # out-flip a random device
        dev = state.devices[rng.integers(state.n_devices)]
        state.mark_out(dev.id, out=dev.id not in state.out_osds)
    elif kind == 1:                            # foreign legal movement
        for pg in sorted(state.acting):
            osds = state.acting[pg]
            for slot, src in enumerate(osds):
                for dst in state.devices:
                    if state.move_is_legal(pg, slot, dst.id):
                        state.apply(Movement(pg, slot, src, dst.id,
                                             state.shard_sizes[pg]))
                        return
    elif kind == 2:                            # pool growth
        state.grow_pool(int(rng.integers(2)), float(rng.uniform(0.2, 1.5))
                        * TiB)
    else:                                      # device add (append class)
        nid = 900 + int(rng.integers(90))
        if nid not in state.dev_by_id:
            state.add_device(Device(id=nid, capacity=6 * TiB,
                                    device_class="ssd", host=f"hx{nid}"))


def _check_absorption_bit_identical(seed, ops, first_budget):
    state = small_test_cluster(seed=seed)
    planner = create_planner("equilibrium_batch", chunk=6)
    planner.plan(state, budget=first_budget)
    rng = np.random.default_rng(seed)
    for op in ops:
        _apply_op(state, op, rng)
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    # the only rebuild-worthy op above is a class-renumbering device add
    # ("ssd" joining an hdd-only view cannot happen here: small_test_cluster
    # always has both classes), so absorption must always hold
    assert dense_rebuild_count() - before == 0
    state.check_valid()


def _check_absorption_with_stash(seed, budget):
    state = small_test_cluster(seed=seed)
    planner = create_planner("equilibrium_batch", chunk=64)
    planner.plan(state, budget=budget)
    state.mark_out(state.devices[seed % state.n_devices].id)
    state.grow_pool(0, 1.0 * TiB)
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    assert dense_rebuild_count() - before == 0


# deterministic spine (hypothesis is optional in the container image)
@pytest.mark.parametrize("seed,ops,first_budget", [
    (0, [1], 2), (3, [2, 3], 4), (7, [0, 1, 2], 1),
    (11, [3, 0], 8), (23, [1, 2, 3, 0, 1], 3),
])
def test_absorption_bit_identical_cases(seed, ops, first_budget):
    _check_absorption_bit_identical(seed, ops, first_budget)


@pytest.mark.parametrize("seed,budget", [(0, 1), (9, 3), (17, 6)])
def test_absorption_with_stash_cases(seed, budget):
    _check_absorption_with_stash(seed, budget)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 40),
       ops=st.lists(st.integers(0, 3), min_size=1, max_size=5),
       first_budget=st.integers(1, 8))
def test_absorption_bit_identical_to_cold_rebuild(seed, ops, first_budget):
    _check_absorption_bit_identical(seed, ops, first_budget)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 40), budget=st.integers(1, 6))
def test_absorption_with_stash_bit_identical(seed, budget):
    """chunk ≫ budget keeps a device-planned overshoot stash alive at the
    moment the delta lands — absorption must discard it and still match
    a cold plan exactly."""
    _check_absorption_with_stash(seed, budget)


# ---------------------------------------------------------------------------
# regression anchors: the rebuild-heavy lifecycles now build once


@pytest.mark.slow
@pytest.mark.parametrize("name", ["churn-heavy", "cascading-failures"])
def test_churn_lifecycles_rebuild_at_most_once(name):
    """The ROADMAP's remaining rebuild classes, closed: device outs,
    failures (out + drain movement burst), pool creates and foreign
    moves all absorb, so these lifecycles build the dense mirror exactly
    once (the initial build)."""
    before = dense_rebuild_count()
    run_scenario(name, "equilibrium_batch", seed=0, quick=True)
    assert dense_rebuild_count() - before <= 1


# ---------------------------------------------------------------------------
# legality-core sanity


def test_scalar_and_vector_criteria_agree():
    counts = np.array([3.0, 5.0, 0.0, 7.0])
    ideal = np.array([4.2, 4.9, 1.1, 6.0])
    for slack in (0.0, 0.5, 1.0):
        vec_dst = legality.dst_count_ok(counts, ideal, slack)
        vec_src = legality.src_count_ok(counts, ideal, slack)
        for i in range(len(counts)):
            assert bool(legality.dst_count_ok(counts[i], ideal[i],
                                              slack)) == vec_dst[i]
            assert bool(legality.src_count_ok(counts[i], ideal[i],
                                              slack)) == vec_src[i]


def test_before_source_matches_stable_sort_rank():
    rng = np.random.default_rng(0)
    util = rng.uniform(size=16)
    util[3] = util[7]                   # force a tie
    order = np.argsort(util, kind="stable")
    idx = np.arange(16)
    for rank, src in enumerate(order):
        mask = legality.before_source(util, util[src], idx, int(src))
        assert set(np.flatnonzero(mask)) == set(int(d)
                                                for d in order[:rank])


def test_variance_improves_numpy_jax_bit_identical():
    """The same legality-core expression traced through jax.numpy must
    produce bitwise-identical float64 decisions to the NumPy evaluation
    — the foundation of the engines' by-construction bit-identity."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    n = 32
    cap = rng.uniform(4, 16, n) * TiB
    used = cap * rng.uniform(0.2, 0.9, n)
    util = used / cap
    us, usq = float(util.sum()), float((util ** 2).sum())
    size = rng.uniform(0.01, 0.4, (8, 1)) * TiB
    src = 5
    with jax.enable_x64(True):
        np_ok = legality.variance_improves(
            used[src], used[None, :], cap[src], cap[None, :], util[src],
            util[None, :], size, us, usq, float(n), 0.0)
        jx_ok = legality.variance_improves(
            jnp.asarray(used)[src], jnp.asarray(used)[None, :],
            jnp.asarray(cap)[src], jnp.asarray(cap)[None, :],
            jnp.asarray(util)[src], jnp.asarray(util)[None, :],
            jnp.asarray(size), us, usq, float(n), 0.0)
        assert np.array_equal(np_ok, np.asarray(jx_ok))


def test_legality_state_matches_dense_state_ids():
    """LegalityState.from_cluster and DenseState agree on every id —
    they are literally the same construction now."""
    from repro.core import DenseState
    state = small_test_cluster()
    leg = legality.LegalityState.from_cluster(state)
    dense = DenseState(state)
    assert leg.class_id == dense.class_id
    assert np.array_equal(leg.dev_class, dense.dev_class)
    assert np.array_equal(leg.dev_domain_arr, dense.dev_domain_arr)
    assert np.array_equal(leg.dev_in, dense.dev_in)
    assert leg.n_domains == dense.n_domains
