"""Unit + property tests for the cluster model (devices, pools, placement,
accounting, max-avail semantics)."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (ClusterState, Device, Movement, PlacementRule, Pool,
                        RuleStep, TiB, build_cluster, small_test_cluster)
from repro.core.crush import place_pg


def make_devices(n_hosts=6, osds_per_host=2, cap=8 * TiB, device_class="hdd"):
    devs = []
    for h in range(n_hosts):
        for j in range(osds_per_host):
            devs.append(Device(id=len(devs), capacity=cap, device_class=device_class,
                               host=f"host{h}", rack=f"rack{h % 3}"))
    return devs


def test_placement_respects_rule():
    devs = make_devices()
    pool = Pool(0, "p", 32, PlacementRule.replicated(3, "host"), stored_bytes=TiB)
    st_ = build_cluster(devs, [pool], seed=0)
    for pg, osds in st_.acting.items():
        hosts = [st_.dev_by_id[o].host for o in osds]
        assert len(set(hosts)) == 3, "replicas must land on distinct hosts"


def test_rack_failure_domain():
    devs = make_devices(n_hosts=6)
    pool = Pool(0, "p", 16, PlacementRule.replicated(3, "rack"), stored_bytes=TiB)
    st_ = build_cluster(devs, [pool], seed=0)
    for pg, osds in st_.acting.items():
        racks = [st_.dev_by_id[o].rack for o in osds]
        assert len(set(racks)) == 3


def test_hybrid_rule_classes():
    devs = (make_devices(4, 2, 8 * TiB, "hdd")
            + [Device(id=100 + i, capacity=2 * TiB, device_class="ssd",
                      host=f"shost{i}") for i in range(4)])
    rule = PlacementRule.hybrid([RuleStep("ssd", 1, "host"),
                                 RuleStep("hdd", 2, "host")])
    pool = Pool(0, "hy", 16, rule, stored_bytes=TiB)
    st_ = build_cluster(devs, [pool], seed=1)
    for pg, osds in st_.acting.items():
        classes = [st_.dev_by_id[o].device_class for o in osds]
        assert classes[0] == "ssd" and classes[1:] == ["hdd", "hdd"]


def test_used_bytes_accounting():
    st_ = small_test_cluster()
    total_shard = sum(st_.shard_sizes[pg] * len(osds)
                      for pg, osds in st_.acting.items())
    assert np.isclose(st_.used().sum(), total_shard, rtol=1e-9)


def test_apply_and_undo_roundtrip():
    st_ = small_test_cluster()
    pg = next(iter(st_.acting))
    src = st_.acting[pg][0]
    dst = next(d.id for d in st_.devices
               if st_.move_is_legal(pg, 0, d.id))
    before_used = st_.used()
    mv = Movement(pg, 0, src, dst, st_.shard_sizes[pg])
    st_.apply(mv)
    st_.check_valid()
    assert st_.acting[pg][0] == dst
    st_.undo(mv)
    st_.check_valid()
    assert st_.acting[pg][0] == src
    assert np.allclose(st_.used(), before_used)


def test_apply_stale_movement_raises():
    st_ = small_test_cluster()
    pg = next(iter(st_.acting))
    wrong_src = next(d.id for d in st_.devices if d.id not in st_.acting[pg])
    with pytest.raises(ValueError):
        st_.apply(Movement(pg, 0, wrong_src, st_.acting[pg][0], 1.0))


def test_move_illegal_same_pg_and_class():
    st_ = small_test_cluster()
    pg = next(iter(st_.acting))           # pool 0: hdd 3-replica
    peer = st_.acting[pg][1]
    assert not st_.move_is_legal(pg, 0, peer), "dest already holds a shard"
    ssd = next(d.id for d in st_.devices if d.device_class == "ssd")
    assert not st_.move_is_legal(pg, 0, ssd), "wrong device class"


def test_move_illegal_same_host():
    st_ = small_test_cluster()
    pg = next(iter(st_.acting))
    peer_host = st_.dev_by_id[st_.acting[pg][1]].host
    same_host = [d.id for d in st_.devices
                 if d.host == peer_host and d.id not in st_.acting[pg]
                 and d.device_class == "hdd"]
    for osd in same_host:
        assert not st_.move_is_legal(pg, 0, osd)


def test_pool_free_space_is_weight_based_max_avail():
    """free = min_i free_i/growth_i; writing exactly that much (distributed
    by the growth vector) fills the gating device to capacity."""
    st_ = small_test_cluster()
    pool = st_.pools[0]
    growth = st_.pool_growth_vector(pool)
    free = st_.pool_free_space(0)
    used_after = st_.used() + growth * free
    cap = st_.capacity_vector()
    assert (used_after <= cap * (1 + 1e-9)).all()
    assert np.isclose((used_after / cap).max(), 1.0, rtol=1e-6), \
        "gating device should be exactly full"


def test_growth_vector_ec_vs_replicated():
    devs = make_devices(n_hosts=12, osds_per_host=1)
    rep = Pool(0, "r", 8, PlacementRule.replicated(3, "host"), stored_bytes=TiB)
    ec = Pool(1, "e", 8, PlacementRule.erasure(4, 2, "host"), ec_k=4,
              stored_bytes=TiB)
    st_ = build_cluster(devs, [rep, ec], seed=0)
    g_rep = st_.pool_growth_vector(rep).sum()
    g_ec = st_.pool_growth_vector(ec).sum()
    assert np.isclose(g_rep, 3.0)         # 3 full copies
    assert np.isclose(g_ec, 6 / 4)        # (k+m)/k overhead


def test_utilization_variance_by_class():
    st_ = small_test_cluster()
    v_hdd = st_.utilization_variance("hdd")
    v_ssd = st_.utilization_variance("ssd")
    assert v_hdd >= 0 and v_ssd >= 0
    assert st_.utilization_variance() >= 0


def _check_cluster_valid(n_hosts, pg_count, size, seed):
    devs = make_devices(n_hosts=n_hosts)
    pool = Pool(0, "p", pg_count, PlacementRule.replicated(size, "host"),
                stored_bytes=0.4 * n_hosts * 2 * 8 * TiB / size)
    st_ = build_cluster(devs, [pool], seed=seed)
    st_.check_valid()
    assert (st_.utilization() >= 0).all()


def _check_placement_deterministic(seed):
    devs = make_devices()
    pool = Pool(0, "p", 8, PlacementRule.replicated(3, "host"), stored_bytes=TiB)
    a = place_pg(devs, pool, 3, seed=seed)
    b = place_pg(devs, pool, 3, seed=seed)
    assert a == b


# deterministic spine (hypothesis is optional in the container image)
@pytest.mark.parametrize("n_hosts,pg_count,size,seed", [
    (4, 4, 2, 0), (5, 17, 3, 101), (6, 33, 2, 4096),
    (7, 48, 3, 31337), (8, 24, 3, 65535),
])
def test_cluster_valid_cases(n_hosts, pg_count, size, seed):
    _check_cluster_valid(n_hosts, pg_count, size, seed)


@pytest.mark.parametrize("seed", [0, 1, 7, 4242, 65535])
def test_placement_deterministic_cases(seed):
    _check_placement_deterministic(seed)


@settings(max_examples=25, deadline=None)
@given(
    n_hosts=st.integers(4, 8),
    pg_count=st.integers(4, 48),
    size=st.integers(2, 3),
    seed=st.integers(0, 2**16),
)
def test_random_clusters_valid(n_hosts, pg_count, size, seed):
    _check_cluster_valid(n_hosts, pg_count, size, seed)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_placement_deterministic(seed):
    _check_placement_deterministic(seed)


def _place_pg_loop(devices, pool, pg_index, seed=0):
    """The per-device loop the array-based placement replaced: the
    reference its draws must reproduce exactly."""
    rng = np.random.default_rng((seed, pool.id, pg_index))
    taken, acting = set(), []
    for step in pool.rule.steps:
        domains = set()
        pool_devs = [d for d in devices if step.device_class is None
                     or d.device_class == step.device_class]
        for _ in range(step.count):
            cands = [d for d in pool_devs if d.id not in taken
                     and d.domain(step.failure_domain) not in domains]
            weights = np.array([d.capacity for d in cands], dtype=np.float64)
            weights /= weights.sum()
            pick = cands[int(rng.choice(len(cands), p=weights))]
            acting.append(pick.id)
            taken.add(pick.id)
            domains.add(pick.domain(step.failure_domain))
    return acting


@pytest.mark.parametrize("rule", [
    PlacementRule.replicated(3, "host"),
    PlacementRule.replicated(2, "rack"),
    PlacementRule.erasure(4, 2, "host", "hdd"),
    PlacementRule.hybrid([RuleStep("ssd", 1, "host"),
                          RuleStep("hdd", 2, "host")]),
], ids=["r3-host", "r2-rack", "ec42-hdd", "hybrid"])
def test_placement_matches_loop_reference(rule):
    rng = np.random.default_rng(5)
    devs = [Device(id=i, capacity=float(rng.uniform(2, 12)) * TiB,
                   device_class="ssd" if i % 5 == 0 else "hdd",
                   host=f"host{i // 2}", rack=f"rack{i // 6}")
            for i in range(40)]
    pool = Pool(3, "p", 24, rule, stored_bytes=TiB)
    for pg in range(pool.pg_count):
        assert place_pg(devs, pool, pg, seed=9) == \
            _place_pg_loop(devs, pool, pg, seed=9)
