"""The benchmark's frozen cluster generator.

The fingerprints were recorded from the program's ``cluster_b()`` and
``cluster_e()`` as the repository stood when the benchmark was made, and
are not recomputed from program code, so no later change to the
program's generator can move the yardstick."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import clustergen  # noqa: E402

FINGERPRINTS = {
    "ceph-b": "cc94ecde31859014a7b3fe56e851fb458a003fda6e31b304fd1d23947251e03f",
    "ceph-e": "9882f35287305cbbb0ae3382b92a305b32670b1b261cdebbc05945214abe7b82",
}
SIZES = {"ceph-b": (995, 8731, 58961), "ceph-e": (617, 8321, 90499)}


def _config(name):
    with open(ROOT / "benchmarks/chip/configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(FINGERPRINTS))
def test_paper_cluster_matches_recorded_fingerprint(name):
    dep = clustergen.paper_cluster(_config(name))
    assert (dep.n_dev, len(dep.pgs), int((dep.acting >= 0).sum())) \
        == SIZES[name]
    assert clustergen.fingerprint(dep) == FINGERPRINTS[name]


def test_seed_relabels_the_same_cluster():
    cfg = json.loads((ROOT / "benchmarks/chip/tests/data/small.json")
                     .read_text())
    base = clustergen.paper_cluster(cfg)
    a = clustergen.generate(cfg, 2**31 + 11)
    b = clustergen.generate(cfg, 2**31 + 11)
    c = clustergen.generate(cfg, 5)
    assert clustergen.fingerprint(a) == clustergen.fingerprint(b)
    assert clustergen.fingerprint(a) != clustergen.fingerprint(c)
    # same capacities, sizes and per-PG placements up to the relabelling
    assert sorted(a.cap.tolist()) == sorted(base.cap.tolist())
    assert np.array_equal(a.size, base.size)
    perm = {}
    for old_row, new_row in zip(base.acting.tolist(), a.acting.tolist()):
        for o, n in zip(old_row, new_row):
            assert perm.setdefault(o, n) == n
    assert sorted(a.host) == sorted(base.host)
    used = clustergen.used_bytes(a.acting, a.size, a.n_dev)
    old = clustergen.used_bytes(base.acting, base.size, base.n_dev)
    assert sorted(used.tolist()) == sorted(old.tolist())
