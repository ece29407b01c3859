"""The plain reference against the program's brute-force oracle at a
small size, its comparisons, and its control."""

import numpy as np
import pytest

from bench import gen, reference

CFG = {"data": dict(n=4000, d=3, variant="simden", restarts=10,
                    c_reset=100, r_vicinity=100.0, r_shift=150.0,
                    noise_frac=0.01, domain=1e5, seed=0, round=True)}


@pytest.fixture(scope="module")
def table():
    return gen.make_table(CFG, 1)


@pytest.fixture(scope="module")
def brute(table):
    from repro.engine import cluster
    return cluster(table, 200.0, 10, engine="brute")


@pytest.fixture(scope="module")
def ref(table):
    return reference.fit(table, 200.0, 10)


def test_reference_equals_the_brute_oracle(ref, brute):
    assert np.array_equal(ref.core, brute.core)
    assert reference.compare_fit(ref, brute.labels, brute.core) == \
        {"core_flags": 0, "labels": 0}
    assert reference.compare_fit(ref, ref.labels(), ref.core) == \
        {"core_flags": 0, "labels": 0}


def test_broken_answers_are_counted(ref, brute):
    lab = brute.labels.copy()
    core = brute.core.copy()
    lab[np.flatnonzero(lab >= 0)[:3]] = -1
    core[np.flatnonzero(core)[:2]] = False
    got = reference.compare_fit(ref, lab, core)
    assert got["core_flags"] == 2 and got["labels"] >= 3
    merged = brute.labels.copy()
    merged[merged == 1] = 0                 # two clusters made one
    assert reference.compare_fit(ref, merged, brute.core)["labels"] > 0


def test_an_equally_near_core_of_another_cluster_is_accepted():
    # two clusters whose nearest cores are equally far from the point
    a = np.array([[0, 0, 0]] * 4 + [[300, 0, 0]] * 4, np.float64)
    a += np.array([[0, 0, 0], [0, 1, 0], [0, 0, 1], [0, 1, 1]] * 2)
    a = np.concatenate([a, [[150.0, 0, 0]]])
    ref = reference.fit(a, 150.0, 4)
    assert not ref.core[-1] and ref.core[:-1].all()
    lab = ref.labels()
    assert lab[0] != lab[4]
    for other in (lab[0], lab[4]):
        lab[-1] = other
        assert reference.compare_fit(ref, lab, ref.core)["labels"] == 0


def test_control_fails_the_comparison():
    # a core point only through two neighbours at distance exactly eps,
    # and a border point reached only at distance exactly eps
    t = np.array([[0, 0, 0], [100, 0, 0], [0, 100, 0], [-60, -80, 0]],
                 np.float64)
    ref = reference.fit(t, 100.0, 3)
    assert ref.core.tolist() == [True, False, False, False]
    assert (ref.labels() == 0).all()
    ctl = reference.fit(t, 100.0, 3, ties=False)
    got = reference.compare_fit(ref, ctl.labels(), ctl.core)
    assert got == {"core_flags": 1, "labels": 4}


def test_control_script_runs_the_cell():
    from bench import control
    checks = control.control_checks("ss3d-1m-batch.fit", seed=3, n=3000)
    assert [lim for _, _, lim in checks] == [0] * len(checks)


def test_roofline_work_counts_every_pair_within_eps(table):
    from scipy.spatial import cKDTree
    from bench import roofline
    eps, min_pts = 200.0, 10
    work = roofline.eps_count_work(table, eps, min_pts)
    # every pair within eps of a point in a cell below MinPts is counted,
    # and nothing beyond the cells that can hold such a pair
    side = eps / np.sqrt(3)
    cells = np.floor((table - table.min(0)) / side).astype(np.int64)
    _, inv, occ = np.unique(cells, axis=0, return_inverse=True,
                            return_counts=True)
    small = occ[inv.ravel()] < min_pts
    near = cKDTree(table).query_ball_point(table[small], eps + 1e-6,
                                           return_length=True)
    assert near.sum() <= work["distances"] <= small.sum() * len(table)
    assert work["flops"] == 8 * work["distances"]
