"""The plain reference on instances small enough to work out by hand."""
import numpy as np
import pytest

import reference as ref

# a 4-node network: links by id (sorted)
LINKS = [(0, 1), (0, 2), (0, 3), (1, 2), (2, 3)]
CAP = np.array([2.0, 1.0, 4.0, 2.0, 4.0])


def test_eq15_shares_each_link_by_volume():
    # flow a 0-1-2 (links 0, 3), flow b 1-2 (link 3): link 3 carries 4 units
    # of volume, so a gets 1 * 2/4 on it (link 0 offers 2/1), b gets 3 * 2/4
    vol = np.array([1.0, 3.0])
    assert ref.eq15(vol, CAP, [[0, 3], [3]]).tolist() == [0.5, 1.5]
    assert ref.bandwidth_gap(vol, CAP, [[0, 3], [3]], [0.5, 1.5], 2.0) == 0.0


def test_bandwidth_gap_is_relative():
    vol = np.array([1.0])
    gap = ref.bandwidth_gap(vol, CAP, [[1]], [1.001], 1.0 / 1.001)
    assert gap == pytest.approx(1e-3, rel=1e-6)


def test_k_shortest_hops_count_loopless_paths():
    links = [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (1, 4)]
    topo = ref.Topology(links)
    # 0 -> 4: 0-1-4 (2 hops), 0-1-3-4 and 0-2-3-4 (3), 0-2-3-1-4 (4)
    assert topo.hops(0, 4, 2) == [2, 3]
    assert topo.hops(0, 4, 4) == [2, 3, 3, 4]
    assert topo.hops(0, 4, 9) == [2, 3, 3, 4]


def test_candidates_must_be_the_k_shortest_and_hold_the_route():
    topo = ref.Topology(LINKS)
    flows = [(0, 2, 1.0)]
    # 0 -> 2: 0-2 (1 hop), 0-1-2 and 0-3-2 (2 hops)
    good = [[[0, 2], [0, 1, 2], [0, 3, 2]]]
    assert ref.check_candidates(topo, 4, flows, good, [[0, 1, 2]])[0] == 0
    # the route is none of the candidates
    assert ref.check_candidates(topo, 4, flows, good, [[0, 3, 1, 2]])[0] == 1
    # a candidate missing while another exists
    assert ref.check_candidates(topo, 4, flows, [good[0][:2]], [[0, 2]])[0] == 1
    # a candidate that is longer than the k shortest (k = 2: 1 and 2 hops)
    assert ref.check_candidates(topo, 2, flows, [[[0, 2], [0, 1, 2]]], [[0, 2]])[0] == 0
    assert ref.check_candidates(topo, 2, flows, [[[0, 2], [0, 3, 1, 2]]], [[0, 2]])[0] == 1
    # a loop, and a link that does not exist (1-3)
    assert ref.check_candidates(topo, 2, flows, [[[0, 2], [0, 1, 0, 2]]], [[0, 2]])[0] == 1
    assert ref.check_candidates(topo, 2, flows, [[[0, 2], [0, 1, 3, 2]]], [[0, 2]])[0] == 1


def test_dead_links_and_cut_endpoints():
    alive = np.array([True, False, True, True, True])  # 0-2 is down
    topo = ref.Topology(LINKS, alive)
    flows = [(0, 2, 1.0)]
    assert ref.check_candidates(topo, 4, flows, [[[0, 1, 2], [0, 3, 2]]], [[0, 1, 2]])[0] == 0
    assert ref.check_candidates(topo, 4, flows, [[[0, 2], [0, 1, 2]]], [[0, 2]])[0] == 1
    cut = ref.Topology([(0, 1), (2, 3)])
    assert ref.check_candidates(cut, 4, flows, [[]], [[]]) == (0, [[]])


def test_rounding_from_every_start_finds_the_split():
    # two flows 0 -> 2 of volume 1, candidates 0-2 (link 1, capacity 1) and
    # 0-1-2 (links 0, 3, capacity 2): both on 0-2 give congestion 2, one
    # each gives max(1/1, 1/2) = 1, both on the detour 2/2 = 1
    cands = [[[1], [0, 3]], [[1], [0, 3]]]
    vol = np.array([1.0, 1.0])
    assert ref.rounding_span(cands, vol, CAP) == pytest.approx(1.0)
    assert ref.rounding_span(cands, vol, CAP, np.float32) == pytest.approx(1.0)


def test_relaxation_splits_a_flow_over_parallel_paths():
    # one flow of volume 3 over links of capacity 1 and 2: the best spread
    # sends 1 and 2, congestion 1; the even spread reads 1.5
    cap = np.array([1.0, 2.0])
    cands = [[[0], [1]]]
    vol = np.array([3.0])
    span, w = ref.relaxation(cands, vol, cap)
    assert 1.0 <= span < 1.1
    assert w.sum() == pytest.approx(1.0)
    m = w * vol[:, None]
    assert ref.relaxation_gap(cands, vol, cap, m, span, 400, 1e4) == pytest.approx(0.0, abs=1e-9)
    even = np.array([[1.5, 1.5]])
    assert ref.relaxation_gap(cands, vol, cap, even, 1.5, 400, 1e4) > 0.3
    assert ref.relaxation_gap(cands, vol, cap, np.zeros((1, 2)), 0.0, 400, 1e4) == np.inf


def test_algorithm1_weighs_compute_and_transfer():
    # a line 0 - 1 - 2; node 1 is fast, node 2 faster but two hops away
    power = [10.0, 100.0, 200.0]
    links = [(0, 1), (1, 2)]
    cap = [2.0, 1.0]
    alive = [True, True]
    # the source is pinned to 0; task 1 (work 10, memory 1) reads 1 unit
    tasks = [(0.0, 0.0, 0), (10.0, 1.0, None)]
    edges = [(0, 1, 1.0)]
    # on 0: 10/10 = 1; on 1: 10/100 + 1/2 = 0.6; on 2: 10/200 + 1/1.5 = 0.72
    assert ref.algorithm1(power, [4.0, 4.0, 4.0], links, cap, alive, tasks, edges) == [0, 1]
    # no room on 1: the next best, 2
    assert ref.algorithm1(power, [4.0, 0.5, 4.0], links, cap, alive, tasks, edges) == [0, 2]
    # no room anywhere
    assert ref.algorithm1(power, [0.5, 0.5, 0.5], links, cap, alive, tasks, edges) is None
    # a cut link: node 2 can no longer be reached from the source
    assert ref.algorithm1(power, [4.0, 0.5, 4.0], links, cap, [True, False], tasks,
                          edges) == [0, 0]


def test_job_span_is_the_busiest_node_or_the_slowest_flow():
    power = [10.0, 100.0]
    assert ref.job_span(power, [0, 1, 1], [0.0, 50.0, 30.0], [2.0], [4.0]) == 0.8
    assert ref.job_span(power, [0, 1, 1], [0.0, 50.0, 30.0], [2.0], [1.0]) == 2.0
    assert ref.job_span(power, [0, 1], [0.0, 1.0], [2.0], [0.0]) == np.inf
