"""Sparse congestion solver: dense/sparse/Pallas rounding equivalence across
the scenario suite, early-exit soundness, the vectorized Eq. 15, the
single-flow fast path, and the program-tensor cache."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    Flow,
    JRBAEngine,
    OnlineScheduler,
    SCENARIOS,
    build_program,
    random_edge_network,
    random_flow_sets,
    resolve_solver,
    solve_relaxation,
    solve_relaxation_sparse,
    solve_relaxation_sparse_batch,
    wan_mesh,
)
from repro.core.jrba import _eq15_bandwidth, _finalize

K = 3
FAST_SCENARIOS = ("edge-mesh", "wan-mesh", "wan-mesh-xl", "fat-tree")


def _scenario_programs(names, n_sets=3, n_flows=5):
    """Pinned per-scenario flow programs (the acceptance corpus)."""
    progs = []
    for name in names:
        net, _ = SCENARIOS[name].build(seed=0, n_jobs=4)
        for fs in random_flow_sets(net, n_sets, n_flows, seed=11):
            prog = build_program(net, fs, k=K)
            if prog is not None:
                progs.append((name, prog))
    return progs


def _routes(prog, m, span):
    return _finalize(prog, m, span).routes


# ---------------------------------------------------------------------------
# dense / sparse / pallas-interpret rounding equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", FAST_SCENARIOS)
def test_sparse_matches_dense_rounding(name):
    """Acceptance: identical k* rounding (routes after refine) between the
    sparse solver and the dense reference on pinned scenario programs."""
    for _, prog in _scenario_programs([name]):
        m_d, sp_d = solve_relaxation(prog, n_iters=300)
        m_s, sp_s, steps = solve_relaxation_sparse(prog, n_iters=300)
        assert _routes(prog, m_s, sp_s) == _routes(prog, m_d, sp_d)
        # the relaxation certificate is an interior diagnostic; it must stay
        # in the same ballpark but is not bit-stable across formulations
        assert sp_s == pytest.approx(sp_d, rel=0.15)
        assert 0 < steps <= 300


@pytest.mark.slow
def test_sparse_matches_dense_rounding_full_suite():
    """The full core/scenarios.py suite, not just the fast subset."""
    for name, prog in _scenario_programs(sorted(SCENARIOS), n_sets=4):
        m_d, sp_d = solve_relaxation(prog, n_iters=300)
        m_s, sp_s, _ = solve_relaxation_sparse(prog, n_iters=300)
        assert _routes(prog, m_s, sp_s) == _routes(prog, m_d, sp_d), name


def test_pallas_interpret_matches_sparse_and_dense():
    """The fused Pallas kernel (interpret mode on CPU) rounds identically to
    both the jnp sparse path and the dense reference."""
    for name, prog in _scenario_programs(("edge-mesh", "wan-mesh")):
        m_d, sp_d = solve_relaxation(prog, n_iters=200)
        m_s, sp_s, st_s = solve_relaxation_sparse(prog, n_iters=200)
        m_p, sp_p, st_p = solve_relaxation_sparse(
            prog, n_iters=200, backend="pallas", interpret=True
        )
        routes_d = _routes(prog, m_d, sp_d)
        assert _routes(prog, m_p, sp_p) == routes_d, name
        assert _routes(prog, m_s, sp_s) == routes_d, name
        assert sp_p == pytest.approx(sp_s, rel=0.05)


def test_pallas_interpret_batch_matches_jnp_batch():
    net, _ = SCENARIOS["edge-mesh"].build(seed=0, n_jobs=4)
    progs = [build_program(net, fs, k=K) for fs in random_flow_sets(net, 4, 4, seed=3)]
    # group to one sparse bucket (the engine normally does this)
    key = lambda p: (p.valid.shape, p.la_pad)  # noqa: E731
    progs = [p for p in progs if key(p) == key(progs[0])]
    assert len(progs) >= 2
    out_j = solve_relaxation_sparse_batch(progs, n_iters=200)
    out_p = solve_relaxation_sparse_batch(progs, n_iters=200, backend="pallas", interpret=True)
    for prog, (m_j, sp_j, _), (m_p, sp_p, _) in zip(progs, out_j, out_p):
        assert _routes(prog, m_p, sp_p) == _routes(prog, m_j, sp_j)


def test_large_l_waxman_instance():
    """Crafted large-L Waxman: the regime the sparse formulation targets
    (L ~ 200 links, active set a fraction of that). Rounding must match the
    dense reference exactly."""
    net = wan_mesh(48, rng=np.random.RandomState(0))
    (fs,) = random_flow_sets(net, 1, 8, seed=1)
    prog = build_program(net, fs, k=K)
    assert len(net.links) > 100
    assert prog.la_pad < len(net.links)  # compression actually engaged
    m_d, sp_d = solve_relaxation(prog, n_iters=300)
    m_s, sp_s, _ = solve_relaxation_sparse(prog, n_iters=300)
    assert _routes(prog, m_s, sp_s) == _routes(prog, m_d, sp_d)


def test_link_idx_consistent_with_dense_usage():
    """The padded path->link index tensor is the canonical sparse artifact:
    scattering it back must reproduce the dense usage tensor exactly, and
    the active-compressed usage must be its gather."""
    net, _ = SCENARIOS["edge-cloud"].build(seed=0, n_jobs=4)
    (fs,) = random_flow_sets(net, 1, 5, seed=2)
    prog = build_program(net, fs, k=K)
    L = len(net.links)
    Nf, k, P = prog.link_idx.shape
    rebuilt = np.zeros((Nf, k, L + 1), dtype=np.float32)
    for i in range(Nf):
        for kk in range(k):
            for p in range(P):
                rebuilt[i, kk, prog.link_idx[i, kk, p]] = 1.0
    np.testing.assert_array_equal(rebuilt[:, :, :L], prog.usage)
    la = len(prog.active_links)
    np.testing.assert_array_equal(prog.usage_active[:, :, :la], prog.usage[:, :, prog.active_links])
    assert not prog.usage_active[:, :, la:].any()


# ---------------------------------------------------------------------------
# early-exit soundness
# ---------------------------------------------------------------------------
def test_early_exit_converged_instance_exits_early_and_matches():
    """A converged (uncontested) instance exits well before the budget with
    the same rounding as both the full schedule and the dense reference."""
    net = random_edge_network(10, mean_bandwidth=8.0, rng=np.random.RandomState(1))
    (fs,) = random_flow_sets(net, 1, 2, seed=4)
    prog = build_program(net, fs, k=K)
    m_e, sp_e, steps_e = solve_relaxation_sparse(prog, n_iters=400)
    m_f, sp_f, steps_f = solve_relaxation_sparse(prog, n_iters=400, early_exit=False)
    m_d, sp_d = solve_relaxation(prog, n_iters=400)
    assert steps_e < 400 and steps_f == 400
    routes_d = _routes(prog, m_d, sp_d)
    assert _routes(prog, m_e, sp_e) == routes_d
    assert _routes(prog, m_f, sp_f) == routes_d


def test_early_exit_bottleneck_instance_runs_full_schedule():
    """A hard bottleneck instance (8 flows contending on a thin 8-node mesh;
    its span keeps improving chunk over chunk) must NOT exit prematurely:
    the adaptive schedule walks every chunk and lands bitwise on the
    full-schedule trajectory."""
    net = random_edge_network(8, mean_bandwidth=2.0, rng=np.random.RandomState(10))
    (fs,) = random_flow_sets(net, 1, 8, seed=30)
    prog = build_program(net, fs, k=K)
    m_e, sp_e, steps_e = solve_relaxation_sparse(prog, n_iters=200)
    m_f, sp_f, steps_f = solve_relaxation_sparse(prog, n_iters=200, early_exit=False)
    assert steps_e == 200 == steps_f
    np.testing.assert_array_equal(m_e, m_f)
    assert sp_e == sp_f


def test_early_exit_never_changes_rounding_on_scheduler_corpus():
    """Soundness on the workload the scheduler actually produces: across the
    pinned scenario corpus, an instance either runs the full schedule or its
    early-exit rounding equals the full-schedule rounding (the bottleneck
    test above pins the no-premature-exit side)."""
    exited = 0
    for _, prog in _scenario_programs(FAST_SCENARIOS, n_sets=2, n_flows=4):
        m_e, sp_e, steps_e = solve_relaxation_sparse(prog, n_iters=200)
        m_f, sp_f, _ = solve_relaxation_sparse(prog, n_iters=200, early_exit=False)
        if steps_e < 200:
            exited += 1
            assert _routes(prog, m_e, sp_e) == _routes(prog, m_f, sp_f)
        else:
            np.testing.assert_array_equal(m_e, m_f)
    assert exited > 0


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_flows=st.integers(2, 7))
def test_sparse_quality_property(seed, n_flows):
    """Property sweep: on arbitrary instances the sparse solver's rounded
    span stays within tolerance of the dense reference's (identical-k* is
    pinned on the scenario suite; on adversarial random instances the two
    formulations may settle on different but equal-quality vertices)."""
    net = random_edge_network(10, mean_bandwidth=3.0, rng=np.random.RandomState(seed))
    (fs,) = random_flow_sets(net, 1, n_flows, seed=seed % 97)
    prog = build_program(net, fs, k=K)
    m_d, sp_d = solve_relaxation(prog, n_iters=200)
    m_s, sp_s, steps = solve_relaxation_sparse(prog, n_iters=200)
    rd = _finalize(prog, m_d, sp_d)
    rs = _finalize(prog, m_s, sp_s)
    assert rs.span <= rd.span * 1.15 + 1e-9
    assert rd.span <= rs.span * 1.15 + 1e-9
    assert 0 < steps <= 200
    # feasibility of the sparse result on the real link capacities
    load = np.zeros(len(net.links))
    for route, b in zip(rs.routes, rs.bandwidth):
        for u, v in zip(route, route[1:]):
            load[net.link_id(u, v)] += b
    assert np.all(load <= net.capacity * (1 + 1e-6))


# ---------------------------------------------------------------------------
# scheduler-level equivalence: sparse default must reproduce dense records
# ---------------------------------------------------------------------------
def _record_dev(a, b):
    """Strict: zero only when every schedule/finish time is EXACTLY equal
    (sign/finiteness mismatches count as full deviation, never skipped)."""
    dev = 0.0
    assert a.n_scheduled == b.n_scheduled
    for ra, rb in zip(a.records, b.records):
        for va, vb in (
            (ra.schedule_time, rb.schedule_time),
            (ra.finish_time, rb.finish_time),
        ):
            if va == vb:
                continue
            scale = abs(va) if np.isfinite(va) and va != 0 else 1.0
            gap = abs(va - vb)
            dev = max(dev, gap / scale if np.isfinite(gap) else 1.0)
    return dev


@pytest.mark.parametrize("scenario", ("edge-mesh", "wan-mesh"))
def test_otfs_records_identical_sparse_vs_dense(scenario):
    results = {}
    for mode in ("dense", "sparse"):
        engine = JRBAEngine(k=K, n_iters=150, solver=mode)
        net, arrivals = SCENARIOS[scenario].build(seed=0, n_jobs=6)
        sched = OnlineScheduler(net, "OTFS", k_paths=K, jrba_iters=150, engine=engine)
        results[mode] = sched.run(arrivals)
    assert _record_dev(results["dense"], results["sparse"]) == 0.0


@pytest.mark.slow
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_otfs_records_identical_full_suite(scenario):
    results = {}
    for mode in ("dense", "sparse"):
        engine = JRBAEngine(k=K, n_iters=200, solver=mode)
        outs = []
        for seed in range(2):
            net, arrivals = SCENARIOS[scenario].build(seed=seed, n_jobs=8)
            sched = OnlineScheduler(net, "OTFS", k_paths=K, jrba_iters=200, engine=engine)
            outs.append(sched.run(arrivals))
        results[mode] = outs
    for a, b in zip(results["dense"], results["sparse"]):
        assert _record_dev(a, b) == 0.0


# ---------------------------------------------------------------------------
# engine plumbing: fast path, program cache, solver modes, sparse buckets
# ---------------------------------------------------------------------------
def test_single_flow_fast_path_matches_dense():
    net, _ = SCENARIOS["edge-mesh"].build(seed=0, n_jobs=4)
    for seed in range(6):
        (fs,) = random_flow_sets(net, 1, 1, seed=seed)
        sparse = JRBAEngine(k=K, n_iters=200, solver="sparse")
        dense = JRBAEngine(k=K, n_iters=200, solver="dense")
        rs, rd = sparse.solve(net, fs), dense.solve(net, fs)
        assert rs.routes == rd.routes
        assert rs.bandwidth == pytest.approx(rd.bandwidth)
        assert sparse.stats.fast_path_solves == 1
        assert sparse.stats.solver_steps == 0  # no relaxation ran at all
        assert sparse.stats.single_solves == 0


def test_program_cache_shares_tensors_and_refreshes_capacity():
    net, _ = SCENARIOS["edge-mesh"].build(seed=0, n_jobs=4)
    (fs,) = random_flow_sets(net, 1, 4, seed=5)
    eng = JRBAEngine(k=K, n_iters=100)
    p1 = eng.build(net, fs)
    p2 = eng.build(net, fs, capacity=net.capacity * 0.5)
    assert eng.stats.prog_cache_misses == 1 and eng.stats.prog_cache_hits == 1
    # solve-invariant tensors (and the device-mirror dict) are shared…
    assert p1.usage is p2.usage
    assert p1.link_idx is p2.link_idx
    assert p1.usage_active is p2.usage_active
    assert p1.dev is p2.dev
    # …while capacity is per-solve
    assert p2.capacity == pytest.approx(np.maximum(net.capacity * 0.5, 1e-9).astype(np.float32))
    # a different flow set is a different entry
    (fs2,) = random_flow_sets(net, 1, 4, seed=6)
    eng.build(net, fs2)
    assert eng.stats.prog_cache_misses == 2


def test_resolve_solver_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "dense")
    assert resolve_solver("auto") == "dense"
    assert JRBAEngine(solver="auto").solver == "dense"
    # explicit choice beats the env
    assert resolve_solver("sparse") == "sparse"
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "bogus")
    with pytest.raises(ValueError):
        resolve_solver("auto")


def test_sparse_cross_network_bucket_batching():
    """Sparse buckets never see L: programs from different topologies (and
    different link counts) share one compiled batch whenever their
    active-compressed shapes agree."""
    nets = [
        random_edge_network(n, mean_bandwidth=4.0, rng=np.random.RandomState(s))
        for n, s in ((10, 5), (12, 6))
    ]
    assert len({len(n.links) for n in nets}) == 2  # genuinely different L
    eng = JRBAEngine(k=K, n_iters=100, solver="sparse")
    sets, use = [], []
    for net, fseed in zip(nets, (4, 2)):
        (fs,) = random_flow_sets(net, 1, 3, seed=fseed)
        prog = eng.build(net, fs)
        sets.append(fs)
        use.append(eng._shape_key(prog))
    assert use[0] == use[1], f"pinned programs drifted buckets: {use}"
    out = eng.solve_many(nets, sets)
    assert all(r is not None for r in out)
    assert eng.stats.batched_solves == 1
    assert eng.stats.batched_instances == 2


def test_eq15_vectorized_matches_loop_reference():
    rng = np.random.RandomState(0)
    for _ in range(20):
        n, L = rng.randint(1, 7), rng.randint(2, 12)
        sel = (rng.rand(n, L) < 0.3).astype(np.float32)
        vols = rng.uniform(0.5, 4.0, n).astype(np.float32)
        cap = rng.uniform(0.5, 5.0, L).astype(np.float32)
        got = _eq15_bandwidth(sel, vols, cap)
        crossing = sel.T @ vols
        with np.errstate(divide="ignore", invalid="ignore"):
            share = np.where(crossing > 0, cap / crossing, np.inf)
        for i in range(n):
            links = sel[i] > 0
            want = vols[i] * (share[links].min() if links.any() else np.inf)
            assert got[i] == want or (np.isinf(got[i]) and np.isinf(want))
