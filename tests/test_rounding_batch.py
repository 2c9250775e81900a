"""Batched rounding: one vectorised best-response sweep over every chain of
every program a call rounds returns exactly what sweeping each chain alone
returns — the same routes and the same chain, sweep and relaxation-start
counts — whatever else shares the batch, and a start equal to an earlier
chain's *end* is still swept."""
import importlib

import numpy as np
import pytest

from repro.core import JRBAEngine

# the module, not the jrba() function repro.core re-exports under its name
jrba = importlib.import_module("repro.core.jrba")


# -- the oracle: one chain at a time ---------------------------------------------


def _oracle_greedy(prog):
    Nf, K, L = prog.usage.shape
    ks = np.zeros(Nf, dtype=np.int64)
    load = np.zeros(L)
    for i in np.argsort(-prog.volumes, kind="stable"):
        cand = load[None, :] + prog.usage[i] * prog.volumes[i]
        cong = np.max(cand / prog.capacity[None, :], axis=1)
        cong = np.where(prog.valid[i], cong, np.inf)
        ks[i] = int(np.argmin(cong))
        load = load + prog.usage[i, ks[i]] * prog.volumes[i]
    return ks


def _oracle_sweeps(prog, ks, sweeps, counts):
    Nf, K, L = prog.usage.shape
    order = np.argsort(-prog.volumes)
    load = prog.usage[np.arange(Nf), ks].T @ prog.volumes
    done = 0
    for _ in range(sweeps):
        done += 1
        changed = False
        for i in order:
            load = load - prog.usage[i, ks[i]] * prog.volumes[i]
            cand = load[None, :] + prog.usage[i] * prog.volumes[i]
            cong = np.max(cand / prog.capacity[None, :], axis=1)
            cong = np.where(prog.valid[i], cong, np.inf)
            new_k = int(np.argmin(cong))
            if new_k != ks[i]:
                ks[i] = new_k
                changed = True
            load = load + prog.usage[i, ks[i]] * prog.volumes[i]
        if not changed:
            break
    counts.refine_chains += 1
    counts.refine_sweeps += done
    return ks


def _oracle_round(prog, m, counts, sweeps=5):
    """The start portfolio one chain at a time, each chain sweeping its own
    copy of its start, so duplicates are starts equal to earlier starts."""
    Nf, K = prog.valid.shape
    first_valid = np.argmax(prog.valid, axis=1)
    starts = [_oracle_greedy(prog)]
    starts += [np.where(prog.valid[:, k], k, first_valid).astype(np.int64) for k in range(K)]
    seen, best_ks, best = [], None, np.inf
    for start in starts:
        if any(np.array_equal(start, s) for s in seen):
            continue
        seen.append(start)
        ks = _oracle_sweeps(prog, start.copy(), sweeps, counts)
        span = jrba._rounding_span(prog, ks)
        if span < best:
            best_ks, best = ks, span
    start_w = np.argmax(np.where(prog.valid, m, -1.0), axis=1)
    if any(np.array_equal(start_w, s) for s in seen):
        return best_ks
    ks_w = _oracle_sweeps(prog, start_w.copy(), sweeps, counts)
    if jrba._rounding_span(prog, ks_w) < best:
        counts.relax_start_wins += 1
        return ks_w
    return best_ks


# -- seeded random programs -------------------------------------------------------


def _program(rng, Nf, K, L, n_real):
    """A padded program as ``build_program`` lays it out, with flows that
    have fewer than K valid paths, a partitioned flow (no valid path),
    links floored at 1e-9 and runs of equal volumes."""
    usage = np.zeros((Nf, K, L), dtype=np.float32)
    valid = np.zeros((Nf, K), dtype=bool)
    valid[n_real:, 0] = True  # dummies: one no-op path
    for i in range(n_real):
        n_paths = int(rng.integers(1, K + 1))
        if n_real > 1 and i == n_real - 1 and rng.random() < 0.5:
            n_paths = 0  # partitioned: its endpoints share no live path
        valid[i, :n_paths] = True
        for k in range(n_paths):
            hops = rng.choice(L, size=int(rng.integers(1, min(L, 5) + 1)), replace=False)
            usage[i, k, hops] = 1.0
    volumes = np.zeros(Nf, dtype=np.float32)
    levels = np.array([1.0, 2.0, 0.5], dtype=np.float32)  # ties on purpose
    volumes[:n_real] = np.where(
        rng.random(n_real) < 0.5,
        rng.choice(levels, n_real),
        rng.uniform(0.1, 3.0, n_real).astype(np.float32),
    )
    capacity = rng.uniform(0.2, 2.0, L).astype(np.float32)
    capacity[rng.random(L) < 0.15] = 1e-9  # failed links keep the floor
    return jrba.FlowProgram(
        usage=usage,
        valid=valid,
        volumes=volumes,
        capacity=capacity,
        paths=[[[0]] * K for _ in range(n_real)],
        flows=[],
        n_real=n_real,
        link_idx=np.zeros((Nf, K, 1), dtype=np.int32),
        active_links=np.arange(L, dtype=np.int32),
        usage_active=usage,
    )


def _relaxed(rng, prog):
    """A relaxation output: near-uniform splits (argmax is noise there) on
    some programs, clear preferences on others."""
    m = rng.random(prog.valid.shape).astype(np.float32)
    if rng.random() < 0.3:
        m = 1.0 + 1e-6 * m
    return m * prog.volumes[:, None]


def _programs(seed, shape, count):
    rng = np.random.default_rng(seed)
    Nf, K, L = shape
    progs = [_program(rng, Nf, K, L, int(rng.integers(1, Nf + 1))) for _ in range(count)]
    return progs, [_relaxed(rng, p) for p in progs]


SHAPES = [(8, 4, 21), (16, 4, 21), (32, 4, 21), (8, 2, 6), (16, 3, 40)]


def _counts(stats):
    return stats.refine_chains, stats.refine_sweeps, stats.relax_start_wins


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("seed", range(4))
def test_batched_group_matches_the_chain_at_a_time_oracle(shape, seed):
    progs, ms = _programs(seed, shape, count=12)
    got_stats, want_stats = jrba.EngineStats(), jrba.EngineStats()
    got = jrba._round_group(progs, ms, got_stats)
    want = [_oracle_round(p, m, want_stats) for p, m in zip(progs, ms)]
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert _counts(got_stats) == _counts(want_stats)
    assert got_stats.refine_batches == 1


@pytest.mark.parametrize("sweeps", [1, 2])
@pytest.mark.parametrize("seed", range(3))
def test_cut_chains_match_the_oracle(seed, sweeps):
    """Chains cut before they converge leave their last sweep's routes."""
    progs, ms = _programs(100 + seed, (16, 4, 21), count=10)
    got_stats, want_stats = jrba.EngineStats(), jrba.EngineStats()
    got = jrba._round_group(progs, ms, got_stats, sweeps=sweeps)
    want = [_oracle_round(p, m, want_stats, sweeps) for p, m in zip(progs, ms)]
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert _counts(got_stats) == _counts(want_stats)


@pytest.mark.parametrize("seed", range(3))
def test_routes_do_not_depend_on_the_batch(seed):
    """Mixed shapes round one batch per shape; each program's routes equal
    those it gets rounded alone."""
    progs, ms = [], []
    for j, shape in enumerate(SHAPES):
        p, m = _programs(1000 * seed + j, shape, count=3)
        progs += p
        ms += m
    order = np.random.default_rng(seed).permutation(len(progs))
    progs, ms = [progs[j] for j in order], [ms[j] for j in order]
    engine = JRBAEngine(solver="sparse")
    together = engine._round(progs, ms)
    assert engine.stats.refine_batches == len(SHAPES)
    assert engine.stats.refined_programs == len(progs)
    for p, m, ks in zip(progs, ms, together):
        assert np.array_equal(ks, jrba._round_and_refine(p, m))


def test_single_flow_programs_round_with_the_others():
    """The analytic single-flow path's programs share the call's batch."""
    progs, ms = _programs(7, (8, 4, 21), count=6)
    single = _program(np.random.default_rng(8), 8, 4, 21, 1)
    progs.append(single)
    ms.append(jrba._fast_start(single))
    stats = jrba.EngineStats()
    got = jrba._round_group(progs, ms, stats)
    for p, m, ks in zip(progs, ms, got):
        assert np.array_equal(ks, _oracle_round(p, m, jrba.EngineStats()))
    assert stats.refine_batches == 1


def _unconverged_end_program():
    """Three flows, two paths each, four links. With one sweep the greedy
    start's chain is cut unconverged on the all-0 routes, which is also the
    later uniform all-0 start: a start equal to an earlier chain's end."""
    usage = np.zeros((3, 2, 4), dtype=np.float32)
    usage[0, 0, [1, 3]] = usage[0, 1, 2] = 1.0
    usage[1, 0, 0] = usage[1, 1, 0] = 1.0
    usage[2, 0, [1, 3]] = usage[2, 1, 0] = 1.0
    return jrba.FlowProgram(
        usage=usage,
        valid=np.ones((3, 2), dtype=bool),
        volumes=np.array([3.0, 1.0, 2.0], dtype=np.float32),
        capacity=np.array([1.0, 4.0, 2.0, 2.0], dtype=np.float32),
        paths=[[[0], [1]]] * 3,
        flows=[],
        n_real=3,
        link_idx=np.zeros((3, 2, 1), dtype=np.int32),
        active_links=np.arange(4, dtype=np.int32),
        usage_active=usage,
    )


def test_a_start_equal_to_an_earlier_chains_end_is_still_swept():
    prog = _unconverged_end_program()
    m = np.ones((3, 2), dtype=np.float32)  # argmax start: all 0
    all0, all1 = np.zeros(3, dtype=np.int64), np.ones(3, dtype=np.int64)
    greedy = _oracle_greedy(prog)
    cut = _oracle_sweeps(prog, greedy.copy(), 1, jrba.EngineStats())
    assert not np.array_equal(cut, greedy)  # the sweep moved: cut unconverged
    assert np.array_equal(cut, all0)  # ... on the all-0 start
    stats = jrba.EngineStats()
    ks = jrba._round_and_refine(prog, m, stats, sweeps=1)
    # greedy, all-0 and all-1 are three different starts: three chains
    assert (stats.refine_chains, stats.refine_sweeps) == (3, 3)
    assert np.array_equal(ks, _oracle_round(prog, m, jrba.EngineStats(), sweeps=1))
    swept0 = _oracle_sweeps(prog, all0.copy(), 1, jrba.EngineStats())
    assert np.array_equal(ks, swept0)  # the all-0 chain is the best
    assert jrba._rounding_span(prog, ks) < jrba._rounding_span(prog, cut)
    assert jrba._rounding_span(prog, ks) == min(
        jrba._rounding_span(prog, _oracle_sweeps(prog, s.copy(), 1, jrba.EngineStats()))
        for s in (greedy, all0, all1)
    )
