"""Plain reference for the scheduler's answers, written from the semantics
alone: it imports nothing of the program and reads only plain data (nodes'
power and free memory, link endpoints and capacities, which links are up,
the job's tasks and edges, the flows asked for, and what was answered).

What the scheduler promises (ENTS Sec. V, Algorithms 1 and 2, Eq. 15):

* Algorithm 1 visits a job's tasks in topological order (Kahn's, the last
  task made ready first) and puts each on the node with room that minimises
  its compute time plus the slowest transfer from an already placed
  predecessor; a transfer runs at the average capacity of the links of the
  cheapest route (one per hop, a thousandth over the link's capacity on top,
  so that of equally short routes the one of fatter links wins); a
  colocated transfer is free and a cut-off one never ends; the first node
  wins a tie; pinned tasks stay where they are and draw no memory;
* a JRBA answer answers exactly the flows that cross nodes (``src != dst``,
  volume above 0), in the order asked; each flow's candidate paths are the
  ``k`` shortest loopless paths by hop count over live links (any of
  several equally long ones), and its route is one of them;
* the relaxation (P3-RELAX-CVX) spreads each flow's volume over its
  candidate paths to lower the largest link congestion, by the schedule
  ``relaxation`` below spells out; its answer's span is the congestion of
  the spread it returns;
* rounding picks one path per flow by best-response sweeps (each flow, in
  order of falling volume, re-picks the path of least resulting congestion,
  up to 5 sweeps) from a greedy start and from every all-``k`` start; the
  relaxation's own start may only make it better. So an answer's span is at
  most the best of those relaxation-free starts (the program rounds in
  float32, where paths tie that float64 tells apart, so the check takes
  the better of the float32 and float64 roundings' bests as the bar);
* bandwidths follow Eq. 15 over the offered capacity (floored at 1e-9): on
  every link the capacity splits across the flows crossing it in proportion
  to volume, and a flow gets its smallest share along its route; the span is
  the largest ``volume / bandwidth``.
"""
from __future__ import annotations

import collections
import heapq

import numpy as np

# the smallest capacity a solve sees: a link with nothing left offers a
# vanishing share, not none
CAPACITY_FLOOR = 1e-9
SWEEPS = 5


def _key(u, v):
    return (u, v) if u < v else (v, u)


def _bfs_path(adj, src, dst, banned_nodes, banned_edges):
    """Fewest-hop path src -> dst avoiding the banned nodes and edges."""
    if src in banned_nodes or dst in banned_nodes:
        return None
    prev = {src: None}
    queue = collections.deque([src])
    while queue:
        u = queue.popleft()
        if u == dst:
            break
        for v in sorted(adj[u]):
            if v in prev or v in banned_nodes or _key(u, v) in banned_edges:
                continue
            prev[v] = u
            queue.append(v)
    if dst not in prev:
        return None
    path = [dst]
    while path[-1] != src:
        path.append(prev[path[-1]])
    return path[::-1]


def k_shortest_hops(adj, src, dst, k):
    """Hop counts of the ``k`` shortest loopless paths (Yen's algorithm on
    unit weights). The multiset of the ``k`` smallest hop counts does not
    depend on how ties are broken."""
    first = _bfs_path(adj, src, dst, set(), set())
    if first is None:
        return []
    found = [first]
    cands: list[tuple[int, tuple[int, ...]]] = []
    seen = {tuple(first)}
    while len(found) < k:
        last = found[-1]
        for i in range(len(last) - 1):
            root = last[: i + 1]
            banned_edges = {_key(p[i], p[i + 1]) for p in found
                            if len(p) > i + 1 and p[: i + 1] == root}
            spur = _bfs_path(adj, last[i], dst, set(root[:-1]), banned_edges)
            if spur is None:
                continue
            total = tuple(root[:-1] + spur)
            if total not in seen:
                seen.add(total)
                heapq.heappush(cands, (len(total) - 1, total))
        if not cands:
            break
        found.append(list(heapq.heappop(cands)[1]))
    return sorted(len(p) - 1 for p in found)


class Topology:
    """Adjacency of one network state, with a memo of k-shortest hop counts."""

    def __init__(self, links, alive=None):
        self.n_links = len(links)
        self.link_id = {}
        self.adj = collections.defaultdict(set)
        for l, (u, v) in enumerate(links):
            self.link_id[_key(u, v)] = l
            if alive is None or alive[l]:
                self.adj[u].add(v)
                self.adj[v].add(u)
        self._hops = {}

    def hops(self, src, dst, k):
        key = (src, dst, k)
        if key not in self._hops:
            self._hops[key] = k_shortest_hops(self.adj, src, dst, k)
        return self._hops[key]

    def path_links(self, path):
        """Link ids of a node path over live links, or None where it is no
        loopless walk over live links."""
        if len(path) < 2 or len(set(path)) != len(path):
            return None
        ids = []
        for u, v in zip(path[:-1], path[1:]):
            if v not in self.adj[u]:
                return None
            ids.append(self.link_id[_key(u, v)])
        return ids


# ---------------------------------------------------------------------------
# Algorithm 2: candidates, relaxation, rounding, Eq. 15
# ---------------------------------------------------------------------------
def check_candidates(topo: Topology, k, flows, paths, routes):
    """Faults in the candidate paths and routes of one answer, and the link
    ids of each flow's candidates (``None`` where they are at fault)."""
    faults, cand_links = 0, []
    for (s, d, _), ps, route in zip(flows, paths, routes):
        want = topo.hops(s, d, k)
        ids = [topo.path_links(list(p)) for p in ps]
        ok = (
            len(ps) == len(want)
            and all(x is not None for x in ids)
            and all(p[0] == s and p[-1] == d for p in ps)
            and len({tuple(p) for p in ps}) == len(ps)
            and sorted(len(x) for x in ids) == want
        )
        if want:
            ok = ok and list(route) in [list(p) for p in ps]
        else:
            ok = ok and len(route) == 0
        faults += not ok
        cand_links.append(ids if ok else None)
    return faults, cand_links


def eq15(vol, cap, on_route):
    """Eq. 15 bandwidths of flows on fixed routes (link id lists)."""
    crossing = np.zeros(len(cap))
    for i, ids in enumerate(on_route):
        for l in ids:
            crossing[l] += vol[i]
    b = np.zeros(len(vol))
    for i, ids in enumerate(on_route):
        if ids:
            b[i] = vol[i] * min(cap[l] / crossing[l] for l in ids)
    return b


def bandwidth_gap(vol, cap, on_route, bandwidth, span):
    """Widest relative gap of the answered bandwidths and span from Eq. 15."""
    ref_b = eq15(vol, cap, on_route)
    got_b = np.asarray(bandwidth, dtype=np.float64)
    if got_b.shape != ref_b.shape:
        return np.inf
    gap = float(np.max(np.abs(got_b - ref_b) / np.maximum(np.abs(ref_b), 1e-12))) if len(
        ref_b) else 0.0
    with np.errstate(divide="ignore"):
        ref_span = float(np.max(np.where(ref_b > 0, vol / np.maximum(ref_b, 1e-300), np.inf)))
    if np.isfinite(ref_span) or np.isfinite(span):
        if not (np.isfinite(ref_span) and np.isfinite(span)):
            return np.inf
        gap = max(gap, abs(span - ref_span) / max(ref_span, 1e-12))
    return gap


def _usage(cand_links, n_links):
    """Dense 0/1 usage ``(n, K, L)`` of flows' candidate paths, and which
    candidates exist."""
    n = len(cand_links)
    K = max(len(c) for c in cand_links)
    u = np.zeros((n, K, n_links))
    valid = np.zeros((n, K), dtype=bool)
    for i, cs in enumerate(cand_links):
        for kk, ids in enumerate(cs):
            u[i, kk, ids] = 1.0
            valid[i, kk] = True
    return u, valid


def chunks(n_iters):
    """Chunks of the relaxation's schedule: the most (at most 16) that
    divide ``n_iters`` with at least 25 steps each, and their length."""
    best = 1
    for c in range(1, min(16, n_iters) + 1):
        if n_iters % c == 0 and n_iters // c >= 25:
            best = c
    return best, n_iters // best


def relaxation(cand_links, vol, cap, *, n_iters=400, lr=0.25, span_rtol=2e-2,
               stable_chunks=2, min_chunks=2):
    """The relaxation as ENTS's JRBA runs it here, in float64: path weights
    are a softmax of logits over each flow's candidates, and Adam (0.9,
    0.999, 1e-8, step ``lr``) descends the smoothed largest congestion
    ``tau * logsumexp(c / tau)`` over every link while ``tau`` falls
    geometrically from 1 to 1e-3. At the end of every chunk it reads the
    exact span of the weights and stops once at least ``min_chunks`` chunks
    ran, the argmax path of every flow held for ``stable_chunks`` chunks and
    the span moved by at most ``span_rtol``. Returns the span and the path
    weights."""
    u, valid = _usage(cand_links, len(cap))
    mask = np.where(valid, 0.0, -1e9)
    taus = np.geomspace(1.0, 1e-3, n_iters)
    n_chunks, steps = chunks(n_iters)
    logits = np.zeros(valid.shape)
    m1 = np.zeros(valid.shape)
    m2 = np.zeros(valid.shape)

    def weights(x):
        z = x + mask
        e = np.exp(z - z.max(axis=1, keepdims=True))
        return e / e.sum(axis=1, keepdims=True)

    def congestion_of(w):
        return np.einsum("i,ik,ikl->l", vol, w, u) / cap

    span_prev, ks_prev, stable = np.inf, None, 0
    for ci in range(n_chunks):
        for s in range(steps):
            t = ci * steps + s
            tau = taus[t]
            w = weights(logits)
            c = congestion_of(w)
            e = np.exp((c - c.max()) / tau)
            glink = e / e.sum() / cap
            gw = vol[:, None] * np.einsum("ikl,l->ik", u, glink)
            g = w * (gw - (w * gw).sum(axis=1, keepdims=True))
            m1 = 0.9 * m1 + 0.1 * g
            m2 = 0.999 * m2 + 0.001 * g * g
            mh = m1 / (1 - 0.9 ** (t + 1))
            vh = m2 / (1 - 0.999 ** (t + 1))
            logits = logits - lr * mh / (np.sqrt(vh) + 1e-8)
        w = weights(logits)
        span = float(np.max(congestion_of(w)))
        ks = np.argmax(logits + mask, axis=1)
        stable = stable + 1 if ks_prev is not None and np.array_equal(ks, ks_prev) else 0
        if (ci + 1 >= min_chunks and stable >= stable_chunks
                and abs(span - span_prev) <= span_rtol * max(span, 1e-12)):
            break
        span_prev, ks_prev = span, ks
    return span, w


def congestion(m, cand_links, cap):
    """Largest link congestion of path volumes ``m[i, k]``."""
    load = np.zeros(len(cap))
    for i, cs in enumerate(cand_links):
        for kk, ids in enumerate(cs):
            load[ids] += m[i, kk]
    return float(np.max(load / cap))


def relaxation_gap(cand_links, vol, cap, m, relaxed, n_iters, accept):
    """How far the relaxation's answer lies from the plain one: the larger
    log ratio of its span, and of the congestion of the spread it returned,
    to the plain span, or the widest relative gap of a flow's spread total
    from its volume. Ratios, since the two runs' Adam steps part ways in
    flat directions (float32 against float64) and the spans with them by a
    share; a spread of nothing reads infinite."""
    want, _ = relaxation(cand_links, vol, cap, n_iters=n_iters)
    if want > accept and relaxed > accept:
        return 0.0  # both say: no room; see check.py
    m = np.asarray(m, dtype=np.float64)
    K = max(len(c) for c in cand_links)
    spread = np.zeros((len(vol), K))
    spread[:, : min(K, m.shape[1])] = m[:, :K]
    for i, cs in enumerate(cand_links):
        spread[i, len(cs):] = 0.0  # nothing rides a missing candidate
    total_gap = float(np.max(np.abs(m.sum(axis=1) - vol) / vol))
    c = congestion(spread, cand_links, cap)
    with np.errstate(divide="ignore"):
        return max(abs(np.log(relaxed / want)), abs(np.log(c / want)), total_gap)


def _sweeps(u, valid, vol, cap, ks):
    order = np.argsort(-vol, kind="stable")
    load = u[np.arange(len(vol)), ks].T @ vol
    for _ in range(SWEEPS):
        changed = False
        for i in order:
            load = load - u[i, ks[i]] * vol[i]
            cong = np.max((load[None, :] + u[i] * vol[i]) / cap[None, :], axis=1)
            best = int(np.argmin(np.where(valid[i], cong, np.inf)))
            if best != ks[i]:
                ks[i] = best
                changed = True
            load = load + u[i, ks[i]] * vol[i]
        if not changed:
            break
    return ks


def _span(u, vol, cap, ks):
    return float(np.max((u[np.arange(len(vol)), ks].T @ vol) / cap))


def rounding_span(cand_links, vol, cap, dtype=np.float64):
    """Best span of the rounding from its relaxation-free starts: the greedy
    start (flows by falling volume each take the path of least resulting
    congestion) and every all-``k`` start, each refined by the sweeps; all
    arithmetic in ``dtype``."""
    u, valid = _usage(cand_links, len(cap))
    u, vol, cap = u.astype(dtype), np.asarray(vol, dtype), np.asarray(cap, dtype)
    n, K = valid.shape
    ks = np.zeros(n, dtype=np.int64)
    load = np.zeros(len(cap), dtype)
    for i in np.argsort(-vol, kind="stable"):
        cong = np.max((load[None, :] + u[i] * vol[i]) / cap[None, :], axis=1)
        ks[i] = int(np.argmin(np.where(valid[i], cong, np.inf)))
        load = load + u[i, ks[i]] * vol[i]
    starts = [ks]
    first = np.argmax(valid, axis=1)
    for kk in range(K):
        starts.append(np.where(valid[:, kk], kk, first).astype(np.int64))
    return min(_span(u, vol, cap, _sweeps(u, valid, vol, cap, s.copy())) for s in starts)


# ---------------------------------------------------------------------------
# Algorithm 1
# ---------------------------------------------------------------------------
def _cheapest_route(adj, cap_of, src, dst):
    """Links of the cheapest route (1 per hop plus 1e-3 over capacity)."""
    dist = {src: 0.0}
    prev = {}
    heap = [(0.0, src)]
    done = set()
    while heap:
        d, u = heapq.heappop(heap)
        if u in done:
            continue
        done.add(u)
        if u == dst:
            break
        for v in adj[u]:
            nd = d + 1.0 + 1e-3 / max(cap_of(u, v), 1e-9)
            if nd < dist.get(v, np.inf):
                dist[v], prev[v] = nd, u
                heapq.heappush(heap, (nd, v))
    if dst not in done:
        return None
    path = [dst]
    while path[-1] != src:
        path.append(prev[path[-1]])
    path.reverse()
    return list(zip(path[:-1], path[1:]))


def algorithm1(power, mem, links, capacity, alive, tasks, edges):
    """Placement of one job: ``tasks`` are ``(workload, mem, pinned node or
    None)``, ``edges`` ``(u, v, volume)``; returns the node of each task, or
    None where some task fits nowhere."""
    n_nodes = len(power)
    adj = collections.defaultdict(set)
    cap = {}
    for l, (u, v) in enumerate(links):
        cap[_key(u, v)] = float(capacity[l]) if alive[l] else 0.0
        if alive[l]:
            adj[u].add(v)
            adj[v].add(u)
    avg = {}

    def avg_bw(a, b):
        if a == b:
            return np.inf
        if (a, b) not in avg:
            hops = _cheapest_route(adj, lambda u, v: cap[_key(u, v)], a, b)
            avg[(a, b)] = 0.0 if hops is None else (
                sum(cap[_key(u, v)] for u, v in hops) / len(hops))
        return avg[(a, b)]

    n = len(tasks)
    preds = [[] for _ in range(n)]
    succ = [[] for _ in range(n)]
    indeg = [0] * n
    for u, v, vol in edges:
        preds[v].append((u, vol))
        succ[u].append(v)
        indeg[v] += 1
    stack = [i for i in range(n) if indeg[i] == 0]
    order = []
    while stack:
        u = stack.pop()
        order.append(u)
        for v in succ[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                stack.append(v)
    place = [-1] * n
    free = np.array(mem, dtype=np.float64)
    for i in order:
        work, need, pinned = tasks[i]
        if pinned is not None:
            place[i] = pinned
            continue
        best, best_t = -1, np.inf
        for j in range(n_nodes):
            if free[j] < need:
                continue
            t_comm = 0.0
            for p, vol in preds[i]:
                if place[p] < 0:
                    continue
                bw = avg_bw(place[p], j)
                if bw == 0.0:
                    t_comm = np.inf
                    break
                t_comm = max(t_comm, 0.0 if bw == np.inf else vol / bw)
            t = work / power[j] + t_comm
            if t < best_t:
                best, best_t = j, t
        if best < 0:
            return None
        place[i] = best
        free[best] -= need
    return place


def job_span(power, place, workloads, volumes, bandwidths):
    """The time per stream unit of a placed and routed job: the busiest
    node's summed compute, or the slowest flow's ``volume / bandwidth``."""
    load = np.zeros(len(power))
    for node, w in zip(place, workloads):
        load[node] += w
    t = float(np.max(load / np.asarray(power, dtype=np.float64)))
    for v, b in zip(volumes, bandwidths):
        t = max(t, np.inf if b <= 0 else v / b)
    return t
