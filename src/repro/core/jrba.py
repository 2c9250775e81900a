"""JRBA — Joint Routing and Bandwidth Allocation (paper Algorithm 2).

The paper relaxes P3 (route + bandwidth per flow, min of max V_i/b_i) to the
convex program P3-RELAX-CVX (Eqs. 10-14) and solves it with an off-the-shelf
convex optimizer, then rounds (k* = argmax_k m_i^k) and recovers bandwidths
via Eq. 15.

Eliminating ``q_i`` at its optimum (q_i = V_i: shrinking q only loosens
Eq. 11) leaves the classic *maximum concurrent flow / minimum congestion* LP:

    min_{w_i in simplex}  max_l ( sum_i V_i w_i^k [l in P_i^k] / B_l )

We solve it natively in JAX: Adam on per-flow path logits against a
temperature-annealed logsumexp smoothing of the max — jit-compiled,
vmap-friendly, no external solver. Rounding and Eq. 15 follow the paper
verbatim; the optional water-filling top-up (beyond-paper, see DESIGN.md §4)
redistributes capacity stranded by Eq. 15 and is reported separately.

Two solver formulations share that math:

* **dense** — the original reference: a ``(Nf, K, L)`` usage einsum per Adam
  step, autodiff gradient, fixed ``n_iters`` schedule. Byte-stable, kept as
  the cross-check oracle.
* **sparse** — the production path: each candidate path crosses only a
  handful of links, so the congestion vector is supported on the *active
  link set* (every link on any candidate path, derived from the padded
  path->link index tensor ``FlowProgram.link_idx``). The solver runs on
  tensors compressed to ``La_pad`` active-link slots (power-of-two bucketed;
  the L - La_pad inactive links contribute exactly ``exp(-max_c/tau)`` each
  to the softmax denominator, folded in as one scalar correction, so the
  objective equals the dense one), with a hand-fused gradient (no autodiff
  tape) and a convergence-adaptive schedule: the tau anneal runs in chunks
  under ``lax.while_loop`` and exits once the exact span plateaus. On TPU
  the per-chunk step loop additionally runs as the fused Pallas kernel in
  ``repro.kernels.jrba_congestion``.

``JRBAEngine`` picks the formulation per backend (``solver="auto"``:
Pallas on TPU, sparse-jnp elsewhere; ``REPRO_JRBA_SOLVER`` overrides), and
adds a per-program tensor cache so repeated solves of the same flow set —
the OTFS re-solve loop — rebuild nothing and re-upload only capacity.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import os
import time
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Flow, NetworkGraph
from .paths import k_shortest_paths, path_link_index, path_links
from ..obs import compiles
from ..obs.trace import NULL_TRACER

__all__ = [
    "EngineStats",
    "FlowProgram",
    "JRBAEngine",
    "JRBAResult",
    "build_program",
    "solve_relaxation",
    "solve_relaxation_batch",
    "solve_relaxation_sparse",
    "solve_relaxation_sparse_batch",
    "jrba",
    "link_load_fits",
    "water_fill",
    "brute_force_span",
]

SOLVERS = ("dense", "sparse", "pallas", "pallas-interpret")


def resolve_solver(solver: str = "auto") -> str:
    """Map ``"auto"`` (after the ``REPRO_JRBA_SOLVER`` env override) to the
    backend-appropriate formulation: the fused Pallas kernel on TPU, the
    sparse jnp path everywhere else."""
    if solver == "auto":
        solver = os.environ.get("REPRO_JRBA_SOLVER", "auto")
    if solver == "auto":
        solver = "pallas" if jax.default_backend() == "tpu" else "sparse"
    if solver not in SOLVERS:
        raise ValueError(f"unknown solver {solver!r}; one of {('auto', *SOLVERS)}")
    return solver


def _clamp_capacity(net: NetworkGraph, capacity: np.ndarray | None) -> np.ndarray:
    """Solver-facing capacity vector: f32, floored at 1e-9. One definition,
    shared by :func:`build_program` and the engine's program-cache hit path —
    the OTFS speculation staleness check (``online.spec_exact``) compares
    residuals through this exact clamp, so the two construction paths must
    never diverge."""
    cap = (net.capacity if capacity is None else capacity).astype(np.float32)
    return np.maximum(cap, 1e-9)


@dataclasses.dataclass
class FlowProgram:
    """Tensorized P3 instance over K candidate paths per flow.

    Rows may be padded with zero-volume dummy flows (``n_real`` marks the
    real prefix) so the jitted solver sees shape-stable inputs — the online
    scheduler calls JRBA with a constantly-changing flow count, and without
    padding every call would retrace/retranspile.

    Alongside the dense ``usage`` tensor the program carries the sparse
    formulation: ``link_idx`` (the padded path->link index tensor), the
    active link set, and the active-compressed usage tensor the
    sparse solver actually consumes. Everything except ``capacity``,
    ``volumes`` and ``flows`` depends only on topology + candidate paths, so
    the engine's program cache shares these tensors (and their device
    mirrors in ``dev``) across every re-solve of the same flow set."""

    usage: np.ndarray  # (Nf, K, L) 0/1 — path k of flow i crosses link l
    valid: np.ndarray  # (Nf, K) bool
    volumes: np.ndarray  # (Nf,)
    capacity: np.ndarray  # (L,)
    paths: list[list[list[int]]]  # node paths, paths[i][k]
    flows: list[Flow]
    n_real: int
    link_idx: np.ndarray  # (Nf, K, Pmax) int32; padding slots hold L
    active_links: np.ndarray  # (La,) int32 — links on any candidate path
    usage_active: np.ndarray  # (Nf, K, La_pad) — usage gathered to active slots
    # lazily-populated device mirrors of the solve-invariant tensors above;
    # shared (same dict object) across cache-replayed copies of this program
    dev: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)

    def device(self, name: str) -> jax.Array:
        """Device-resident mirror of a solve-invariant tensor, uploaded once
        per program signature (not once per solve)."""
        arr = self.dev.get(name)
        if arr is None:
            arr = self.dev[name] = jnp.asarray(getattr(self, name))
        return arr

    @property
    def la_pad(self) -> int:
        return self.usage_active.shape[-1]

    def capacity_active(self) -> np.ndarray:
        """Current capacity gathered to the active-link slots (padding slots
        get capacity 1 and zero usage, i.e. exactly zero congestion)."""
        cap = np.ones(self.la_pad, dtype=np.float32)
        cap[: len(self.active_links)] = self.capacity[self.active_links]
        return cap


def build_program(
    net: NetworkGraph,
    flows: list[Flow],
    *,
    k: int = 4,
    capacity: np.ndarray | None = None,
    pad: bool = True,
    pad_to: int | None = None,
    path_cache: dict | None = None,
) -> FlowProgram | None:
    """Enumerate P_i^k and build the (Nf, K, L) usage tensor. Colocated flows
    (src == dst) never reach here — they cost nothing and are dropped by the
    allocator. Returns None when Nf == 0. ``pad_to`` pins the padded row count
    to an exact bucket size (used by the batched engine so instances with
    different flow counts stack into one tensor). ``path_cache`` memoizes
    Yen's enumeration per (src, dst) — sound because candidate paths depend
    only on topology and static bandwidth, not on residual capacity."""
    flows = [f for f in flows if f.src != f.dst and f.volume > 0]
    if not flows:
        return None
    L = len(net.links)
    all_paths: list[list[list[int]]] = []
    for f in flows:
        key = (f.src, f.dst, k)
        ps = None if path_cache is None else path_cache.get(key)
        if ps is None:
            ps = k_shortest_paths(net, f.src, f.dst, k)
            if path_cache is not None:
                path_cache[key] = ps
        all_paths.append(ps)
    n_real = len(flows)
    if pad_to is not None:
        if pad_to < n_real:
            raise ValueError(f"pad_to={pad_to} < {n_real} real flows")
        Nf = pad_to
    else:
        Nf = -(-n_real // 8) * 8 if pad else n_real  # round up to a multiple of 8
    usage = np.zeros((Nf, k, L), dtype=np.float32)
    valid = np.zeros((Nf, k), dtype=bool)
    valid[n_real:, 0] = True  # dummies: one no-op path
    for i, ps in enumerate(all_paths):
        for kk, path in enumerate(ps[:k]):
            valid[i, kk] = True
            for l in path_links(net, path):
                usage[i, kk, l] = 1.0
    volumes = np.zeros((Nf,), dtype=np.float32)
    volumes[:n_real] = [f.volume for f in flows]
    cap = _clamp_capacity(net, capacity)
    # sparse formulation: padded path->link index tensor + active-link
    # compression (see module docstring). La pads to a power of two (capped
    # at L) so the jitted sparse solver sees O(log L) distinct shapes.
    link_idx = path_link_index(net, all_paths, k=k, rows=Nf)
    active = np.unique(link_idx[link_idx < L]).astype(np.int32)
    la = int(active.size)
    la_pad = 8
    while la_pad < la:
        la_pad *= 2
    la_pad = min(la_pad, L)
    usage_active = np.zeros((Nf, k, la_pad), dtype=np.float32)
    usage_active[:, :, :la] = usage[:, :, active]
    return FlowProgram(
        usage=usage,
        valid=valid,
        volumes=volumes,
        capacity=cap,
        paths=all_paths,
        flows=flows,
        n_real=n_real,
        link_idx=link_idx,
        active_links=active,
        usage_active=usage_active,
    )


# ---------------------------------------------------------------------------
# The JAX solver for P3-RELAX-CVX
# ---------------------------------------------------------------------------
def _solve_md_impl(
    usage: jax.Array,  # (Nf, K, L)
    valid: jax.Array,  # (Nf, K)
    volumes: jax.Array,  # (Nf,)
    capacity: jax.Array,  # (L,)
    n_iters: int = 400,
    lr: float = 0.25,
) -> tuple[jax.Array, jax.Array]:
    """Returns (w, relaxed_span): w is the per-flow path distribution, and
    relaxed_span the exact (unsmoothed) congestion max_l load_l/B_l of w."""
    neg_inf = jnp.float32(-1e9)
    mask = jnp.where(valid, 0.0, neg_inf)

    def congestion(w):
        load = jnp.einsum("i,ik,ikl->l", volumes, w, usage)
        return load / capacity

    def smooth_obj(logits, tau):
        w = jax.nn.softmax(logits + mask, axis=-1)
        c = congestion(w)
        return tau * jax.nn.logsumexp(c / tau), c

    taus = jnp.geomspace(1.0, 1e-3, n_iters)

    def step(carry, tau):
        logits, m, v, t = carry
        (obj, _), g = jax.value_and_grad(smooth_obj, has_aux=True)(logits, tau)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (t + 1))
        vh = v / (1 - 0.999 ** (t + 1))
        logits = logits - lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (logits, m, v, t + 1), obj

    z = jnp.zeros_like(mask)
    (logits, _, _, _), _ = jax.lax.scan(step, (z, z, z, 0), taus)
    w = jax.nn.softmax(logits + mask, axis=-1)
    return w, jnp.max(congestion(w))


_solve_md = functools.partial(jax.jit, static_argnames=("n_iters",))(_solve_md_impl)


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _solve_md_batched(
    usage: jax.Array,  # (B, Nf, K, L)
    valid: jax.Array,  # (B, Nf, K)
    volumes: jax.Array,  # (B, Nf)
    capacity: jax.Array,  # (B, L) — per-instance (OTFS solves on residuals)
    n_iters: int = 400,
    lr: float = 0.25,
) -> tuple[jax.Array, jax.Array]:
    """B independent JRBA relaxations in one compiled call (the fleet path)."""
    solve = lambda u, va, vo, c: _solve_md_impl(u, va, vo, c, n_iters, lr)  # noqa: E731
    return jax.vmap(solve)(usage, valid, volumes, capacity)


# ---------------------------------------------------------------------------
# Sparse congestion solver: active-link compression + fused gradient +
# convergence-adaptive chunked schedule
# ---------------------------------------------------------------------------
def probe_schedule(n_iters: int) -> tuple[int, int]:
    """Chunk layout ``(n_chunks, chunk_steps)`` for the adaptive solver: the
    most chunks (<= 16) that divide ``n_iters`` evenly while keeping >= 25
    steps per chunk — the granularity the early-exit criterion was validated
    at. A run that never converges walks every chunk and matches the dense
    schedule step for step (best case: ``(stable_chunks + 1) * chunk_steps``
    steps)."""
    best = 1
    for c in range(1, min(16, n_iters) + 1):
        if n_iters % c == 0 and n_iters // c >= 25:
            best = c
    return best, n_iters // best


def _converged(ci_next, stable, span, prev_span, span_rtol, min_chunks, stable_chunks):
    """Chunk-boundary early-exit criterion, shared by the jnp driver below
    and the Pallas chunk driver in ``kernels.jrba_congestion`` — the two
    backends must agree on when a solve is allowed to stop or they would
    round differently. Elementwise over lanes: converged iff enough chunks
    ran, the argmax rounding was stable for ``stable_chunks`` consecutive
    boundaries, and the exact span plateaued within ``span_rtol``."""
    return jnp.logical_and(
        jnp.logical_and(ci_next >= min_chunks, stable >= stable_chunks),
        jnp.abs(span - prev_span) <= span_rtol * jnp.maximum(span, 1e-12),
    )


@functools.partial(jax.jit, static_argnames=("n_iters", "early_exit"))
def _solve_sparse_batched(
    usage_a: jax.Array,  # (B, Nf, K, La_pad) — usage over active-link slots
    valid: jax.Array,  # (B, Nf, K)
    volumes: jax.Array,  # (B, Nf)
    cap_a: jax.Array,  # (B, La_pad) — capacity on active slots (padding: 1)
    n_outside: jax.Array,  # (B,): L - La_pad inactive links (denominator fold)
    n_iters: int = 400,
    lr: float = 0.25,
    early_exit: bool = True,
    span_rtol: float = 2e-2,
    stable_chunks: int = 2,
    min_chunks: int = 2,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sparse twin of :func:`_solve_md_impl` (B lanes in one compiled call;
    the scalar path is just B == 1). Same Adam-on-logits math, but:

    * congestion lives on the La_pad active-link slots only — each step is
      O(Nf*K*La) instead of O(Nf*K*L), and the L - La_pad zero-congestion
      links enter the softmax denominator as one closed-form scalar
      (``n_outside * exp(-max_c / tau)``), so the objective is exactly the
      dense one;
    * the gradient is hand-fused (softmax-of-congestion gathered back onto
      the usage support) instead of an autodiff tape over the smoothed
      objective;
    * the schedule is convergence-adaptive (see :func:`probe_schedule` and
      :func:`_converged`): a lane exits at a chunk boundary once it has
      *converged in the sense the scheduler consumes it* — the rounding
      ``argmax_k w`` unchanged across ``stable_chunks`` consecutive chunk
      boundaries (a single agreement is not enough: at warm tau ``w`` is
      near-uniform and its argmax is stable-looking noise that a later
      anneal chunk can flip) and the exact (unsmoothed) span plateaued
      within ``span_rtol``. Converged lanes freeze (masked updates), so
      their results match the B == 1 trajectory; the ``lax.while_loop``
      ends when every lane converged or the budget is spent, so the device
      work of a batch is governed by its slowest lane.

    Returns ``(w, exact_span, steps_taken)`` with per-lane step counts.
    """
    B = usage_a.shape[0]
    neg_inf = jnp.float32(-1e9)
    mask = jnp.where(valid, 0.0, neg_inf)

    def congestion(w):  # (B, Nf, K) -> (B, La)
        return jnp.einsum("bi,bik,bikl->bl", volumes, w, usage_a) / cap_a

    pc, ps = probe_schedule(n_iters)
    probe_steps = pc * ps
    taus = jnp.geomspace(1.0, 1e-3, n_iters)
    taus_probe = taus[:probe_steps].reshape(pc, ps)

    def step(carry, tau):
        logits, m, v, t = carry
        w = jax.nn.softmax(logits + mask, axis=-1)
        c = congestion(w)
        maxc = jnp.max(c, axis=-1, keepdims=True)  # (B, 1)
        e = jnp.exp((c - maxc) / tau)
        denom = e.sum(axis=-1, keepdims=True) + n_outside[:, None] * jnp.exp(-maxc / tau)
        # d obj / d load_l = softmax(c/tau)_l / B_l, gathered onto the usage
        # support; then the softmax Jacobian maps it back to logits
        glink = (e / denom) / cap_a
        gw = volumes[:, :, None] * jnp.einsum("bikl,bl->bik", usage_a, glink)
        g = w * (gw - (w * gw).sum(-1, keepdims=True))
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (t + 1))
        vh = v / (1 - 0.999 ** (t + 1))
        logits = logits - lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (logits, m, v, t + 1), None

    z = jnp.zeros_like(mask)

    def chunk(state):
        logits, m, v, ci, span, ks, stable, done, steps = state
        (l2, m2, v2, _), _ = jax.lax.scan(step, (logits, m, v, ci * ps), taus_probe[ci])
        keep = done[:, None, None]
        logits = jnp.where(keep, logits, l2)
        m = jnp.where(keep, m, m2)
        v = jnp.where(keep, v, v2)
        sp = jnp.max(congestion(jax.nn.softmax(logits + mask, axis=-1)), axis=-1)
        new_span = jnp.where(done, span, sp)
        new_ks = jnp.argmax(logits + mask, axis=-1).astype(jnp.int32)
        stable = jnp.where(jnp.all(new_ks == ks, axis=-1), stable + 1, 0)
        steps = jnp.where(done, steps, (ci + 1) * ps)
        if early_exit:
            conv = _converged(ci + 1, stable, new_span, span, span_rtol, min_chunks, stable_chunks)
            done = jnp.logical_or(done, conv)
        return (logits, m, v, ci + 1, new_span, new_ks, stable, done, steps)

    def probing(state):
        return jnp.logical_and(state[3] < pc, jnp.logical_not(jnp.all(state[7])))

    init = (
        z,
        z,
        z,
        0,
        jnp.full((B,), jnp.inf, jnp.float32),
        jnp.full((B, valid.shape[1]), -1, jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.int32),
    )
    logits, _, _, _, span, _, _, done, steps = jax.lax.while_loop(probing, chunk, init)
    steps = jnp.where(done, steps, n_iters)
    return jax.nn.softmax(logits + mask, axis=-1), span, steps


# ---------------------------------------------------------------------------
# The relaxation call: operands staged (what each solver uploads), the solver
# launched, its results waited for on the host — timed and counted the same
# way in every solve function when a caller passes its stats
# ---------------------------------------------------------------------------
class _Relaxing:
    """``with`` block around one relaxation call in a solve function: from
    entry to :meth:`launching` the ``engine/stage`` span (host stacking and
    upload), from there to exit the ``engine/wait`` span (launch to results
    on the host), both on ``track``. On exit ``stats`` (an
    :class:`EngineStats`, or None) receives both times and the backend
    compiles made inside, which an enabled ``tracer`` also marks as one
    ``jax/compile`` instant. The solve functions call the jitted solver from
    their own frame, not through a helper: on a TPU v5 lite, one frame more
    between them and the solver made lowering the kernel 1.6-2.6 s slower
    over the benchmark's 54 warm-up shapes (the Mosaic body keeps the
    callers' locations)."""

    __slots__ = ("_stats", "_tracer", "_track", "_c0", "_t0", "_t1")

    def __init__(self, stats, tracer, track: str) -> None:
        self._stats = stats
        self._tracer = tracer
        self._track = track

    def __enter__(self) -> "_Relaxing":
        self._c0 = compiles.snapshot()
        self._t1 = None
        self._t0 = time.perf_counter()
        self._tracer.begin("engine/stage", track=self._track, cat="engine")
        return self

    def launching(self) -> None:
        self._tracer.end("engine/stage", track=self._track)
        self._t1 = time.perf_counter()
        self._tracer.begin("engine/wait", track=self._track, cat="engine")

    def __exit__(self, *exc) -> bool:
        tracer, track, t1 = self._tracer, self._track, self._t1
        tracer.end("engine/stage" if t1 is None else "engine/wait", track=track)
        stats = self._stats
        if stats is None or t1 is None:
            return False
        stats.stage_seconds += t1 - self._t0
        stats.wait_seconds += time.perf_counter() - t1
        new = compiles.snapshot() - self._c0
        if new.compiles or new.cache_hits:
            stats.backend_compiles += new.compiles
            stats.compile_seconds += new.seconds
            stats.persistent_cache_hits += new.cache_hits
            if tracer.enabled:
                tracer.instant(
                    "jax/compile",
                    track=track,
                    cat="compile",
                    compiles=new.compiles,
                    duration_s=new.seconds,
                )
        return False


def _dense_operands(prog: FlowProgram) -> tuple:
    """One program's dense operands: device-memoized solve-invariant tensors,
    capacity uploaded fresh."""
    return (
        prog.device("usage"),
        prog.device("valid"),
        prog.device("volumes"),
        jnp.asarray(prog.capacity),
    )


def _dense_batch_operands(progs: list[FlowProgram]) -> tuple:
    """Host-side stack + one upload per operand: stacking device-resident
    mirrors costs a dispatch per operand, which for these small tensors is
    slower than the copy (the device memo pays off on the scalar paths)."""
    shapes = {p.usage.shape for p in progs}
    if len(shapes) != 1:
        raise ValueError(f"programs span multiple shape buckets: {sorted(shapes)}")
    return tuple(
        jnp.asarray(np.stack([getattr(p, name) for p in progs]))
        for name in ("usage", "valid", "volumes", "capacity")
    )


def _sparse_operands(prog: FlowProgram) -> tuple:
    """One program's sparse operands as a batch of one: device-memoized
    solve-invariant tensors, only capacity uploaded per solve."""
    cap_a = jnp.asarray(prog.capacity_active())
    n_out = jnp.float32(len(prog.capacity) - prog.la_pad)
    return (
        prog.device("usage_active")[None],
        prog.device("valid")[None],
        prog.device("volumes")[None],
        cap_a[None],
        n_out[None],
    )


def _sparse_batch_operands(progs: list[FlowProgram]) -> tuple:
    """Same-bucket programs' sparse operands, host-stacked and uploaded once
    per operand (see :func:`_dense_batch_operands`)."""
    shapes = {(p.valid.shape, p.la_pad) for p in progs}
    if len(shapes) != 1:
        raise ValueError(f"programs span multiple sparse buckets: {sorted(shapes)}")
    return (
        jnp.asarray(np.stack([p.usage_active for p in progs])),
        jnp.asarray(np.stack([p.valid for p in progs])),
        jnp.asarray(np.stack([p.volumes for p in progs])),
        jnp.asarray(np.stack([p.capacity_active() for p in progs])),
        jnp.asarray(np.array([len(p.capacity) - p.la_pad for p in progs], dtype=np.float32)),
    )


def solve_relaxation(
    prog: FlowProgram,
    *,
    n_iters: int = 400,
    stats: EngineStats | None = None,
    tracer=NULL_TRACER,
    track: str = "engine",
) -> tuple[np.ndarray, float]:
    """Solve P3-RELAX-CVX; returns (m_i^k = V_i w_i^k, relaxed span TH*).
    ``stats``/``tracer``/``track``: see :class:`_Relaxing` (every solve
    function takes them; the engine passes its own)."""
    with _Relaxing(stats, tracer, track) as clock:
        ops = _dense_operands(prog)
        clock.launching()
        w, span = jax.device_get(_solve_md(*ops, n_iters=n_iters))
    m = np.asarray(w) * prog.volumes[:, None]
    return m, float(span)


def _sparse_solver(backend: str, interpret: bool):
    """The batched sparse solver of ``backend``; both take the same operands
    (the active-link usage leads) and return ``(w, span, steps)``."""
    if backend == "pallas":
        from ..kernels.jrba_congestion import sparse_congestion_solve

        return functools.partial(sparse_congestion_solve, interpret=interpret)
    return _solve_sparse_batched


def solve_relaxation_sparse(
    prog: FlowProgram,
    *,
    n_iters: int = 400,
    early_exit: bool = True,
    span_rtol: float = 2e-2,
    stable_chunks: int = 2,
    backend: str = "jnp",
    interpret: bool = False,
    stats: EngineStats | None = None,
    tracer=NULL_TRACER,
    track: str = "engine",
) -> tuple[np.ndarray, float, int]:
    """Sparse solve of one program; returns ``(m, relaxed_span, steps)``.

    ``backend="pallas"`` routes the chunked step loop through the fused
    Pallas kernel (``interpret=True`` for CPU validation); ``"jnp"`` is the
    pure-XLA path. Both consume the program's device-memoized
    solve-invariant tensors — only capacity is uploaded per solve. The
    B == 1 lane of the batched solver IS the scalar path, so scalar and
    batched solves share one compiled structure."""
    with _Relaxing(stats, tracer, track) as clock:
        ops = _sparse_operands(prog)
        clock.launching()
        w, span, steps = jax.device_get(
            _sparse_solver(backend, interpret)(
                *ops,
                n_iters=n_iters,
                early_exit=early_exit,
                span_rtol=span_rtol,
                stable_chunks=stable_chunks,
            )
        )
    m = np.asarray(w[0]) * prog.volumes[:, None]
    return m, float(span[0]), int(steps[0])


def solve_relaxation_sparse_batch(
    progs: list[FlowProgram],
    *,
    n_iters: int = 400,
    early_exit: bool = True,
    span_rtol: float = 2e-2,
    stable_chunks: int = 2,
    backend: str = "jnp",
    interpret: bool = False,
    stats: EngineStats | None = None,
    tracer=NULL_TRACER,
    track: str = "engine",
) -> list[tuple[np.ndarray, float, int]]:
    """Sparse twin of :func:`solve_relaxation_batch`; one vmapped (or
    Pallas-gridded) dispatch for N same-shape programs, one device sync for
    all results. Programs must share the (Nf, K, La_pad) bucket."""
    with _Relaxing(stats, tracer, track) as clock:
        ops = _sparse_batch_operands(progs)
        clock.launching()
        w, spans, steps = jax.device_get(
            _sparse_solver(backend, interpret)(
                *ops,
                n_iters=n_iters,
                early_exit=early_exit,
                span_rtol=span_rtol,
                stable_chunks=stable_chunks,
            )
        )
    return [
        (np.asarray(w[i]) * p.volumes[:, None], float(spans[i]), int(steps[i]))
        for i, p in enumerate(progs)
    ]


def solve_relaxation_batch(
    progs: list[FlowProgram],
    *,
    n_iters: int = 400,
    stats: EngineStats | None = None,
    tracer=NULL_TRACER,
    track: str = "engine",
) -> list[tuple[np.ndarray, float]]:
    """Solve N same-shape programs in one vmapped call.

    All programs must already be padded to a common (Nf, K, L) bucket (the
    engine guarantees this); raises on shape mismatch rather than silently
    re-padding, so callers control bucketing policy."""
    with _Relaxing(stats, tracer, track) as clock:
        ops = _dense_batch_operands(progs)
        clock.launching()
        w, spans = jax.device_get(_solve_md_batched(*ops, n_iters=n_iters))
    return [
        (np.asarray(w[i]) * p.volumes[:, None], float(spans[i]))
        for i, p in enumerate(progs)
    ]


# ---------------------------------------------------------------------------
# Rounding + Eq. 15 + (beyond-paper) water-filling
# ---------------------------------------------------------------------------
def _eq15_bandwidth(sel_usage: np.ndarray, volumes: np.ndarray, capacity: np.ndarray) -> np.ndarray:
    """Paper Eq. 15: on each link, capacity splits across crossing flows in
    proportion to volume; a flow gets the min share along its route.
    Vectorized masked min (it runs on every finalize): flows crossing no
    link get an infinite share, matching the per-flow loop it replaced."""
    crossing = sel_usage.T @ volumes  # (L,) total volume through each link
    with np.errstate(divide="ignore", invalid="ignore"):
        share = np.where(crossing > 0, capacity / crossing, np.inf)  # (L,) per-unit-volume
    row_share = np.where(sel_usage > 0, share[None, :], np.inf).min(axis=1, initial=np.inf)
    return (volumes * row_share).astype(np.float64)


def water_fill(
    sel_usage: np.ndarray, volumes: np.ndarray, capacity: np.ndarray
) -> np.ndarray:
    """Weighted (by V_i) max-min progressive filling on fixed routes.

    Level 1 equals Eq. 15 at the global bottleneck (so the paper-faithful
    span is preserved); later levels lift flows Eq. 15 leaves stranded,
    which raises *per-job* throughput in multi-job rounds (OTFA+WF)."""
    Nf = len(volumes)
    rate = np.zeros(Nf)
    frozen = np.zeros(Nf, dtype=bool)
    residual = capacity.astype(np.float64).copy()
    for _ in range(Nf + 1):
        if frozen.all():
            break
        active_vol = sel_usage.T @ (volumes * ~frozen)  # (L,)
        # links carrying at least one active flow constrain the increment
        constrained = active_vol > 1e-12
        if not constrained.any():
            break
        theta = np.min(residual[constrained] / active_vol[constrained])
        theta = max(theta, 0.0)
        rate[~frozen] += theta * volumes[~frozen]
        residual -= theta * active_vol
        saturated = constrained & (residual <= 1e-9 * np.maximum(capacity, 1e-12))
        hit = (sel_usage[:, saturated].sum(axis=1) > 0) & ~frozen
        if not hit.any():  # numerical guard
            break
        frozen |= hit
    return rate


@dataclasses.dataclass
class JRBAResult:
    routes: list[list[int]]  # chosen node path per flow
    bandwidth: np.ndarray  # b_i per flow
    span: float  # exact max_i V_i / b_i under the rounded solution
    relaxed_span: float  # LP lower-bound certificate (TH of the relaxation)
    flows: list[Flow]
    link_load: np.ndarray  # consumed bandwidth per link
    # links on ANY candidate path of ANY real flow — the solver's output is a
    # function of capacity on exactly these links (zero-usage links contribute
    # exact zeros to the congestion vector), so speculative intra-round
    # batching can accept a stale solve whenever the residual is unchanged on
    # this mask (see OnlineScheduler's repair pass)
    candidate_links: np.ndarray | None = None
    # relaxation steps this program's own lane ran before its early exit
    # (the full budget without early exit; 0 on the analytic single-flow
    # path, which runs no relaxation)
    relax_steps: int = 0

    @property
    def throughput_bound(self) -> float:
        return 1.0 / self.span if self.span > 0 else float("inf")


def link_load_fits(
    link_load: np.ndarray, residual: np.ndarray, *, rel_eps: float = 1e-9
) -> bool:
    """Overcommit detector: does ``link_load`` fit within ``residual`` on every
    link? The speculative OTFS repair pass runs this before committing an
    accepted solve, so a bad speculation can never oversubscribe a link; tests
    craft deliberate two-job conflicts against it."""
    slack = rel_eps * np.maximum(np.abs(residual), 1.0)
    return bool(np.all(link_load <= residual + slack))


_SWEEPS = 5  # best-response passes a chain may run before it is cut


@dataclasses.dataclass
class _Stack:
    """Same-shape programs stacked for one batched rounding: ``vu`` is each
    program's ``usage * volumes`` (the product every sweep step reads),
    ``order`` each program's sweep order and ``reach`` one past the last
    position of that order holding a real flow (padding dummies have zero
    volume and one valid path, so the positions after it are no-ops)."""

    progs: list[FlowProgram]
    vu: np.ndarray  # (P, Nf, K, L)
    valid: np.ndarray  # (P, Nf, K)
    capacity: np.ndarray  # (P, L)
    order: np.ndarray  # (P, Nf)
    reach: np.ndarray  # (P,)

    @classmethod
    def of(cls, progs: list[FlowProgram]) -> "_Stack":
        vols = np.stack([p.volumes for p in progs])
        order = np.stack([np.argsort(-p.volumes) for p in progs])
        return cls(
            progs=progs,
            vu=np.stack([p.usage for p in progs]) * vols[:, :, None, None],
            valid=np.stack([p.valid for p in progs]),
            capacity=np.stack([p.capacity for p in progs]),
            order=order,
            reach=_reach(order, progs),
        )


def _reach(order: np.ndarray, progs: list[FlowProgram]) -> np.ndarray:
    """One past the last position of each row of ``order`` that holds a real
    flow (a real flow whose float32 volume underflowed to 0 may sort among
    the dummies, so this is not always ``n_real``)."""
    real = order < np.array([p.n_real for p in progs])[:, None]
    return np.where(real.any(axis=1), order.shape[1] - np.argmax(real[:, ::-1], axis=1), 0)


def _greedy_starts(st: _Stack) -> np.ndarray:
    """Deterministic sequential rounding start of every program in ``st``:
    flows in volume-descending order (stable sort — deterministic on ties)
    each take the path that minimizes the resulting link congestion given
    the flows already placed. A pure function of the program — no solver
    output involved — so every solver formulation derives the identical
    start from the same program. Step ``j`` places the ``j``-th flow of
    every program at once, with the per-program arithmetic unchanged."""
    P, Nf, K, L = st.vu.shape
    order = np.stack([np.argsort(-p.volumes, kind="stable") for p in st.progs])
    reach = _reach(order, st.progs)
    by_reach = np.argsort(-reach, kind="stable")  # each step's programs: a prefix
    reach = reach[by_reach]
    ks = np.zeros((P, Nf), dtype=np.int64)
    load = np.zeros((P, L))
    for j in range(int(reach[0])):
        n = int(np.count_nonzero(reach > j))
        p = by_reach[:n]
        i = order[p, j]
        v = st.vu[p, i]  # (n, K, L)
        cong = np.max((load[:n, None, :] + v) / st.capacity[p][:, None, :], axis=2)
        k = np.argmin(np.where(st.valid[p, i], cong, np.inf), axis=1)
        ks[p, i] = k
        load[:n] = load[:n] + v[np.arange(n), k]
    return ks


def _sweep_chains(
    st: _Stack, owner: np.ndarray, starts: np.ndarray, sweeps: int = _SWEEPS
) -> tuple[np.ndarray, np.ndarray]:
    """Vertex-recovery refinement after argmax rounding, for many chains.

    The paper rounds ``k* = argmax_k m_i^k`` from a *simplex* LP solution,
    which sits on a vertex (near-integral y). Our mirror-descent solver
    converges to interior points of the optimal face, where argmax can pick a
    congested path (e.g. it loses Fig. 2(f)). Best-response sweeps — each
    flow re-picks the path minimizing the resulting congestion with the
    others fixed — monotonically reduce the span and recover vertex quality.

    Chain ``c`` starts from its own copy of ``starts[c]`` on program
    ``owner[c]`` of ``st``. Step ``j`` of a sweep re-picks the ``j``-th flow
    of every live chain (each in its own program's order) with exactly the
    arithmetic of a chain swept alone; a chain freezes after a sweep that
    changed nothing, or after ``sweeps`` sweeps. Returns the chains' routes
    and the sweeps each ran."""
    C, Nf = starts.shape
    ks = starts.copy()
    done = np.zeros(C, dtype=np.int64)
    # chains in order of their reach, so each step's live chains are a prefix
    idx = np.argsort(-st.reach[owner], kind="stable")
    reach = st.reach[owner[idx]]
    pos = st.order[owner[idx]]  # (C, Nf) flow at each position
    vu = st.vu[owner[idx, None], pos]  # (C, Nf, K, L) in sweep order
    valid = st.valid[owner[idx, None], pos]
    cap = st.capacity[owner[idx]][:, None, :]
    kso = np.take_along_axis(ks[idx], pos, axis=1)
    load = np.stack(
        [
            st.progs[p].usage[np.arange(Nf), ks[c]].T @ st.progs[p].volumes
            for c, p in zip(idx, owner[idx])
        ]
    )
    for sweep in range(1, sweeps + 1):
        changed = np.zeros(len(idx), dtype=bool)
        rows = np.arange(len(idx))
        for j in range(int(reach[0])):
            n = int(np.count_nonzero(reach > j))
            v = vu[:n, j]  # (n, K, L)
            old = kso[:n, j]
            base = load[:n] - v[rows[:n], old]
            cong = np.max((base[:, None, :] + v) / cap[:n], axis=2)
            new = np.argmin(np.where(valid[:n, j], cong, np.inf), axis=1)
            changed[:n] |= new != old
            kso[:n, j] = new
            load[:n] = base + v[rows[:n], new]
        done[idx] = sweep
        ends = np.empty_like(kso)
        np.put_along_axis(ends, pos, kso, axis=1)
        ks[idx] = ends
        if sweep == sweeps or not changed.any():
            break
        # a chain whose sweep changed nothing stops (no further load
        # updates: ``load - a + a`` can move the last bit)
        idx, reach, pos, vu, valid, cap, kso, load = (
            a[changed] for a in (idx, reach, pos, vu, valid, cap, kso, load)
        )
    return ks, done


def _rounding_span(prog: FlowProgram, ks: np.ndarray) -> float:
    """Exact congestion span of a rounded route choice (the quantity the
    refinement minimizes) — pure numpy on program tensors, so identical
    across solver formulations."""
    Nf = prog.usage.shape[0]
    sel = prog.usage[np.arange(Nf), ks]
    return float(np.max((sel.T @ prog.volumes) / prog.capacity))


def _round_group(
    progs: list[FlowProgram], ms: list[np.ndarray], counts=None, *, sweeps: int = _SWEEPS
) -> list[np.ndarray]:
    """Solver-robust rounding: best-response sweeps from a deterministic
    portfolio of starts, with the relaxation's argmax start consulted last,
    for programs of one ``usage`` shape — every chain of every program in
    one batched sweep (:func:`_sweep_chains`).

    On symmetric programs — a job's parallel flows between one node pair,
    the common shape in scheduler streams — the relaxed optimum splits each
    flow near-uniformly across its candidate paths, so per-flow
    ``argmax_k m_i^k`` is numerical noise: two numerically different solver
    trajectories (dense vs sparse, scalar vs vmapped) land on different
    all-same-path vertices and the sweeps repair them into *different* local
    optima. The portfolio makes rounding start-independent exactly there:
    sweep from the greedy sequential start and from every uniform all-k
    start (both pure functions of the program; a start equal to an earlier
    start is swept once), keep the first best, and let the argmax start —
    swept only when it equals none of them — win only when *strictly*
    better. Any all-same-path argmax vertex is already in the portfolio, so
    in the degenerate regime every formulation returns the identical (and
    never worse) solution — the property the churn benchmark asserts as
    zero record deviation.

    ``counts`` (an :class:`EngineStats`) receives one batch, the chains
    swept, their sweeps, and a relaxation-start win for each program whose
    argmax start's chain is the one returned."""
    st = _Stack.of(progs)
    P, Nf, K, _ = st.vu.shape
    starts = np.empty((P, K + 2, Nf), dtype=np.int64)
    starts[:, 0] = _greedy_starts(st)
    first_valid = np.argmax(st.valid, axis=2)
    starts[:, 1 : K + 1] = np.where(
        st.valid.transpose(0, 2, 1), np.arange(K)[None, :, None], first_valid[:, None, :]
    )
    starts[:, K + 1] = np.argmax(np.where(st.valid, np.stack(ms), -1.0), axis=2)
    # starts compared with starts: keep each that equals no earlier one
    same = (starts[:, :, None, :] == starts[:, None, :, :]).all(axis=3)
    keep = ~np.tril(same, -1).any(axis=2)
    owner, slot = np.nonzero(keep)
    ends, done = _sweep_chains(st, owner, starts[owner, slot], sweeps)
    if counts is not None:
        counts.refine_batches += 1
        counts.refine_chains += len(owner)
        counts.refine_sweeps += int(done.sum())
    out = []
    for p, prog in enumerate(progs):
        chains = np.flatnonzero(owner == p)
        portfolio = chains[slot[chains] <= K]
        spans = [_rounding_span(prog, ends[c]) for c in portfolio]
        best = portfolio[int(np.argmin(spans))]
        ks = ends[best]
        if keep[p, K + 1] and _rounding_span(prog, ends[chains[-1]]) < min(spans):
            if counts is not None:
                counts.relax_start_wins += 1
            ks = ends[chains[-1]]
        out.append(ks)
    return out


def _round_and_refine(
    prog: FlowProgram, m: np.ndarray, counts=None, *, sweeps: int = _SWEEPS
) -> np.ndarray:
    """:func:`_round_group` of one program."""
    return _round_group([prog], [m], counts, sweeps=sweeps)[0]


def _fast_start(prog: FlowProgram) -> np.ndarray:
    """Stand-in relaxation of a single-flow program: every valid path of a
    flow weighted by its volume, so the argmax start is the first valid
    path (the engine's analytic fast path, ``JRBAEngine._use_fast_path``)."""
    return np.where(prog.valid, prog.volumes[:, None], -1.0)


def _finalize(
    prog: FlowProgram,
    m: np.ndarray,
    relaxed: float,
    *,
    water_filling: bool = False,
    refine: bool = True,
    ks: np.ndarray | None = None,
) -> JRBAResult:
    """Rounding (k* = argmax), vertex-recovery refinement, Eq. 15 bandwidth
    recovery and the optional water-filling top-up — the host-side half of
    Algorithm 2, shared by the single and batched solve paths. With
    ``refine`` the rounding runs through the start-portfolio refinement
    (:func:`_round_and_refine`), which is deterministic across solver
    formulations on degenerate symmetric programs; ``ks`` passes in a
    rounding the caller already made."""
    if ks is None and refine:
        ks = _round_and_refine(prog, m)
    elif ks is None:
        ks = np.argmax(np.where(prog.valid, m, -1.0), axis=1)  # k* = argmax_k m_i^k
    n = prog.n_real  # drop shape-padding dummies
    sel_usage = prog.usage[np.arange(n), ks[:n]]  # (n_real, L)
    vols = prog.volumes[:n]
    b = _eq15_bandwidth(sel_usage, vols, prog.capacity)
    if water_filling:
        b = np.maximum(b, water_fill(sel_usage, vols, prog.capacity))
    # a real flow with no candidate path (its endpoints are partitioned by
    # link/node failures) has an all-zero usage row, which Eq. 15 would read
    # as "crosses no link" and award infinite bandwidth; it is unroutable, so
    # it gets zero bandwidth and drives the span infinite until the network
    # heals and the scheduler re-solves
    has_path = prog.valid[:n].any(axis=1)
    b = np.where(has_path, b, 0.0)
    with np.errstate(divide="ignore"):
        span = float(np.max(np.where(b > 0, vols / b, np.inf)))
    routes = [prog.paths[i][int(ks[i])] if has_path[i] else [] for i in range(n)]
    link_load = sel_usage.T @ b
    return JRBAResult(
        routes=routes,
        bandwidth=b,
        span=span,
        relaxed_span=relaxed,
        flows=prog.flows,
        link_load=link_load,
        candidate_links=(prog.usage > 0).any(axis=(0, 1)),
    )


def jrba(
    net: NetworkGraph,
    flows: list[Flow],
    *,
    k: int = 4,
    capacity: np.ndarray | None = None,
    n_iters: int = 400,
    water_filling: bool = False,
    refine: bool = True,
    solver: str = "auto",
) -> JRBAResult | None:
    """Algorithm 2. ``capacity`` overrides link capacity (the online scheduler
    passes residual capacity for OTFS and full capacity for OTFA re-runs).
    ``solver`` follows the engine's backend resolution; pass ``"dense"`` for
    the byte-stable reference formulation."""
    prog = build_program(net, flows, k=k, capacity=capacity)
    if prog is None:
        return None
    solver = resolve_solver(solver)
    if solver == "dense":
        m, relaxed = solve_relaxation(prog, n_iters=n_iters)
    else:
        m, relaxed, _ = solve_relaxation_sparse(
            prog,
            n_iters=n_iters,
            backend="pallas" if solver.startswith("pallas") else "jnp",
            interpret=solver == "pallas-interpret",
        )
    return _finalize(prog, m, relaxed, water_filling=water_filling, refine=refine)


# ---------------------------------------------------------------------------
# Fleet engine: shape-bucketed compilation cache + batched solves
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EngineStats:
    """Observability for the solver cache (`hits`/`misses` count shape-bucket
    signatures the engine keys: a miss normally means an XLA trace+compile,
    which ``backend_compiles`` counts whatever the shape key), the
    convergence-adaptive sparse solver (per-lane semantic steps vs the fixed
    budget the dense schedule would have burned — a lockstep batch's device
    work is governed by its slowest live lane, and batch-padding lanes are
    excluded; ``fast_path_solves`` are single-flow programs rounded
    host-side with no relaxation at all), and the work inside each phase.
    Every field is a number, so two snapshots subtract."""

    single_solves: int = 0
    batched_solves: int = 0  # compiled batch calls
    batched_instances: int = 0  # programs solved through batch calls
    cache_hits: int = 0
    cache_misses: int = 0
    solve_seconds: float = 0.0
    # phase split of the engine's wall-clock. ``solve_seconds`` keeps its
    # historical meaning (relaxation dispatch + analytic fast-path time, the
    # quantity every benchmark baseline records); the phases decompose where
    # an engine call actually spends: host program build (path enumeration +
    # tensor assembly), program-cache hit replay, device relaxation dispatch,
    # and host rounding/refine/Eq. 15. Identity: solve_seconds ==
    # dispatch_seconds + (the fast-path share of finalize_seconds; a
    # solve_many call rounds its programs together, so there the share is
    # its finalize time split by program count).
    build_seconds: float = 0.0  # build_program: path enum + program tensors
    cache_seconds: float = 0.0  # program-cache hits: capacity-only replay
    dispatch_seconds: float = 0.0  # jitted relaxation calls (device dispatch)
    finalize_seconds: float = 0.0  # host rounding / refine / water-filling
    # inside the phases above, each measured around its own work (and drawn
    # as the engine/* span of the same name): operand stacking + upload and
    # the launch-to-device_get wait split dispatch; the start portfolios'
    # batched sweeps (_round_group) sit inside finalize; Yen's enumeration on path-cache
    # misses sits inside build, or in candidate_links outside every phase
    stage_seconds: float = 0.0
    wait_seconds: float = 0.0
    refine_seconds: float = 0.0
    refined_programs: int = 0  # programs rounded through the start portfolio
    refine_batches: int = 0  # batched sweeps: one per program shape a call rounds
    refine_chains: int = 0  # best-response chains swept
    refine_sweeps: int = 0  # best-response passes over a program's flows
    relax_start_wins: int = 0  # the relaxation's argmax start strictly won
    paths_seconds: float = 0.0
    paths_computed: int = 0  # (src, dst) pairs enumerated by Yen's algorithm
    # backend compiles made during this engine's relaxation calls, where all
    # of its JAX work runs (jax.monitoring via repro.obs.compiles, so shapes
    # outside the engine's key model count too), their seconds, and how many
    # of them the persistent cache answered
    backend_compiles: int = 0
    compile_seconds: float = 0.0
    persistent_cache_hits: int = 0
    solver_steps: int = 0  # relaxation steps actually run (early exit counted)
    solver_step_budget: int = 0  # n_iters * relaxation solves (the dense cost)
    fast_path_solves: int = 0  # single-flow programs solved analytically
    prog_cache_hits: int = 0  # program-tensor cache: no rebuild, no re-upload
    prog_cache_misses: int = 0
    # invalidation traffic (see JRBAEngine.invalidate): full drops vs
    # footprint-scoped prunes, and how many cached entries each scoped call
    # kept alive vs evicted — the churn-resilience observable
    invalidations_full: int = 0
    invalidations_scoped: int = 0
    progs_pruned: int = 0  # program-cache entries evicted by scoped calls
    progs_kept: int = 0  # program-cache entries a scoped call left valid
    paths_pruned: int = 0  # path-cache entries evicted by scoped calls

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class JRBAEngine:
    """Cached, batched JRBA solver for fleet-scale scheduling.

    Two ideas:

    * **Shape buckets** — flow programs are padded so Nf lands on a power-of
      -two bucket (min 8). The jitted solver then sees O(log N) distinct
      shapes instead of one per flow count, so online re-scheduling stops
      paying per-event trace/compile cost after warm-up.
    * **Batched solves** — ``solve_many`` stacks same-bucket programs into a
      (B, Nf, K, L) tensor and runs one vmapped+jitted relaxation for all of
      them; per-instance rounding/Eq. 15 stays on host. N independent
      instances (a fleet of jobs, or OTFS solves across simulations) cost one
      dispatch instead of N.

    The engine is deliberately topology-agnostic: programs built on different
    networks (different L) simply land in different buckets.

    ``solver`` picks the relaxation formulation (see module docstring):
    ``"auto"`` resolves via :func:`resolve_solver` (``REPRO_JRBA_SOLVER``
    env override, then Pallas on TPU / sparse-jnp elsewhere); ``"dense"``
    forces the byte-stable reference. Sparse modes additionally take the
    analytic fast path for single-flow programs — the best-response sweep
    finds the global min-congestion path from any start when there is only
    one flow, so the rounded result provably equals the dense pipeline's
    with zero relaxation steps.

    A per-network **program cache** (keyed by the kept flows' (src, dst,
    volume) signature and shape bucket) replays the solve-invariant tensors
    — dense/sparse usage, index tensors, candidate paths, and their device
    mirrors — so the OTFS re-solve loop (same job, shrinking residual)
    rebuilds nothing and re-uploads only the capacity vector.
    """

    def __init__(
        self,
        *,
        k: int = 4,
        n_iters: int = 400,
        min_bucket: int = 8,
        solver: str = "auto",
        early_exit: bool = True,
        span_rtol: float = 2e-2,
        stable_chunks: int = 2,
        prog_cache_size: int = 256,
    ) -> None:
        self.k = k
        self.n_iters = n_iters
        self.min_bucket = min_bucket
        self.solver = resolve_solver(solver)
        self.early_exit = early_exit
        self.span_rtol = span_rtol
        self.stable_chunks = stable_chunks
        self.prog_cache_size = prog_cache_size
        self.stats = EngineStats()
        # observability: the fleet runtime points this at its Tracer so
        # engine dispatches land on one shared "engine" timeline track
        # (every lane's solves funnel through the same engine); the default
        # null tracer keeps the solve paths branch-cheap
        self.tracer = NULL_TRACER
        self.trace_track = "engine"
        self._seen_shapes: set[tuple] = set()
        # per-network (src, dst, k) -> candidate paths; weak keys so dropping
        # a topology frees its cache
        self._paths: "weakref.WeakKeyDictionary[NetworkGraph, dict]" = (
            weakref.WeakKeyDictionary()
        )
        # per-network LRU of solve-invariant program tensors
        self._progs: "weakref.WeakKeyDictionary[NetworkGraph, collections.OrderedDict]" = (
            weakref.WeakKeyDictionary()
        )
        # topology epoch each net's caches were built in (see _check_topology)
        self._topo_seen: "weakref.WeakKeyDictionary[NetworkGraph, int]" = (
            weakref.WeakKeyDictionary()
        )
        # per-program relaxation steps of the last relaxation this engine ran
        self._steps: list[int] = []
        compiles.listen()

    def bucket(self, n_real: int) -> int:
        """Smallest power-of-two bucket (>= min_bucket) holding n_real rows."""
        b = self.min_bucket
        while b < n_real:
            b *= 2
        return b

    def _note_shape(self, key: tuple) -> None:
        if key in self._seen_shapes:
            self.stats.cache_hits += 1
        else:
            self._seen_shapes.add(key)
            self.stats.cache_misses += 1

    def bucket_key(self, net: NetworkGraph, flows: list[Flow]) -> tuple:
        """Cheap dispatch-grouping key for a (net, flows) pair — the key the
        async fleet dispatcher queues :class:`~repro.core.SolveRequest`s
        under, computed WITHOUT enumerating paths or building the program
        (both of which ``build`` pays exactly once at solve time).

        For the dense solver the key — ``(Nf bucket, k, L)`` — is exactly the
        compiled-shape signature, so one queued bucket is one vmapped call.
        Sparse/Pallas signatures additionally depend on the active-link
        compression (``La_pad``), which only the built program
        knows; there the key is a *proxy* — programs sharing it usually share
        a compiled shape, and ``solve_many`` re-buckets exactly inside the
        dispatch, so a mixed bucket costs extra compiled calls, never a wrong
        result. Empty programs (colocated-only / zero-volume flows) collapse
        to ``("empty",)``: they never reach the solver and any driver can
        answer them in any grouping."""
        kept = sum(1 for f in flows if f.src != f.dst and f.volume > 0)
        if not kept:
            return ("empty",)
        return (self.bucket(kept), self.k, len(net.links))

    def _shape_key(self, prog: FlowProgram) -> tuple:
        """Compiled-signature key of one program under the active solver.
        Sparse solves never see L, so instances from different topologies
        share a signature whenever their active-compressed shapes agree."""
        if self.solver == "dense":
            return prog.usage.shape
        return ("sp", *prog.valid.shape, prog.la_pad)

    def build(
        self,
        net: NetworkGraph,
        flows: list[Flow],
        *,
        capacity: np.ndarray | None = None,
    ) -> FlowProgram | None:
        with self.tracer.span("engine/build", track=self.trace_track, cat="engine"):
            return self._build(net, flows, capacity)

    def _build(
        self, net: NetworkGraph, flows: list[Flow], capacity: np.ndarray | None
    ) -> FlowProgram | None:
        # mirror build_program's flow filter so the bucket is known up front
        # and the program is built exactly once
        t0 = time.perf_counter()
        self._check_topology(net)
        kept = [f for f in flows if f.src != f.dst and f.volume > 0]
        if not kept:
            return None
        bucket = self.bucket(len(kept))
        progs = self._progs.get(net)
        if progs is None:
            progs = self._progs.setdefault(net, collections.OrderedDict())
        key = (tuple((f.src, f.dst, f.volume) for f in kept), bucket)
        ent = progs.get(key)
        if ent is not None:
            progs.move_to_end(key)
            self.stats.prog_cache_hits += 1
            cap = _clamp_capacity(net, capacity)
            # share every solve-invariant tensor (and the device-mirror dict)
            # with the cached program; only capacity and the caller's Flow
            # objects are fresh
            out = dataclasses.replace(ent, capacity=cap, flows=kept)
            self.stats.cache_seconds += time.perf_counter() - t0
            return out
        paths = self._path_cache(net)
        for f in kept:
            self._candidate_paths(net, paths, f.src, f.dst)
        prog = build_program(
            net,
            flows,
            k=self.k,
            capacity=capacity,
            pad_to=bucket,
            path_cache=paths,
        )
        self.stats.prog_cache_misses += 1
        progs[key] = prog
        while len(progs) > self.prog_cache_size:
            progs.popitem(last=False)
        self.stats.build_seconds += time.perf_counter() - t0
        return prog

    def invalidate(self, net: NetworkGraph, links: np.ndarray | None = None) -> None:
        """The one invalidation surface for ``net``'s per-network caches
        (candidate paths and solve-invariant program tensors).

        ``links=None`` — **full topology invalidation**: drop everything.
        Required when the adjacency *gained* links (a recovery can create a
        shorter path between any pair, so no cached enumeration is provably
        still the top-k) and after ``restore_topology`` (drift-era caches
        tie-break on live bandwidth and are not the pristine-network ones).

        ``links=<bool mask over link ids>`` — **footprint-scoped
        invalidation**: drop only cache entries whose recorded link footprint
        intersects the mask. Sound for link *failures* and capacity changes:
        removing (or drifting) a link that lies on none of an entry's
        candidate paths cannot change Yen's top-k for that entry — deletion
        only removes longer paths, and costs of the surviving paths are
        untouched — so the cached paths, the program's usage/index tensors,
        and its device mirrors all stay valid; the program-cache hit path
        refreshes capacity on every build anyway. Path-cache entries record
        their footprint as the union of their paths' links; cached programs
        record theirs as ``active_links``.

        Pure capacity drift needs no call at all (the hit path re-reads
        capacity); the online scheduler calls ``invalidate(net, touched)``
        for failure-only churn steps and ``invalidate(net)`` when a step
        recovered links.

        Either form syncs the engine's topology epoch for ``net``. Every
        cache access still self-checks ``net.topology_version``
        (:meth:`_check_topology`), so a missed explicit call degrades to a
        lazy *full* invalidation rather than a stale solve."""
        if links is None:
            self._paths.pop(net, None)
            self._progs.pop(net, None)
            self.stats.invalidations_full += 1
            self._topo_seen[net] = net.topology_version
            return
        mask = np.asarray(links, dtype=bool)
        self.stats.invalidations_scoped += 1
        if mask.any():
            paths = self._paths.get(net)
            if paths:
                stale = [
                    key
                    for key, ps in paths.items()
                    if any(mask[l] for p in ps for l in path_links(net, p))
                ]
                for key in stale:
                    del paths[key]
                self.stats.paths_pruned += len(stale)
            progs = self._progs.get(net)
            if progs:
                stale = [
                    key for key, ent in progs.items() if mask[ent.active_links].any()
                ]
                for key in stale:
                    del progs[key]
                self.stats.progs_pruned += len(stale)
                self.stats.progs_kept += len(progs)
        self._topo_seen[net] = net.topology_version

    def _check_topology(self, net: NetworkGraph) -> None:
        """Lazy safety net behind :meth:`invalidate`: drop caches whose
        topology epoch is stale (a full drop — the touched-link mask is
        unknown by the time the staleness is noticed)."""
        seen = self._topo_seen.get(net)
        if seen is None:
            self._topo_seen[net] = net.topology_version
        elif seen != net.topology_version:
            self.invalidate(net)

    def candidate_links(self, net: NetworkGraph, flows: list[Flow]) -> np.ndarray:
        """Bool mask over links of every candidate path of ``flows`` — the
        footprint a JRBA solve of them could touch (and the only capacity
        entries its output depends on). Served from the per-net path cache, so
        after warm-up this is a cheap host-side lookup; the speculative OTFS
        repair pass uses it to decide which queued speculations an admission
        can invalidate."""
        self._check_topology(net)
        cache = self._path_cache(net)
        mask = np.zeros(len(net.links), dtype=bool)
        for f in flows:
            if f.src == f.dst or f.volume <= 0:
                continue
            for path in self._candidate_paths(net, cache, f.src, f.dst):
                mask[path_links(net, path)] = True
        return mask

    def _path_cache(self, net: NetworkGraph) -> dict:
        cache = self._paths.get(net)
        if cache is None:
            cache = self._paths.setdefault(net, {})
        return cache

    def _candidate_paths(self, net: NetworkGraph, cache: dict, src: int, dst: int) -> list:
        """The k candidate paths of ``src -> dst`` from ``net``'s path cache,
        enumerated (and timed) on a miss."""
        key = (src, dst, self.k)
        ps = cache.get(key)
        if ps is None:
            t0 = time.perf_counter()
            with self.tracer.span("engine/paths", track=self.trace_track, cat="engine"):
                ps = cache[key] = k_shortest_paths(net, src, dst, self.k)
            self.stats.paths_seconds += time.perf_counter() - t0
            self.stats.paths_computed += 1
        return ps

    def _use_fast_path(self, prog: FlowProgram, refine: bool) -> bool:
        """Analytic single-flow solve: with one flow the best-response sweep
        picks the globally min-congestion candidate path from any starting
        ``k`` (first argmin on ties), which is exactly where the dense
        argmax-round-then-refine pipeline lands — so skip the relaxation and
        round from :func:`_fast_start`. The span certificate equals the
        rounded span (the LP could split traffic lower; nothing downstream
        consumes the certificate)."""
        return self.solver != "dense" and refine and prog.n_real == 1

    def _solver_options(self) -> dict:
        """The engine's solver settings and observers, as the solve
        functions take them."""
        opts = {
            "n_iters": self.n_iters,
            "stats": self.stats,
            "tracer": self.tracer,
            "track": self.trace_track,
        }
        if self.solver != "dense":
            opts.update(
                early_exit=self.early_exit,
                span_rtol=self.span_rtol,
                stable_chunks=self.stable_chunks,
                backend="pallas" if self.solver.startswith("pallas") else "jnp",
                interpret=self.solver == "pallas-interpret",
            )
        return opts

    def _relax_one(self, prog: FlowProgram) -> tuple[np.ndarray, float]:
        """Solver-mode dispatch for one program (stats included)."""
        if self.solver == "dense":
            m, relaxed = solve_relaxation(prog, **self._solver_options())
            steps = self.n_iters
        else:
            m, relaxed, steps = solve_relaxation_sparse(prog, **self._solver_options())
        self._steps = [steps]
        self.stats.solver_steps += steps
        self.stats.solver_step_budget += self.n_iters
        return m, relaxed

    def _relax_group(
        self, progs: list[FlowProgram], n_real: int | None = None
    ) -> list[tuple[np.ndarray, float]]:
        """One compiled call for a same-shape group (stats included).
        ``n_real`` excludes batch-dimension padding lanes (repeats of the
        last program) from the step counters; note the per-lane step counts
        are the *semantic* early-exit points — a lockstep batch's device
        work is governed by its slowest live lane. Each program's own step
        count is left in ``_steps``."""
        n_real = len(progs) if n_real is None else n_real
        if self.solver == "dense":
            solved = solve_relaxation_batch(progs, **self._solver_options())
            self._steps = [self.n_iters] * len(progs)
        else:
            solved3 = solve_relaxation_sparse_batch(progs, **self._solver_options())
            solved = [(m, relaxed) for m, relaxed, _ in solved3]
            self._steps = [steps for _, _, steps in solved3]
        self.stats.solver_steps += sum(self._steps[:n_real])
        self.stats.solver_step_budget += self.n_iters * n_real
        return solved

    def _round(self, progs: list[FlowProgram], ms: list[np.ndarray]) -> list[np.ndarray]:
        """The start portfolio of every program: one batched sweep (and one
        ``engine/refine`` span) per ``usage`` shape, timed and counted.
        Rounding reads no active-link compression, so these groups are
        wider than the relaxation's."""
        groups: dict[tuple, list[int]] = {}
        for j, prog in enumerate(progs):
            groups.setdefault(prog.usage.shape, []).append(j)
        out: list[np.ndarray] = [None] * len(progs)
        for idxs in groups.values():
            t0 = time.perf_counter()
            with self.tracer.span("engine/refine", track=self.trace_track, cat="engine"):
                ks = _round_group([progs[j] for j in idxs], [ms[j] for j in idxs], self.stats)
            self.stats.refine_seconds += time.perf_counter() - t0
            self.stats.refined_programs += len(idxs)
            for j, k in zip(idxs, ks):
                out[j] = k
        return out

    def _finalize_many(self, items: list[tuple], refine: bool) -> list[JRBAResult]:
        """:func:`_finalize` of ``(prog, m, relaxed, water_filling, steps,
        fast)`` items, rounded together; ``fast`` marks a single-flow
        program solved without a relaxation."""
        ks = self._round([it[0] for it in items], [it[1] for it in items]) if refine else None
        out = []
        for j, (prog, m, relaxed, wf, steps, fast) in enumerate(items):
            res = _finalize(
                prog, m, relaxed, water_filling=wf, refine=refine, ks=None if ks is None else ks[j]
            )
            res.relax_steps = steps
            if fast:
                res.relaxed_span = res.span
                self.stats.fast_path_solves += 1
            out.append(res)
        return out

    def solve(
        self,
        net: NetworkGraph,
        flows: list[Flow],
        *,
        capacity: np.ndarray | None = None,
        water_filling: bool = False,
        refine: bool = True,
    ) -> JRBAResult | None:
        """Drop-in replacement for :func:`jrba` with bucketing + cache stats."""
        prog = self.build(net, flows, capacity=capacity)
        if prog is None:
            return None
        fast = self._use_fast_path(prog, refine)
        if fast:
            m, relaxed, steps = _fast_start(prog), 0.0, 0
        else:
            self._note_shape(("single", self._shape_key(prog), self.n_iters))
            t0 = time.perf_counter()
            m, relaxed = self._relax_one(prog)
            dt = time.perf_counter() - t0
            self.stats.solve_seconds += dt
            self.stats.dispatch_seconds += dt
            self.stats.single_solves += 1
            steps = self._steps[0]
        t0 = time.perf_counter()
        with self.tracer.span("engine/finalize", track=self.trace_track, cat="engine"):
            (res,) = self._finalize_many([(prog, m, relaxed, water_filling, steps, fast)], refine)
        dt = time.perf_counter() - t0
        self.stats.finalize_seconds += dt
        if fast:
            self.stats.solve_seconds += dt
        return res

    def solve_many(
        self,
        net: NetworkGraph | Sequence[NetworkGraph],
        flow_sets: list[list[Flow]],
        *,
        capacities: list[np.ndarray] | None = None,
        water_filling: bool | Sequence[bool] = False,
        refine: bool = True,
    ) -> list[JRBAResult | None]:
        """Solve N independent JRBA instances; same-shape instances share one
        vmapped compiled call. Result list aligns with ``flow_sets`` (None for
        empty/colocated-only instances).

        ``net`` may be a single network or one per instance — the fleet
        co-scheduling path, where every simulation owns its own topology.
        Network identity only matters host-side (path enumeration and the
        per-net path cache); the compiled relaxation sees pure tensors, so
        programs from *different* networks batch together whenever they land
        in the same (Nf, K, L) shape bucket. Different topologies have
        different link counts L and thus separate buckets automatically.

        ``water_filling`` may likewise be per-instance (rounding and the
        top-up are host-side, so mixed fleets of ``…+WF`` and plain policies
        share one batched solve).

        The batch dimension is padded up to a power of two (repeating the
        last program; padded lanes are discarded) so a draining fleet —
        16 live simulations, then 15, then 14… — reuses O(log N) compiled
        batch shapes instead of recompiling the vmapped solver per size.
        """
        n = len(flow_sets)
        nets = [net] * n if isinstance(net, NetworkGraph) else list(net)
        if len(nets) != n:
            raise ValueError(f"nets ({len(nets)}) must align with flow_sets ({n})")
        wf = [water_filling] * n if isinstance(water_filling, bool) else list(water_filling)
        if len(wf) != n:
            raise ValueError(f"water_filling ({len(wf)}) must align with flow_sets ({n})")
        if capacities is None:
            capacities = [None] * n
        elif len(capacities) != n:
            raise ValueError(
                f"capacities ({len(capacities)}) must align with flow_sets ({n})"
            )
        progs: list[FlowProgram | None] = [
            self.build(g, fs, capacity=cap)
            for g, fs, cap in zip(nets, flow_sets, capacities)
        ]
        results: list[JRBAResult | None] = [None] * n
        by_bucket: dict[tuple, list[int]] = {}
        # (result slot, finalize item): every program is relaxed first, then
        # the call's programs are rounded together
        pending: list[tuple[int, tuple]] = []
        n_fast = 0
        for i, p in enumerate(progs):
            if p is None:
                continue
            if self._use_fast_path(p, refine):
                pending.append((i, (p, _fast_start(p), 0.0, wf[i], 0, True)))
                n_fast += 1
            else:
                by_bucket.setdefault(self._shape_key(p), []).append(i)
        for shape, idxs in by_bucket.items():
            group = [progs[i] for i in idxs]
            b_pad = 1
            while b_pad < len(group):
                b_pad *= 2
            # the jitted batch solver specializes on B too, so the cache key
            # must include the (padded) batch size or stats would claim false
            # hits; padding keeps the set of B values seen logarithmic
            self._note_shape(("batch", b_pad, shape, self.n_iters))
            padded = group + [group[-1]] * (b_pad - len(group))
            t0 = time.perf_counter()
            self._steps = []
            solved = self._relax_group(padded, n_real=len(group))[: len(group)]
            steps = self._steps or [0] * len(group)
            dt = time.perf_counter() - t0
            self.stats.solve_seconds += dt
            self.stats.dispatch_seconds += dt
            self.stats.batched_solves += 1
            self.stats.batched_instances += len(group)
            for i, prog, (m, relaxed), st in zip(idxs, group, solved, steps):
                pending.append((i, (prog, m, relaxed, wf[i], st, False)))
        if pending:
            t0 = time.perf_counter()
            with self.tracer.span("engine/finalize", track=self.trace_track, cat="engine"):
                done = self._finalize_many([item for _, item in pending], refine)
            dt = time.perf_counter() - t0
            self.stats.finalize_seconds += dt
            self.stats.solve_seconds += dt * n_fast / len(pending)
            for (i, _), res in zip(pending, done):
                results[i] = res
        return results


# ---------------------------------------------------------------------------
# Exact reference for tests: enumerate all path combinations
# ---------------------------------------------------------------------------
def brute_force_span(prog: FlowProgram) -> float:
    """min over route choices of max_l (crossing volume / capacity): the true
    optimum of P3 (optimal bandwidths for fixed routes are proportional
    fills, so the span closed-form is the link-congestion max)."""
    Nf = prog.usage.shape[0]
    choices = [list(np.flatnonzero(prog.valid[i])) for i in range(Nf)]
    best = float("inf")
    for combo in itertools.product(*choices):
        sel = prog.usage[np.arange(Nf), list(combo)]  # (Nf, L)
        crossing = sel.T @ prog.volumes
        span = float(np.max(crossing / prog.capacity))
        best = min(best, span)
    return best
