"""Fused sparse JRBA congestion kernel (Pallas).

One invocation runs a whole chunk of the solver's Adam steps device-resident:
load (path slots -> links), temperature-smoothed congestion softmax,
gradient (links -> path slots), softmax Jacobian, and the Adam update —
nothing round-trips to HBM between steps, and the logits/momentum carries
are aliased onto the outputs (``input_output_aliases``) so the chunked
early-exit driver's re-dispatches can reuse buffers where XLA allows it.

Input is the active-link usage ``FlowProgram.usage_active (B, Nf, K, La)``
that the jnp sparse path consumes too; the driver lays it out per path index
as ``(B, K, Nf, La)`` so each of the K candidate-path planes is one
tile-aligned ``(Nf, La)`` load. Load and gradient are then K broadcast
multiply-reduce passes on the vector unit: the link load sums over the flow
(sublane) axis, the gradient over the link (lane) axis. Nothing in the
kernel reshapes across the lane dimension (Mosaic refuses such shape casts),
and there is no MXU contraction, so every product and sum is plain f32 — no
matmul precision setting applies. The ``L - La`` inactive links enter the
softmax denominator as one closed-form term (they all sit at zero
congestion), so the objective — and therefore the solve trajectory — is the
sparse formulation of ``core.jrba._solve_sparse_batched`` exactly.

The per-step scalars (the anneal temperature and both Adam bias corrections)
are computed by XLA outside the kernel with the jnp path's own expressions
and read from SMEM, so the Adam update matches the jnp path term for term.

``JRBAEngine(solver="auto")`` compiles this kernel on a TPU; the compile for
a described TPU v5e is guarded by ``tests/test_tpu_compile.py`` and the
chip run by ``chip_smoke.py``. Off the chip the kernel runs under
``interpret=True`` (``solver="pallas-interpret"``), validated against the
jnp sparse path and the dense reference by ``tests/test_solver_sparse.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.jrba import _converged, probe_schedule

NEG_INF = -1e9

__all__ = ["sparse_congestion_solve"]


def _congestion_chunk_kernel(
    u_ref,  # (1, K, Nf, La) f32 — 0/1 usage of path k of flow i on active slot l
    mask_ref,  # (1, Nf, K) f32 — 0 on valid paths, NEG_INF on invalid
    vol_ref,  # (1, Nf, 1) f32
    cap_ref,  # (1, 1, La) f32 — active-slot capacity (padding slots: 1)
    nout_ref,  # (1, 1, 1) f32 — count of inactive (zero-congestion) links
    sched_ref,  # SMEM (3, S) f32 — per step: tau, 1 - 0.9^t, 1 - 0.999^t
    l_ref,  # (1, Nf, K) f32 — logits carry (aliased onto lo_ref)
    m_ref,  # (1, Nf, K) f32 — Adam first moment (aliased onto mo_ref)
    v_ref,  # (1, Nf, K) f32 — Adam second moment (aliased onto vo_ref)
    lo_ref,
    mo_ref,
    vo_ref,
    span_ref,  # (1, 1, 1) f32 — exact congestion span at chunk end
    *,
    n_steps: int,
    lr: float,
    k: int,
):
    mask = mask_ref[0]  # (Nf, K)
    vol = vol_ref[0]  # (Nf, 1)
    cap = cap_ref[0]  # (1, La)
    nout = nout_ref[0]  # (1, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)

    def congestion(w):  # (Nf, K) -> (1, La)
        vw = vol * w
        load = sum(
            jnp.sum(vw[:, kk : kk + 1] * u_ref[0, kk], axis=0, keepdims=True) for kk in range(k)
        )
        return load / cap

    def body(s, carry):
        logits, m, v = carry
        tau = sched_ref[0, s]
        w = jax.nn.softmax(logits + mask, axis=-1)
        c = congestion(w)
        maxc = jnp.max(c, axis=-1, keepdims=True)  # (1, 1)
        e = jnp.exp((c - maxc) / tau)
        denom = jnp.sum(e, axis=-1, keepdims=True) + nout * jnp.exp(-maxc / tau)
        glink = (e / denom) / cap  # (1, La): d obj / d load on active slots
        gw = jnp.zeros_like(w)
        for kk in range(k):  # gather back onto the path slots, one plane at a time
            gk = jnp.sum(u_ref[0, kk] * glink, axis=-1, keepdims=True)  # (Nf, 1)
            gw = jnp.where(col == kk, gk, gw)
        gw = gw * vol
        g = w * (gw - (w * gw).sum(-1, keepdims=True))
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / sched_ref[1, s]
        vh = v / sched_ref[2, s]
        logits = logits - lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (logits, m, v)

    logits, m, v = jax.lax.fori_loop(0, n_steps, body, (l_ref[0], m_ref[0], v_ref[0]))
    lo_ref[0] = logits
    mo_ref[0] = m
    vo_ref[0] = v
    w = jax.nn.softmax(logits + mask, axis=-1)
    span_ref[0] = jnp.max(congestion(w), axis=-1, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("n_iters", "early_exit", "interpret"),
)
def sparse_congestion_solve(
    usage_a: jax.Array,  # (B, Nf, K, La) f32 — usage over active-link slots
    valid: jax.Array,  # (B, Nf, K) bool
    volumes: jax.Array,  # (B, Nf) f32
    cap_a: jax.Array,  # (B, La) f32
    n_outside: jax.Array,  # (B,) f32
    *,
    n_iters: int = 400,
    lr: float = 0.25,
    early_exit: bool = True,
    span_rtol: float = 2e-2,
    stable_chunks: int = 2,
    min_chunks: int = 2,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked convergence-adaptive driver over the fused kernel, mirroring
    ``core.jrba._solve_sparse_batched``'s schedule and signature exactly.

    Lanes run lockstep (grid over B) through the schedule's chunks; a lane
    that converged — its rounding ``argmax_k w`` unchanged across
    ``stable_chunks`` consecutive chunk boundaries and its exact span
    plateaued within ``span_rtol`` — freezes (its carries stop updating)
    while the rest anneal on, and the loop ends when every lane converged
    or the ``n_iters`` budget is spent. Returns ``(w, span, steps)`` with
    per-lane step counts.
    """
    B, Nf, K, La = usage_a.shape
    pc, ps = probe_schedule(n_iters)
    t = jnp.arange(n_iters) + 1
    # the jnp path's per-step scalars, evaluated by XLA with its expressions
    sched = jnp.stack(
        [jnp.geomspace(1.0, 1e-3, n_iters), 1 - 0.9**t, 1 - 0.999**t]
    ).astype(jnp.float32)
    mask = jnp.where(valid, 0.0, jnp.float32(NEG_INF))
    u_t = jnp.swapaxes(usage_a, 1, 2)  # (B, K, Nf, La)
    vol2 = volumes[:, :, None]
    cap2 = cap_a[:, None, :]
    nout2 = n_outside[:, None, None]

    lane3 = lambda b: (b, 0, 0)  # noqa: E731
    carry = pl.BlockSpec((1, Nf, K), lane3)
    call = pl.pallas_call(
        functools.partial(_congestion_chunk_kernel, n_steps=ps, lr=lr, k=K),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, Nf, La), lambda b: (b, 0, 0, 0)),
            carry,
            pl.BlockSpec((1, Nf, 1), lane3),
            pl.BlockSpec((1, 1, La), lane3),
            pl.BlockSpec((1, 1, 1), lane3),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            carry,
            carry,
            carry,
        ],
        out_specs=[carry, carry, carry, pl.BlockSpec((1, 1, 1), lane3)],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nf, K), jnp.float32),
            jax.ShapeDtypeStruct((B, Nf, K), jnp.float32),
            jax.ShapeDtypeStruct((B, Nf, K), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        ],
        # alias the Adam carries onto the outputs as a donation hint for
        # the chunk loop's re-dispatches. Caveat: the driver re-reads the
        # pre-call carries in the freeze-merge below (frozen lanes keep
        # their old values), so XLA may still have to copy the buffers —
        # this bounds, rather than eliminates, per-chunk buffer churn
        input_output_aliases={6: 0, 7: 1, 8: 2},
        interpret=interpret,
    )

    def body(state):
        logits, m, v, span, ks, stable, steps, done, g = state
        sched_c = jax.lax.dynamic_slice(sched, (0, g * ps), (3, ps))
        lo, mo, vo, sp = call(u_t, mask, vol2, cap2, nout2, sched_c, logits, m, v)
        sp = sp[:, 0, 0]
        keep = done[:, None, None]
        logits = jnp.where(keep, logits, lo)
        m = jnp.where(keep, m, mo)
        v = jnp.where(keep, v, vo)
        new_span = jnp.where(done, span, sp)
        new_ks = jnp.argmax(logits + mask, axis=-1).astype(jnp.int32)
        stable = jnp.where(jnp.all(new_ks == ks, axis=-1), stable + 1, 0)
        steps = jnp.where(done, steps, (g + 1) * ps)
        if early_exit:
            conv = _converged(g + 1, stable, new_span, span, span_rtol, min_chunks, stable_chunks)
            done = jnp.logical_or(done, conv)
        return (logits, m, v, new_span, new_ks, stable, steps, done, g + 1)

    def probing(state):
        return jnp.logical_and(state[8] < pc, ~jnp.all(state[7]))

    z = jnp.zeros((B, Nf, K), jnp.float32)
    init = (
        z,
        z,
        z,
        jnp.full((B,), jnp.inf, jnp.float32),
        jnp.full((B, Nf), -1, jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool),
        jnp.int32(0),
    )
    logits, _, _, span, _, _, steps, done, _ = jax.lax.while_loop(probing, body, init)
    steps = jnp.where(done, steps, n_iters)
    return jax.nn.softmax(logits + mask, axis=-1), span, steps
