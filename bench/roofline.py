"""Operations and bytes that the JRBA relaxation needs for one program, from
the size of the work alone: its flows ``F``, the ``K`` candidate paths per
flow, the ``A`` links those paths touch, and the relaxation steps it ran.
Nothing here looks at padding, buckets, chunk layout or the kernel's own
tiling, so a rewrite of the kernel leaves the count as it is.

One Adam step of the sparse formulation (``repro.core.jrba``'s
``_solve_sparse_batched``, which the kernel implements) on the active links:

* path weights: a softmax over ``K`` per flow (5 ops per entry);
* link load: ``sum_ik V_i w_ik u_ikl`` over the ``F x K x A`` usage
  (a multiply and an add per entry, after ``V_i w_ik``: ``2FKA + FK``);
* smoothed congestion gradient on the links: divide by capacity, max,
  ``exp((c - max) / tau)``, sum, normalise and divide (8 per link);
* gradient back on the paths: ``sum_l u_ikl g_l`` (``2FKA``), times
  ``V_i`` (``FK``), and the softmax Jacobian (4 per entry);
* Adam: both moments, both bias corrections and the update (14 per entry).

That is ``4FKA + 25FK + 8A`` per step. The early exit reads the exact span
once per chunk of steps: one more softmax and load and a max
(``2FKA + 6FK + 2A``). Bytes are what must cross memory at least once per
solve: the 0/1 usage over the active links, the volumes, capacities and the
path weights out, all float32.
"""
from __future__ import annotations

import math


def chunk_steps(n_iters: int) -> int:
    """Steps between two early-exit checks: the most chunks (at most 16)
    that divide ``n_iters`` with at least 25 steps each."""
    best = 1
    for c in range(1, min(16, n_iters) + 1):
        if n_iters % c == 0 and n_iters // c >= 25:
            best = c
    return n_iters // best


def program_ops(f: int, k: int, a: int, steps: float, n_iters: int) -> float:
    per_step = 4 * f * k * a + 25 * f * k + 8 * a
    per_check = 2 * f * k * a + 6 * f * k + 2 * a
    return steps * per_step + math.ceil(steps / chunk_steps(n_iters)) * per_check


def program_bytes(f: int, k: int, a: int) -> float:
    return 4.0 * (f * k * a + f + a + f * k + 1)


def least_time(programs, n_iters: int, peak_flops: float, peak_bytes: float):
    """Least device time for ``programs`` (tuples ``(F, K, A, steps)``): each
    program's larger of operations over peak FLOP/s and bytes over peak
    bytes/s, summed. Returns ``(seconds, bound)`` where ``bound`` names the
    side that set most of those seconds."""
    compute = memory = 0.0
    for f, k, a, steps in programs:
        t_ops = program_ops(f, k, a, steps, n_iters) / peak_flops
        t_mem = program_bytes(f, k, a) / peak_bytes
        if t_ops >= t_mem:
            compute += t_ops
        else:
            memory += t_mem
    return compute + memory, ("compute" if compute >= memory else "memory")
