import pytest

from roofline import chunk_steps, least_time, program_bytes, program_ops


def test_chunk_steps_of_the_default_schedule():
    # 400 steps split into 16 chunks of 25 (the engine's probe schedule)
    assert chunk_steps(400) == 25
    assert chunk_steps(150) == 25
    assert chunk_steps(30) == 30


def test_ops_and_bytes_at_hand_computed_shapes():
    # F=2 flows, K=4 paths, A=5 links: per step 4*2*4*5 + 25*2*4 + 8*5 = 400,
    # per early-exit check 2*2*4*5 + 6*2*4 + 2*5 = 138; 50 steps = 2 checks
    assert program_ops(2, 4, 5, 50, 400) == 50 * 400 + 2 * 138
    # 51 steps need a third check
    assert program_ops(2, 4, 5, 51, 400) == 51 * 400 + 3 * 138
    # usage 40 + volumes 2 + capacity 5 + weights 8 + span 1, float32
    assert program_bytes(2, 4, 5) == 4 * 56


def test_least_time_takes_the_larger_side_per_program():
    peak_flops, peak_bytes = 1000.0, 100.0
    # 20276 ops -> 20.276 s of compute; 224 bytes -> 2.24 s: compute bound
    seconds, bound = least_time([(2, 4, 5, 50)], 400, peak_flops, peak_bytes)
    assert seconds == pytest.approx(20.276)
    assert bound == "compute"
    # zero steps: only the bytes remain
    seconds, bound = least_time([(2, 4, 5, 0)], 400, peak_flops, peak_bytes)
    assert seconds == pytest.approx(2.24)
    assert bound == "memory"
