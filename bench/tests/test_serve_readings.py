"""The serve driver's readings from the host's token times and quanta:
the time per output token over stretches, the filled share of the page
pool, and the occupancy of the window's quanta."""

import pytest

from bench import harness

serve = harness.driver("serve")


def test_stretches_split_a_steady_stream_evenly():
    held = [0.1 * i for i in range(17)]          # 16 gaps of 0.1 s
    assert serve.stretches(held) == pytest.approx([0.1, 0.1])


def test_stretches_charge_a_burst_to_the_stretch_that_waits_for_it():
    # 8 tokens at once after 1.6 s, then 8 more at once after 0.8 s
    held = [0.0] + [1.6] * 8 + [2.4] * 8
    assert serve.stretches(held) == pytest.approx([0.2, 0.1])


def test_short_outputs_make_one_stretch_or_none():
    assert serve.stretches([0.0, 0.3, 0.4]) == pytest.approx([0.2])
    assert serve.stretches([0.0]) == []
    # 20 gaps: two stretches of 10, none shorter than 8 tokens
    held = [float(i) for i in range(21)]
    assert serve.stretches(held) == pytest.approx([1.0, 1.0])


def test_kv_filled_counts_live_positions_after_each_quantum():
    # (time, wall, chunk, useful, [(position, steps) per active slot])
    quanta = [(0, 0, 1, 2, [(3, 1), (7, 1)]), (0, 0, 1, 1, [(0, 1)])]
    assert serve.kv_filled(quanta, slots=2, max_seq=8) == \
        pytest.approx((4 + 8 + 1) / (2 * 2 * 8))


def test_occupancy_reads_the_window_quanta_only():
    read = harness.layer_reader("serve.occupancy")

    class R:
        counters = {"quanta": [(0, 0, 2, 4, []), (0, 0, 1, 1, []),
                               (0, 0, 4, 0, [])],
                    "window_q1": 2, "slots": 2}

    assert read(R) == pytest.approx(100.0 * 5 / 6)
    R.counters = dict(R.counters, window_q1=0)
    assert read(R) is None
