"""Operation and byte counts of the benchmark's yardstick, against hand
counts, and the peaks table."""

import pytest

from bench.harness import flops
from bench.harness.peaks import peaks_for


def test_two_core_chain_counts():
    # x (8, 6) . g0 (6, 4) -> (8, 4); (8, 4) . g1 (4, 5, 1) -> (8, 5)
    c = flops.tt_chain_cost(8, [(6, 4), (4, 5, 1)], split=1)
    assert c.flops == 2 * 8 * 6 * 4 + 2 * 8 * 1 * 4 * 5 * 1
    assert c.bytes == 4 * (8 * 6 + 6 * 4 + 4 * 5 * 1 + 8 * 5)


def test_three_core_chain_split_one_counts():
    # input core g0 (6, 4); output cores g1 (4, 3, 2), g2 (2, 5, 1)
    c = flops.tt_chain_cost(2, [(6, 4), (4, 3, 2), (2, 5, 1)], split=1)
    hand = 2 * 2 * 6 * 4 + 2 * 2 * 1 * 4 * 3 * 2 + 2 * 2 * 3 * 2 * 5 * 1
    assert c.flops == hand
    assert c.bytes == 4 * (2 * 6 + 24 + 24 + 10 + 2 * 15)


def test_three_core_chain_split_two_counts():
    # input modes 6 x 3: g0 (6, 4) then g1 (4, 3, 2); output g2 (2, 5, 1)
    c = flops.tt_chain_cost(2, [(6, 4), (4, 3, 2), (2, 5, 1)], split=2)
    hand = 2 * 2 * 18 * 4 + 2 * 2 * 3 * 1 * 4 * 2 + 2 * 2 * 1 * 2 * 5 * 1
    assert c.flops == hand
    assert c.bytes == 4 * (2 * 18 + 24 + 24 + 10 + 2 * 5)


def test_least_time_names_its_bound():
    t, bound = flops.least_time(flops.Cost(197e12, 1.0), 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_time(flops.Cost(1.0, 819e9), 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


def test_decode_flops_per_token_hand_count():
    chains = [([(4, 3), (3, 2, 1)], 1)]          # one 4 -> 2 chain a layer
    got = flops.decode_flops_per_token(chains, num_layers=2, num_heads=2,
                                       head_dim=3, d_model=4, vocab=10,
                                       context=5.0)
    chain = 2 * 4 * 3 + 2 * 3 * 2
    attn = 4 * 2 * 3 * 5.0
    assert got == 2 * (chain + attn) + 2 * 4 * 10


def test_mean_context():
    # lengths 2 and 3: steps 1 + 2, keys 1 + (1 + 2)
    assert flops.mean_context([2, 3]) == pytest.approx(4 / 3)


def test_peaks_table_refuses_unknown_device():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks_for("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
