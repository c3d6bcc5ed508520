"""The roofline counts against hand counts at small shapes."""

import pytest

from harness import counts, peaks


def test_probe_bytes():
    # id 8 + position 4 + found 1, slots 1 + 8 * 0.25 / 2 = 2 of 8 B
    assert counts.probe(10, 0.25) == (0.0, 10 * (8 + 4 + 1 + 16))
    assert counts.probe(10, 0.0) == (0.0, 10 * 21)


def test_ftrl_chain():
    ops, nbytes = counts.ftrl(10, 8, 0.0)
    # probe 210, slot 40, rows: grads 1 + (z, n) 2 + arenas 3 + out 3
    assert nbytes == 210 + 40 + 10 * 8 * 4 * 9
    assert ops == 20 * 80


def test_ctr_flops_and_train_bytes():
    assert counts.ctr_flops(1, 39, 8, "fm") == 2 * 39 + 5 * 39 * 8 + 24 + 8
    assert counts.ctr_flops(3, 39, 1, "lr") == 3 * (2 * 39 + 8)
    # forward alone: F + 3Fk + 3k + 5 (LR: F + 5)
    assert counts.ctr_flops(2, 39, 8, "fm", backward=False) == \
        2 * (39 + 3 * 39 * 8 + 24 + 5)
    assert counts.ctr_flops(1, 39, 1, "lr", backward=False) == 39 + 5
    # pull 2 x 32 + id 8 + grad 32 + (z, n) 64 + (z, n, w) 96
    assert counts.train_bytes(1, 8) == 64 + 8 + 32 + 64 + 96


def test_least_time_picks_the_bound():
    pk = peaks.for_kind("TPU v5 lite")
    t, bound = counts.least_time(197e12, 819e9 / 2, pk)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = counts.least_time(0.0, 819e9, pk)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_map_load_follows_the_growth_rule():
    assert counts.map_load(3_750_000) == pytest.approx(3_750_000 / 2 ** 24)
    assert counts.map_load(2 ** 22) == pytest.approx(2 ** 22 / 2 ** 25)
    assert counts.map_load(10) == pytest.approx(10 / 1024)
