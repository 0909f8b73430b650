"""opcount.py against the numbers worked out by hand (ISSUE 24)."""

import pytest

import opcount

SCORE = dict(rows=10 ** 7, features=28, n_trees=1000, max_depth=6)


def test_traverse_call_1000_trees_10m_rows():
    ops, nbytes = opcount.traverse_call(SCORE)
    assert ops == pytest.approx(3.53e13, rel=1e-3)      # 2*1e7*28*1000*63
    assert ops / 197e12 == pytest.approx(0.179, rel=1e-2)
    assert nbytes == 10 ** 7 * 32 + 1000 * 127 * 13


def test_traverse_call_scales_with_rows_and_trees():
    one = opcount.traverse_call(SCORE)[0]
    assert opcount.traverse_call(dict(SCORE, rows=10 ** 8))[0] == 10 * one
    assert opcount.traverse_call(dict(SCORE, n_trees=500))[0] == one / 2
