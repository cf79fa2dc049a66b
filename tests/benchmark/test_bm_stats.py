"""Percentile and gap arithmetic on hand-made event lists."""

import pytest

from benchmark import stats


def test_percentile_interpolates_between_ranks():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile(list(range(101)), 95) == 95
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_gap_counts_where_it_ends():
    tokens = {
        "a": [0.5, 1.5, 2.5, 9.5, 10.5],  # gaps end at 1.5, 2.5, 9.5, 10.5
        "b": [0.2, 0.9],                  # ends before the window opens
        "c": [9.9, 10.0],                 # ends exactly at the close: counts
        "d": [3.0],                       # one token, no gap
    }
    gaps = stats.gaps_in_window(tokens, t_open=1.0, t_close=10.0)
    # a: 1.5 (began outside, ends inside), 2.5, 9.5 (7.0 long); 10.5 is out
    assert sorted(round(g, 6) for g in gaps) == [0.1, 1.0, 1.0, 7.0]


def test_a_gap_that_ends_on_the_opening_edge_does_not_count():
    assert stats.gaps_in_window({"a": [0.0, 1.0, 2.0]}, 1.0, 2.0) == [1.0]


def test_tokens_in_window_uses_the_same_edges():
    tokens = {"a": [0.5, 1.0, 1.5, 2.0, 2.5], "b": [1.2]}
    assert stats.tokens_in_window(tokens, 1.0, 2.0) == 3


def test_histogram_has_an_overflow_bin():
    edges = [0.0, 0.02, 0.04]
    assert stats.histogram([0.01, 0.02, 0.03, 0.05, 1.0], edges) == [1, 2, 2]


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    v5e = stats.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        stats.peaks("cpu")
