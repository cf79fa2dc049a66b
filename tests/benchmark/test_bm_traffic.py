"""The traffic generator: the seed orders the work, it never changes it."""

import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import traffic

TRAFFIC = Path(__file__).resolve().parents[2] / "benchmark" / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_gen_schedule_is_the_grid_for_any_seed(seed):
    t = load("gen-closed")
    grid = len(t["prompt_lengths"]) * len(t["output_lengths"])
    reqs = list(itertools.islice(traffic.gen_requests(t, seed), 2 * grid))
    for cycle in (reqs[:grid], reqs[grid:]):
        pairs = collections.Counter((len(p), o) for p, o in cycle)
        assert set(pairs.values()) == {1} and len(pairs) == grid
    assert all((p > 0).all() and p.dtype == np.int32 for p, _ in reqs)


def test_gen_schedule_order_differs_by_seed_and_repeats_for_one_seed():
    t = load("gen-closed")
    take = lambda s: [(len(p), o) for p, o in
                      itertools.islice(traffic.gen_requests(t, s), 81)]
    assert take(1) != take(2)
    assert take(1) == take(1)
    a = next(traffic.gen_requests(t, 5))[0]
    b = next(traffic.gen_requests(t, 5))[0]
    assert (a == b).all()


def test_every_block_of_nine_has_each_prompt_and_each_output_once():
    t = load("gen-closed")
    reqs = list(itertools.islice(traffic.gen_requests(t, 3), 81))
    for k in range(0, 81, 9):
        block = reqs[k:k + 9]
        assert sorted(len(p) for p, _ in block) == t["prompt_lengths"]
        assert sorted(o for _, o in block) == t["output_lengths"]


def test_paired_cycles_with_unequal_grids_still_uses_every_pair_once():
    rng = np.random.default_rng(0)
    pairs = list(itertools.islice(traffic.paired_cycles([1, 2], [5, 6, 7], rng), 6))
    assert sorted(pairs) == [(a, b) for a in (1, 2) for b in (5, 6, 7)]


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_score_records_cycle_through_the_files_multiset(seed):
    t = load("score-batch")
    n = len(t["lengths"])
    recs = list(itertools.islice(traffic.score_records(t, seed), 2 * n))
    assert sorted(len(r) for _, r in recs[:n]) == sorted(t["lengths"])
    assert sorted(len(r) for _, r in recs[n:]) == sorted(t["lengths"])
    assert len({rid for rid, _ in recs}) == 2 * n
    assert all(r.startswith(b"# ") and len(r) + 1 <= 1024 for _, r in recs)
    assert set(recs[0][1][2:]) <= set(t["alphabet"].encode())


def test_score_lengths_have_the_heavy_tail_the_cell_states():
    lengths = load("score-batch")["lengths"]
    assert min(lengths) == 64 and max(lengths) == 1000
    assert 260 <= float(np.median(lengths)) <= 280
    assert np.mean(lengths) > np.median(lengths)  # the tail pulls the mean


@pytest.mark.parametrize("name,seq_len", [("train", 8192), ("train-dp2tp2", 1024)])
def test_train_batches_are_full_rows_of_the_stated_token_count(name, seq_len):
    t = load(name)
    batches = traffic.train_batches(t, seq_len, seed=9)
    assert len(batches) == t["distinct_batches"]
    b = batches[0]
    assert b.shape == (t["grad_accum"], t["micro_batch"] * t["mesh"]["data"], seq_len + 1)
    assert b.min() >= 1 and b.max() <= 255
    assert b.shape[0] * b.shape[1] * seq_len == t["tokens_per_step"]
    assert (traffic.train_batches(t, seq_len, seed=9)[1] == batches[1]).all()
    assert not (traffic.train_batches(t, seq_len, seed=10)[0] == b).all()
