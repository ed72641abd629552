"""The traffic generator: the same lengths for every seed, tokens from the
seed."""

import itertools

import pytest

from benchmarks.chip import traffic

SEEDS = [0, 7, 2 ** 31 + 11, 2 ** 40 + 3]


@pytest.mark.parametrize("mix", ["serve-decode", "serve-prefill"])
def test_lengths_are_the_laws_quantiles(mix):
    m = traffic.load(mix)
    lengths = traffic.cycle_lengths(m)
    p = m["prompt"]
    assert len(lengths) == m["cycle"]
    assert all(p["min"] <= n <= p["max"] + p["round_to"] for n in lengths)
    assert all(n % p["round_to"] == 0 for n in lengths)
    assert sorted(lengths)[len(lengths) // 2] in (
        p["median"], p["median"] + p["round_to"])
    assert n_shapes(m) <= 8


def n_shapes(m):
    return len(set(traffic.cycle_lengths(m)))


@pytest.mark.parametrize("mix", ["serve-decode", "serve-prefill"])
def test_every_seed_serves_the_same_lengths(mix):
    m = traffic.load(mix)
    runs = [list(itertools.islice(traffic.serve_batches(m, 1000, s), 40))
            for s in SEEDS]
    shapes = [[(len(b), len(b[0])) for b in r] for r in runs]
    assert all(s == shapes[0] for s in shapes)
    assert runs[0][0] != runs[1][0]
    assert all(2 <= t < 1000 for b in runs[2] for p in b for t in p)
    again = list(itertools.islice(traffic.serve_batches(m, 1000, SEEDS[3]),
                                  40))
    assert again == runs[3]


def test_prefill_tail_is_the_longest_bucket():
    """p95 of the prefill cell falls among its longest prompts: more than
    5% of every cycle has the longest length."""
    m = traffic.load("serve-prefill")
    lengths = traffic.cycle_lengths(m)
    assert lengths.count(max(lengths)) / len(lengths) > 0.05


def test_unknown_mix():
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-mix")
