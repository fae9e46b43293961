"""The generator gives every seed the same work in another order."""

import collections

import pytest

from bench import traffic

MIX = {"arrivals": "all_at_start",
       "prompt_tokens": {"median": 512, "sigma": 1.0, "min": 32, "max": 2048},
       "output_tokens": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
       "block": 32}


def test_blocks_hold_the_same_lengths_for_every_seed():
    a = traffic.generate(MIX, 2**33 + 1, 64, 1000)
    b = traffic.generate(MIX, 7, 64, 1000)
    for blk in (slice(0, 32), slice(32, 64)):
        for key in (lambda r: len(r.tokens), lambda r: r.max_new):
            assert (collections.Counter(map(key, a[blk]))
                    == collections.Counter(map(key, b[blk])))
    assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]


def test_same_seed_same_requests_and_lengths_within_bounds():
    a = traffic.generate(MIX, 12345, 40, 1000)
    b = traffic.generate(MIX, 12345, 40, 1000)
    assert len(a) == 64
    assert all((x.tokens == y.tokens).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert all(32 <= len(r.tokens) <= 2048 and 16 <= r.max_new <= 512 for r in a)
    assert all(0 <= r.tokens.min() and r.tokens.max() < 1000 for r in a)


def test_other_arrival_processes_are_refused():
    with pytest.raises(ValueError, match="all_at_start"):
        traffic.generate(dict(MIX, arrivals={"process": "poisson"}), 3, 64, 100)
