from collections import Counter

import numpy as np
import pytest

from bench import traffic
from tiny_bench import ROOT

MIX = {"loop": "closed", "scheduler": "static", "max_seq": 1536,
       "blocks": 3,
       "prompt": {"median": 384, "sigma": 0.8, "min": 64, "max": 1024,
                  "quantum": 128},
       "output": {"median": 192, "sigma": 0.7, "min": 32, "max": 512,
                  "quantum": 32}}
BIG_SEED = 2**31 + 12345


def test_same_seed_same_requests():
    a = traffic.generate(MIX, BIG_SEED, 32000, 64)
    b = traffic.generate(MIX, BIG_SEED, 32000, 64)
    assert len(a) == 3 * 64
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.output_len == y.output_len


def test_seeds_reorder_the_same_lengths():
    a = traffic.generate(MIX, 1, 32000, 64)
    b = traffic.generate(MIX, 2, 32000, 64)
    for lo in range(0, len(a), 64):
        wa, wb = a[lo:lo + 64], b[lo:lo + 64]
        assert Counter(len(r.prompt) for r in wa) == Counter(
            len(r.prompt) for r in wb)
        assert Counter(r.output_len for r in wa) == Counter(
            r.output_len for r in wb)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:64], b[0].prompt[:64])


@pytest.mark.parametrize("block", [1, 7, 64, 192])
def test_lengths_sit_on_their_quanta(block):
    for r in traffic.generate(MIX, 5, 32000, block):
        n, o = len(r.prompt), r.output_len
        assert n % 128 == 0 and 64 <= n <= 1024
        assert o % 32 == 0 and 32 <= o <= 512
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 32000


def test_stratified_lengths_follow_the_lognormal():
    lens = traffic.stratified_lengths(MIX["output"], 1000)
    assert lens == sorted(lens)
    med = lens[500]
    assert 192 <= med <= 192 + 32          # the median, rounded up
    assert lens[0] == 32 and lens[-1] == 512


def test_an_open_loop_is_refused():
    with pytest.raises(ValueError, match="loop"):
        traffic.generate(dict(MIX, loop="poisson"), 0, 32000, 8)


def test_a_request_beyond_max_seq_is_refused():
    with pytest.raises(ValueError, match="max_seq"):
        traffic.generate(dict(MIX, max_seq=1024), 0, 32000, 8)


def test_committed_mix_is_served_within_max_seq():
    import json
    import os

    with open(os.path.join(ROOT, "bench", "traffic", "offline.json")) as f:
        mix = json.load(f)
    reqs = traffic.generate(mix, 9, 32000, 64)
    assert max(len(r.prompt) + r.output_len for r in reqs) <= mix["max_seq"]
