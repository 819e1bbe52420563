"""The traffic generators: the same seed gives the same requests; another
seed the same sizes and arrivals in the same order, with other token ids."""
import itertools

import numpy as np
import pytest

import harness


def first(mix_name, seed, n, vocab=49155, rate=2.0):
    mix = harness.load_traffic(mix_name)
    rng = np.random.default_rng([*harness.seed_key_parts(seed), 1])
    gen = harness.generator(mix)
    return list(itertools.islice(gen.stream(mix, rng, vocab, rate), n))


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_same_seed_same_requests(mix):
    a, b = first(mix, 2**33 + 5, 130), first(mix, 2**33 + 5, 130)
    for (da, pa, ma), (db, pb, mb) in zip(a, b):
        assert da == db and ma == mb
        np.testing.assert_array_equal(pa, pb)


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_other_seed_same_sizes_other_tokens(mix):
    a, b = first(mix, 1, 128), first(mix, 2**40 + 3, 128)
    assert [(d, len(p), m) for d, p, m in a] == [(d, len(p), m) for d, p, m in b]
    assert any((pa != pb).any() for (_, pa, _), (_, pb, _) in zip(a, b))


def test_offline_blocks_hold_the_source_means():
    spec = harness.load_traffic("offline")
    reqs = first("offline", 5, 160)
    for i in range(0, 160, spec["stratum"]):
        block = reqs[i:i + spec["stratum"]]
        assert np.mean([len(p) for _, p, _ in block]) == pytest.approx(161.31, abs=1)
        assert np.mean([m for _, _, m in block]) == pytest.approx(337.99, abs=1)


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_lengths_within_the_mix(mix):
    spec = harness.load_traffic(mix)
    for due, p, m in first(mix, 7, 256):
        assert spec["prompt_tokens"]["min"] <= len(p) <= spec["prompt_tokens"]["max"]
        assert spec["output_tokens"]["min"] <= m <= spec["output_tokens"]["max"]
        assert ((p >= 0) & (p < 49155)).all()


def test_open_loop_rate():
    reqs = first("chat", 3, 640, rate=2.5)
    dues = [d for d, _, _ in reqs]
    assert all(b > a for a, b in zip(dues, dues[1:]))
    assert dues[-1] == pytest.approx(640 / 2.5, rel=1e-9)


def test_backlog_has_no_due_time():
    assert all(d is None for d, _, _ in first("offline", 3, 10))
