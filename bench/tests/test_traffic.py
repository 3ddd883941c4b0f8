"""The traffic generator: what a seed draws, and what every seed shares."""
import json
import os
import statistics

import numpy as np

from bench.lib.traffic import Mix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chat():
    with open(os.path.join(ROOT, "traffic", "chat.json")) as f:
        return json.load(f)


def _sizes(mix, seed, n):
    m = Mix(mix, 50304, seed)
    return [(s.prompt_len, s.max_new_tokens) for s in map(m.spec, range(n))]


def test_same_seed_same_sizes_and_ids():
    mix = _chat()
    big = 2 ** 31 + 12345
    assert _sizes(mix, big, 48) == _sizes(mix, big, 48)
    a, b = Mix(mix, 50304, big), Mix(mix, 50304, big)
    assert np.array_equal(a.prompt_ids(a.spec(3)), b.prompt_ids(b.spec(3)))


def test_every_seed_serves_the_same_sizes_in_the_same_order():
    mix = _chat()
    c = mix["clients"]
    sizes = _sizes(mix, 1, 4 * c)
    for seed in (2, 3 * 10 ** 9, 2 ** 40 + 7):
        assert _sizes(mix, seed, 4 * c) == sizes
    # the seed still draws the prompts' token ids
    a, b = Mix(mix, 50304, 1), Mix(mix, 50304, 2)
    assert not np.array_equal(a.prompt_ids(a.spec(0)), b.prompt_ids(b.spec(0)))
    # another sizes_seed is another fixed sample
    other = dict(mix, sizes_seed=mix["sizes_seed"] + 1)
    assert _sizes(other, 1, c) != sizes[:c]


def test_each_wave_takes_one_length_from_each_stratum():
    mix = _chat()
    c = mix["clients"]
    sizes = _sizes(mix, 1, 3 * c)
    waves = [sorted(p for p, _ in sizes[i:i + c]) for i in range(0, 3 * c, c)]
    # the waves differ from one another
    assert waves[0] != waves[1] != waves[2]
    # but each takes one prompt from each of `clients` strata of equal
    # probability: its k-th shortest lies between the lognormal's quantiles
    nd = statistics.NormalDist()
    p = mix["prompt"]
    for wave in waves:
        for k, length in enumerate(wave):
            lo = p["median"] * np.exp(p["sigma"] * nd.inv_cdf(
                max(k / c, 1e-9)))
            hi = p["median"] * np.exp(p["sigma"] * nd.inv_cdf(
                min((k + 1) / c, 1 - 1e-9)))
            assert min(max(lo, p["min"]), p["max"]) - 1 <= length \
                <= min(hi, p["max"]) + 1


def test_every_request_fits_the_window():
    mix = _chat()
    for seed in (5, 2 ** 33 + 1):
        for prompt, out in _sizes(mix, seed, 256):
            assert mix["prompt"]["min"] <= prompt <= mix["prompt"]["max"]
            assert 1 <= out and prompt + out <= mix["max_len"] - 3
