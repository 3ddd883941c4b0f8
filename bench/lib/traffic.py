"""One general generator for every traffic mix file under ``bench/traffic``.

Requests come in waves of one per client.  Each wave is a stratified draw
from the mix's length distributions: the ``clients`` prompt lengths of a
wave fall one in each of ``clients`` equal-probability strata of the prompt
distribution, and so do its output lengths.  Where in its stratum each
length falls, how prompts and outputs pair up and the order within the
wave are drawn from the mix's own ``sizes_seed``, so every ``--seed``
serves the same sizes in the same order: a window holds a few tens of
requests, and their median wait moves by tens of percent with the sizes
and their order alone.  ``--seed`` draws the prompts' token ids (and, in
the harness, the weights).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

LOOPS = ("closed",)


def seed_state(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed (also past 32 bits)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64)] + list(salt)))


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` derived from any seed."""
    word = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(1)
    return int(word[0]) & 0x7FFFFFFF


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``u`` (in (0, 1)) of a length distribution,
    rounded and clipped to ``[min, max]``."""
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Spec:
    index: int
    prompt_len: int
    max_new_tokens: int


class Mix:
    """A traffic mix: its fixed sizes, and the token ids one seed draws."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"loop {mix['loop']!r} not in {LOOPS}")
        self.mix = mix
        self.clients = int(mix["clients"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.sizes_seed = int(mix["sizes_seed"])
        self.room = int(mix["max_len"]) - 3
        if self.room - int(mix["prompt"]["max"]) < 1:
            raise ValueError("the longest prompt leaves no room for output "
                             "in max_len")
        self._waves: dict = {}

    def wave(self, w: int) -> list:
        """The ``(prompt_len, output_len)`` of wave ``w``, in order."""
        if w not in self._waves:
            c = self.clients
            rng = seed_state(self.sizes_seed, 1, w)
            strata = np.arange(c)

            def draw(spec):
                u = (rng.permutation(strata) + rng.uniform(0.0, 1.0, c)) / c
                return lengths(spec, np.clip(u, 1e-9, 1 - 1e-9))
            prompt = draw(self.mix["prompt"])
            output = np.minimum(draw(self.mix["output"]), self.room - prompt)
            self._waves[w] = [(int(p), int(o)) for p, o in zip(prompt, output)]
        return self._waves[w]

    def spec(self, i: int) -> Spec:
        p, o = self.wave(i // self.clients)[i % self.clients]
        return Spec(i, p, o)

    def prompt_ids(self, spec: Spec) -> np.ndarray:
        rng = seed_state(self.seed, 2, spec.index)
        return rng.integers(0, self.vocab, size=spec.prompt_len,
                            dtype=np.int32)
