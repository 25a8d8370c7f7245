"""The one traffic generator: reads a mix's parameters and deals its inputs
from the run's seed.

Every seed gets the same sizes in the same proportions; the seed only
shuffles their order and draws the token ids.  Token ids are uniform over
the configuration's vocabulary, and every row of every batch differs.
"""
from __future__ import annotations

import numpy as np


class ServeTraffic:
    """Closed-loop batches: batch ``i`` holds ``batch`` prompts of one length.

    Lengths are dealt in rounds of ``sum(weights)`` batches, each length as
    many times as its weight, in an order shuffled per round from the seed.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab, self.seed = vocab, seed
        self.batch = mix["batch"]
        self.lengths = sorted(set(mix["prompt_lens"]))
        self._round = [n for n, w in zip(mix["prompt_lens"], mix["weights"])
                       for _ in range(w)]
        self._order: list[int] = []

    def prompt_len(self, i: int) -> int:
        r = len(self._round)
        while len(self._order) <= i:
            k = len(self._order) // r
            rng = np.random.default_rng([self.seed, 3, k])
            self._order.extend(rng.permutation(self._round).tolist())
        return self._order[i]

    def batch_tokens(self, i: int) -> np.ndarray:
        """(batch, prompt_len) int32 prompt ids of window batch ``i``."""
        rng = np.random.default_rng([self.seed, 0, i])
        return rng.integers(0, self.vocab, (self.batch, self.prompt_len(i)),
                            dtype=np.int32)

    def warmup_tokens(self, length: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, length])
        return rng.integers(0, self.vocab, (self.batch, length), dtype=np.int32)

    def sample(self, finished: list[int], n: int) -> list[tuple[int, int]]:
        """(batch index, row) of ``n`` finished requests drawn from the seed,
        the first request with the longest prompt among them."""
        reqs = [(b, r) for b in finished for r in range(self.batch)]
        longest = max(self.prompt_len(b) for b in finished)
        first = next(q for q in reqs if self.prompt_len(q[0]) == longest)
        rest = [q for q in reqs if q != first]
        rng = np.random.default_rng([self.seed, 2])
        pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
        return [first] + [rest[j] for j in sorted(pick)]


class TrainTraffic:
    """Step ``k`` trains on ``batch`` rows of ``seq`` token ids."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab, self.seed = vocab, seed
        self.batch, self.seq = mix["batch"], mix["seq"]

    def batch_tokens(self, k: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 0, k])
        return rng.integers(0, self.vocab, (self.batch, self.seq), dtype=np.int32)


KINDS = {"serve": ServeTraffic, "train": TrainTraffic}


def make(mix: dict, vocab: int, seed: int):
    return KINDS[mix["kind"]](mix, vocab, seed)
