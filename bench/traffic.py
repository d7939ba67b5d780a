"""The one traffic generator: every mix is a file of parameters it reads.

Lengths are a fixed set, the ``block`` stratified quantiles of each
clipped lognormal, so every seed serves the same work: a seed that
changed the sizes would change what a run measures. With a block of 32
the set reaches both clips (prompts 30 to 1536, answers 23 to 512). The
set is dealt into groups of ``group`` messages, each holding a balanced
spread of ranks (the shortest with the longest, and so on), and the
groups are taken in a fixed order whose every prefix spreads over the
whole range of ranks (the radical inverse of the group's index), so that
a window that holds only part of a cycle still serves heavy and light
groups alike. A driver takes whole groups as its phases. The seed orders
the messages of each group, pairs its prompts with its answers, and draws
every token id uniformly over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Msg:
    """One admission, in the shape the serving worker reads."""
    msg_id: int
    payload: Dict


def quantile_lengths(dist: Dict, n: int) -> List[int]:
    """The ``n`` stratified quantiles ``(i + 1/2) / n`` of a lognormal with
    ``median`` and ``sigma``, rounded and clipped to ``[min, max]``."""
    nd = NormalDist()
    return [int(min(dist["max"], max(dist["min"], round(
        dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def deal(n: int, group: int) -> List[List[int]]:
    """Ranks ``0..n-1`` dealt into ``n // group`` groups, back and forth, so
    that each group spans the range: for ``group=2``, ``{0, n-1}``,
    ``{1, n-2}``, ..."""
    if n % group:
        raise ValueError(f"block of {n} is not a whole number of groups of {group}")
    g = n // group
    groups: List[List[int]] = [[] for _ in range(g)]
    for r in range(n):
        i = r % g
        groups[i if (r // g) % 2 == 0 else g - 1 - i].append(r)
    return groups


def radical_inverse(i: int) -> float:
    """``i``'s binary digits mirrored about the point: 1 -> 0.5, 2 -> 0.25,
    3 -> 0.75, ..."""
    x, f = 0.0, 0.5
    while i:
        x += f * (i & 1)
        i >>= 1
        f /= 2
    return x


def spread_order(n: int) -> List[int]:
    """``0..n-1`` ordered by their radical inverse: for 8,
    ``[0, 4, 2, 6, 1, 5, 3, 7]``, so every prefix spans the range."""
    return sorted(range(n), key=radical_inverse)


class Traffic:
    """Messages of one mix for one seed; ``warmup()`` gives the group that
    set-up serves, ``group(k)`` the ``k``-th group of the cyclic order the
    window takes, with fresh request ids."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.block = int(mix["block"])
        self.group_size = int(mix["group"])
        self.prompts = quantile_lengths(mix["prompt"], self.block)
        self.answers = quantile_lengths(mix["answer"], self.block)
        self.ranks = deal(self.block, self.group_size)
        self.order = spread_order(len(self.ranks))
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.next_id = 0
        self.prompt_of: Dict[int, List[int]] = {}
        self.prompt_len: Dict[int, int] = {}

    @property
    def n_groups(self) -> int:
        return len(self.ranks)

    def max_total(self) -> int:
        """The longest request any pairing can make."""
        return max(self.prompts) + max(self.answers)

    def _msg(self, n_prompt: int, n_answer: int) -> Msg:
        rid = self.next_id
        self.next_id += 1
        prompt = self.rng.integers(0, self.vocab, n_prompt).tolist()
        self.prompt_of[rid] = prompt
        self.prompt_len[rid] = n_prompt
        return Msg(rid, {"request_id": rid, "prompt": prompt,
                         "max_new_tokens": n_answer})

    def _group(self, ranks: List[int]) -> List[Msg]:
        p = [self.prompts[r] for r in ranks]
        a = [self.answers[r] for r in ranks]
        self.rng.shuffle(p)
        self.rng.shuffle(a)
        return [self._msg(x, y) for x, y in zip(p, a)]

    def warmup(self) -> List[Msg]:
        """The median group of the block."""
        return self._group(self.ranks[len(self.ranks) // 2])

    def group(self, k: int) -> List[Msg]:
        return self._group(self.ranks[self.order[k % self.n_groups]])

    def groups(self) -> Iterator[List[Msg]]:
        k = 0
        while True:
            yield self.group(k)
            k += 1
