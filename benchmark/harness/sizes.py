"""The closed loop's requests: a fixed table of sizes and who gets which.

The sizes come from the traffic file alone, so every seed offers the same
work at the same points of the loop: a run whose seed changed the sizes, or
only which client sends which, spread by the luck of the draw and not by
the system (three seeds at 40 s moved the prefill caught in the window by
4 % and the rate by 2.7 %; chip runs, PR 23). The seed makes the prompts'
tokens (and the weights). Client ``k``'s ``n``-th request is a function of
``(seed, k, n)`` and of nothing that happened before it.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np


def grid(spec: dict, n: int) -> List[int]:
    """``n`` sizes at the mid-points of ``n`` equal quantile steps between
    ``min`` and ``max``, evenly (``linear``) or in ratio (``log``)."""
    lo, hi = spec["min"], spec["max"]
    qs = [(i + 0.5) / n for i in range(n)]
    if spec.get("spacing", "log") == "log":
        return [int(round(lo * (hi / lo) ** q)) for q in qs]
    return [int(round(lo + (hi - lo) * q)) for q in qs]


def coprime_stride(n: int) -> int:
    """An odd stride near the golden section of ``n`` that visits every
    entry of a table of ``n`` before it repeats."""
    s = max(1, int(n * 0.382)) | 1
    while math.gcd(s, n) != 1:
        s += 2
    return s


def size_table(traffic: dict) -> List[Tuple[int, int]]:
    """``(prompt tokens, answer tokens)`` pairs: the prompt grid in order,
    the answer grid walked at a coprime stride so that long prompts do not
    always meet long answers."""
    n = traffic["size_table"]
    prompts = grid(traffic["prompt_tokens"], n)
    answers = grid(traffic["answer_tokens"], n)
    stride = coprime_stride(n)
    return [(prompts[i], answers[(i * stride) % n]) for i in range(n)]


class ClosedLoopPlan:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.table = size_table(traffic)
        self.clients = traffic["clients"]
        self.stagger = traffic.get("stagger_first", False)
        self.seed = seed
        self.vocab = vocab
        self.stride = coprime_stride(len(self.table))

    def sizes(self, k: int, n: int) -> Tuple[int, int]:
        """Client ``k`` walks the table from its own starting point at a
        coprime stride: the same for every seed."""
        start = k * len(self.table) // self.clients
        prompt, answer = self.table[(start + n * self.stride)
                                    % len(self.table)]
        if self.stagger and n == 0:
            # the first answers end one after another, as in a loop that has
            # been running for a while, instead of all at once
            answer = max(2, math.ceil(answer * (k + 1) / self.clients))
        return prompt, answer

    def request(self, k: int, n: int) -> Tuple[List[int], int]:
        prompt_len, answer = self.sizes(k, n)
        rng = np.random.default_rng([self.seed, k, n])
        return rng.integers(0, self.vocab, prompt_len).tolist(), answer
