"""Closed-loop clients: each sends an unshared prompt, waits for the whole
reply, and sends the next at once.

Parameters (the mix's JSON file):
  clients           number of clients
  prompt_tokens     [lo, hi]  uniform prompt length
  output_tokens     [lo, hi]  uniform tokens generated per request (greedy)
  requests          requests per client, more than a run can finish
  population_seed   seed of the sizes

The sizes are drawn once from ``population_seed`` as one row of requests
per client, so every run seed serves the same work at the same pace: the
run seed only deals the rows to the clients in another order and draws
the token ids.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.load import Session, Turn


def sessions(p: dict, seed: int, horizon_s: float, vocab: int) -> List[Session]:
    c, m = int(p["clients"]), int(p["requests"])
    pop = np.random.default_rng(int(p["population_seed"]))
    plen = pop.integers(p["prompt_tokens"][0], p["prompt_tokens"][1] + 1, (c, m))
    olen = pop.integers(p["output_tokens"][0], p["output_tokens"][1] + 1, (c, m))
    rng = np.random.default_rng(seed)
    out = []
    for i in rng.permutation(c):
        turns = [Turn(new=rng.integers(1, vocab - 1, int(plen[i, j])).astype(np.int32),
                      max_new=int(olen[i, j]), reset=True) for j in range(m)]
        out.append(Session(due_s=0.0, turns=turns))
    return out


def min_context(p: dict) -> int:
    return int(p["prompt_tokens"][0])
