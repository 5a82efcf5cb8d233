"""Agent sessions: tool-loop conversations that share a framework's system
prompt and grow turn by turn, arriving on an open schedule.

Parameters (the mix's JSON file):
  rate_per_s        sessions started per second (Poisson arrivals)
  frameworks        {"weights": [...], "system_prompt_tokens": n}
  task_tokens       [lo, hi]  uniform, the task that opens a session
  turns             [lo, hi]  uniform, turns per session
  tool_tokens       {"median", "sigma", "min", "max"} lognormal, the tool
                    output each later turn appends
  output_tokens     {"median", "sigma", "min", "max"} lognormal, tokens
                    generated per turn (greedy)
  think_s           [lo, hi]  uniform, pause after each reply
  context_cap       a session ends before a turn would exceed this
  population_seed   seed of the sizes

Every run seed serves the same population of sessions: round(rate x
horizon) sessions, their sizes and their start times drawn once from
``population_seed`` (a Poisson process given its count: that many uniform
times inside the horizon, so every session starts before it ends). The run
seed only deals the sessions to the start times in another order and draws
the token ids.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

from bench.load import Session, Turn


def _lognormal(rng, spec, n):
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def sessions(p: dict, seed: int, horizon_s: float, vocab: int) -> List[Session]:
    rate = float(p["rate_per_s"])
    n = max(1, int(round(rate * horizon_s)))
    pop = np.random.default_rng(int(p["population_seed"]) + n)
    starts = np.sort(pop.uniform(0.0, horizon_s, n))
    fw_w = np.asarray(p["frameworks"]["weights"], float)
    fw = np.repeat(np.arange(len(fw_w)),
                   np.round(fw_w / fw_w.sum() * n).astype(int))[:n]
    fw = np.concatenate([fw, np.zeros(n - len(fw), int)])
    tasks = pop.integers(p["task_tokens"][0], p["task_tokens"][1] + 1, n)
    turns = pop.integers(p["turns"][0], p["turns"][1] + 1, n)
    shapes = []
    for i in range(n):
        k = int(turns[i])
        shapes.append((int(fw[i]), int(tasks[i]),
                       _lognormal(pop, p["tool_tokens"], k),
                       _lognormal(pop, p["output_tokens"], k),
                       pop.uniform(p["think_s"][0], p["think_s"][1], k)))

    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    sys_len = int(p["frameworks"]["system_prompt_tokens"])

    def ids(m):
        return rng.integers(1, vocab - 1, m).astype(np.int32)

    system = [ids(sys_len) for _ in fw_w]
    out = []
    for i, t in zip(order, starts):
        f, task, tool, outs, think = shapes[i]
        ts = [Turn(new=ids(task), max_new=int(outs[0]), think_s=float(think[0]))]
        ts += [Turn(new=ids(int(tool[k])), max_new=int(outs[k]),
                    think_s=float(think[k])) for k in range(1, len(outs))]
        out.append(Session(due_s=float(t), turns=ts, prefix=system[f],
                           cap=int(p["context_cap"])))
    return out


def min_context(p: dict) -> int:
    """Shortest prompt the mix can send."""
    return int(p["frameworks"]["system_prompt_tokens"]) + int(p["task_tokens"][0])
