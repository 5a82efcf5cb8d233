"""Which rows each engine tick ran, at which positions.

The engine consumes its prefill queue the same way every tick: every
queued prompt takes ``min(remaining, C)`` tokens, where ``C`` is the
smallest chunk bucket that holds the longest remaining prompt (at most the
largest bucket), and every decoding request feeds one token. The stamps
give the rest: a request joins the queue at its admission tick with the
prompt tokens it has to prefill (its prompt, less what a prefix-cache hit
restored), and each streamed token names the tick that fed it.

``reconstruct`` replays that and checks every tick it can against the
engine's own per-tick record (rows, real tokens, chunk). It returns None
when one disagrees, so no metric is read from a wrong picture.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class Tick:
    __slots__ = ("kind", "decode", "prefill", "finish")

    def __init__(self, kind: int):
        self.kind = kind
        self.decode: List[int] = []     # position fed by each decode row
        self.prefill: List[tuple] = []  # (offset, tokens) of each prompt row
        self.finish = 0                 # prompt rows that finished


def _admitted_at(r) -> Optional[int]:
    if not r.admits:
        return None
    first_tok = r.stamps[0][1] if r.stamps else None
    ticks = [a for _, a in r.admits if first_tok is None or a < first_tok]
    return ticks[-1] if ticks else None


def reconstruct(run, log=None) -> Optional[Dict[int, Tick]]:
    t = run.ticks
    first, n_rec = int(t["first"]), len(t["kind"])
    end = first + n_rec
    chunks = run.prefill_chunks
    joins: Dict[int, List[list]] = {}
    decode: Dict[int, List[int]] = {}
    for r in run.requests:
        P = len(r.prompt_np)
        a = _admitted_at(r)
        if a is not None:
            todo = P
            if r.response and "usage" in r.response:
                todo = int(r.response["usage"]["prompt_tokens"])
            if todo > 0:
                joins.setdefault(a, []).append([P - todo, todo])
        for i, (_, k) in enumerate(r.stamps):
            decode.setdefault(k, []).append(P + i)
    queue: List[list] = []
    out: Dict[int, Tick] = {}
    bad = 0
    for k in range(0, end):
        queue += joins.get(k, [])
        dec = decode.get(k, [])
        if not queue and not dec:
            continue
        C = 1
        if queue:
            rem = max(j[1] for j in queue)
            C = next((c for c in chunks if c >= rem), chunks[-1])
        tick = Tick(-1)
        tick.decode = list(dec)
        for j in queue:
            n = min(j[1], C)
            tick.prefill.append((j[0], n))
            j[0] += n
            j[1] -= n
        tick.finish = sum(1 for j in queue if j[1] == 0)
        queue = [j for j in queue if j[1] > 0]
        if k >= first:
            i = k - first
            tick.kind = int(t["kind"][i])
            toks = sum(n for _, n in tick.prefill) + len(tick.decode)
            rows = len(tick.prefill) + len(tick.decode)
            if (toks != int(t["tokens"][i]) or rows != int(t["rows"][i])
                    or (tick.prefill and C != int(t["chunk"][i]))):
                bad += 1
        out[k] = tick
    if bad:
        if log:
            log(f"tick reconstruction: {bad} ticks disagree with the "
                f"engine's record")
        return None
    return out


def ticks_between(rows: Dict[int, Tick], a: int, b: int, kinds=None):
    return [rows[k] for k in range(a, b)
            if k in rows and (kinds is None or rows[k].kind in kinds)]


def dims_np(dims):
    layers, d, H, K, hd, dff, vocab, _, _ = dims
    return layers, d, H, K, hd, dff, vocab


def attention_work(rows_ok, dims, dtype_bytes: int = 2):
    """Operations and bytes one layer's attention needs for a tick's rows at
    their live lengths: each query position p attends p + 1 keys (two
    products of 2 * head_dim operations per query head and key), and each
    row reads its K and V up to its last position once, plus its queries
    and outputs."""
    _, _, H, K, hd, _, _ = dims_np(dims)
    flops = 0.0
    nbytes = 0.0
    for o, n in rows_ok:
        flops += 4.0 * H * hd * (n * o + n * (n + 1) / 2)
        nbytes += dtype_bytes * (2.0 * (o + n) * K * hd + 2.0 * n * H * hd)
    return flops, nbytes
