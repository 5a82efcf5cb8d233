"""Warm-up that runs every serving program shape a cell's traffic can reach.

The engine picks one compiled program per tick from the tick's make-up:
the batch bucket ``kb`` (rows in the tick, rounded up to a power of two),
the chunk ``C`` (the chunk bucket of the longest remaining prompt), the
packed token bucket (when the real tokens fit one smaller than kb x C), the
kv bucket (the longest context in the tick), and ``n``, the rows sampled
that tick. A tick is made of decode rows, prompt rows that finish, and
prompt rows that go on. This module builds ticks of each reachable make-up
through the engine's own admission and tick calls, all at the kv bucket the
traffic's contexts land in, so the window compiles nothing.

A pattern (d, j, u, r) admits d prompts of ``o`` tokens, j of ``o + r`` and
u of ``o + 2 * top``, all with one new token. The first tick prefills ``o``
of every row, the second is the target: d decode rows, j rows finishing
their last r tokens, u rows taking another full chunk. ``o`` is a multiple
of the largest chunk, chosen so every context lies in the traffic's kv
bucket.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import numpy as np


def _pow2(n: int, cap: int) -> int:
    k = 1
    while k < n:
        k *= 2
    return min(k, cap)


def _bucket(n: int, buckets) -> int:
    return next((b for b in buckets if b >= n), buckets[-1])


def _signature(d: int, j: int, u: int, r: int, *, slots: int, chunks,
               packed_buckets, align: int, packed: bool) -> Set[Tuple]:
    rows = d + j + u
    kb = _pow2(rows, slots)
    top = chunks[-1]
    C = top if u else _bucket(r, chunks)
    al = lambda n: -(-n // align) * align                      # noqa: E731
    tokens = j * al(r) + u * al(top) + d * al(1)
    npb = next((b for b in packed_buckets if b >= tokens), None)
    use_packed = packed and npb is not None and npb < kb * C
    out = {("program", kb, C, npb if use_packed else 0)}
    if d + j:
        out.add(("sample", kb, d + j))
    return out


def plan(*, slots: int, chunks, packed_buckets, align: int,
         packed: bool) -> List[Tuple[int, int, int, int]]:
    """The fewest patterns, cheapest first, that reach every tick
    signature the candidates can reach."""
    chunks = tuple(chunks)
    rs = range(1, chunks[-1] + 1)
    cands = []
    for d in range(slots + 1):
        for j in range(slots + 1 - d):
            for u in range(slots + 1 - d - j):
                if j + u == 0:
                    continue
                for r in rs if j else [chunks[0]]:
                    sig = _signature(d, j, u, r, slots=slots, chunks=chunks,
                                     packed_buckets=packed_buckets,
                                     align=align, packed=packed)
                    cost = (d + j + u) * (3 + (u > 0))
                    cands.append((cost, (d, j, u, r), sig))
    cands.sort(key=lambda c: c[0])
    seen: Set[Tuple] = set()
    chosen = []
    for _, pat, sig in cands:
        if sig - seen:
            seen |= sig
            chosen.append(pat)
    return chosen


def warm(engine, *, min_context: int, vocab: int, seed: int = 0) -> Dict:
    """Run the patterns on ``engine`` (not yet serving) with the prefix
    cache detached, plus one suspend and resume round trip. Contexts lie
    above the kv bucket below ``min_context``, in the bucket the traffic
    uses."""
    from repro.kernels import ops as kops
    from repro.serving import engine as engine_mod

    chunks = tuple(engine.prefill_chunks)
    top = chunks[-1]
    below = [b for b in engine.kv_buckets if b < min_context]
    floor = max(below) if below else 0
    o = top * (floor // top + 1)
    if o + 2 * top + 2 > engine.max_len:
        raise ValueError(f"warm-up prompts of {o + 2 * top} tokens do not "
                         f"fit max_len {engine.max_len}")
    pats = plan(slots=engine.max_slots, chunks=chunks,
                packed_buckets=engine_mod._EngineJits.PACKED_BUCKETS,
                align=kops.packed_row_align(), packed=bool(engine.packed))
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(1, vocab - 1, n).astype(np.int32)

    def drain(slots):
        while any(not engine.is_done(s) for s in slots):
            engine.serve_step()
        for s in slots:
            engine.free(s)

    pc, engine.prefix_cache = engine.prefix_cache, None
    try:
        for d, j, u, r in pats:
            reqs = ([dict(prompt=prompt(o), max_new=1) for _ in range(d)]
                    + [dict(prompt=prompt(o + r), max_new=1)
                       for _ in range(j)]
                    + [dict(prompt=prompt(o + 2 * top), max_new=1)
                       for _ in range(u)])
            drain(engine.add_sequences(reqs, eager=False))
        # one suspend and resume: the context-switch programs
        slot = engine.add_sequences([dict(prompt=prompt(o + 1), max_new=3)],
                                    eager=False)[0]
        while engine.is_prefilling(slot) or not engine.slots[slot].generated:
            engine.serve_step()
        snap = engine.snapshot(slot)
        slot = engine.restore(snap, eager=False)
        snap.release()
        drain([slot])
    finally:
        engine.prefix_cache = pc
    return {"patterns": len(pats), "prefix_tokens": o}
