"""Prompt prefix cache: byte-budgeted LRU of post-prefill KV snapshots.

The pool-wide admission path (BatchedScheduler dispatcher) hands every new
prompt to ``ServingEngine.add_sequence``; multi-turn agents resubmit grown
conversations whose prefix (previous prompt + previous generation) was already
prefilled, and concurrent agents of one framework often share identical
prompts outright. Entries are ``ContextSnapshot`` objects (the paper §3.4
context machinery) with ``kind="prefix"``: the slot's cache slice captured
right after prefill, plus the last-position logits so an exact hit can sample
its pending token without touching the model.

Keys are the raw token bytes of the cached prefix; lookup returns the longest
cached entry that is a prefix of the incoming prompt, and the engine restores
the cached state into the slot and consumes only the remaining suffix with
ONE chunked-prefill job (`ServingEngine._enqueue_prefill` at the prefix
offset) instead of re-prefilling from token zero -- a suffix extension rides
the same batched chunk dispatches as fresh admissions.

One PrefixCache instance is shared by every core in an ``LLMCorePool``
(identical replicas => snapshots are interchangeable), so a prefix prefilled
on core 0 is a hit on core 1.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Optional

import numpy as np

from repro.obs import span


class PrefixCache:
    """LRU over token-prefix -> snapshot. Values are duck-typed: anything
    with ``.prompt`` (np.int32 tokens), ``.seq_len`` and ``.nbytes()``
    (ContextSnapshot in practice -- kept un-imported to avoid a cycle with
    serving.engine)."""

    def __init__(self, budget_bytes: int = 32 << 20, max_entries: int = 64,
                 min_tokens: int = 4, page_store=None):
        assert budget_bytes > 0 and max_entries > 0
        self.budget_bytes = budget_bytes
        self.max_entries = max_entries
        self.min_tokens = min_tokens
        # KVPageStore: entries are page lists into the shared table (bytes
        # deduplicated with live contexts), evicted entries demote to the
        # storage tier instead of vanishing, and a RAM miss can re-hydrate a
        # prefix persisted by another process on the same storage root
        self.page_store = page_store
        self._entries: "OrderedDict[bytes, Any]" = OrderedDict()
        self._hit_counts: dict = {}   # key -> hits (hit-proven entries are
                                      # evicted only after all unhit ones)
        self._used = 0
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "inserts": 0, "evictions": 0,
                      "hit_tokens": 0, "rehydrates": 0, "discards": 0}

    @staticmethod
    def key_of(tokens) -> bytes:
        return np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def used_bytes(self) -> int:
        return self._used

    # -- lookup -----------------------------------------------------------------
    def _longest_prefix(self, tok: np.ndarray):
        """(key, snap) of the longest cached entry (>= min_tokens) whose
        tokens are a prefix of `tok`, or (None, None). Caller holds _lock."""
        best_key, best = None, None
        for key, snap in self._entries.items():
            n = snap.seq_len
            if n < self.min_tokens or n > len(tok):
                continue
            if best is not None and n <= best.seq_len:
                continue
            if key == tok[:n].tobytes():
                best_key, best = key, snap
        return best_key, best

    def lookup(self, tokens) -> Optional[Any]:
        """Longest cached entry whose tokens are a prefix of `tokens`
        (at least ``min_tokens`` long). Touches the entry (LRU). On a RAM
        miss with a page store attached, falls through to the storage tier:
        a prefix persisted by an earlier (or concurrent) process on the same
        root re-hydrates into the table instead of re-prefilling."""
        tok = np.asarray(tokens, np.int32)
        with self._lock, span("aios.prefix.lookup",
                              tracer=self._tracer()) as sp:
            best_key, best = self._longest_prefix(tok)
            rehydrated = 0
            if self.page_store is not None:
                # probe the storage tier even on a resident hit: a SHORT
                # resident prefix (e.g. the shared base) must not shadow a
                # strictly longer one persisted by a previous process
                rk, rbest = self._rehydrate_locked(
                    tok, longer_than=best.seq_len if best is not None else 0)
                if rbest is not None:
                    best_key, best = rk, rbest
                    rehydrated = 1
            sp.set(hit_tokens=best.seq_len if best is not None else 0,
                   rehydrated=rehydrated)
            if best is None:
                self.stats["misses"] += 1
                return None
            self._entries.move_to_end(best_key)
            self._hit_counts[best_key] = self._hit_counts.get(best_key, 0) + 1
            self.stats["hits"] += 1
            self.stats["hit_tokens"] += best.seq_len
            pages = getattr(best, "pages", None)
            if pages is not None:
                # pin spans lookup-return -> engine materialization: a
                # concurrent insert on another core may evict this entry the
                # moment _lock drops, and non-durable refcount-0 pages would
                # be freed mid-read. The engine unpins after materializing.
                pages._store.pin_pages(pages)
            return best

    def _tracer(self):
        return getattr(self.page_store, "tracer", None)

    def _rehydrate_locked(self, tok: np.ndarray, longer_than: int = 0):
        """Probe the page store's persisted manifests for a prefix of `tok`
        STRICTLY longer than ``longer_than`` tokens and admit it as a
        resident entry. Caller holds _lock."""
        entry = self.page_store.rehydrate_prefix(
            tok, min_tokens=max(self.min_tokens, longer_than + 1))
        if entry is None:
            return None, None
        if entry.nbytes() > self.budget_bytes:
            # persisted under a bigger budget than this process runs with:
            # admitting it would evict the whole cache and still not fit
            entry.release()
            return None, None
        entry._rehydrated = True    # insert must not re-persist it
        key = self.key_of(entry.prompt)
        old = self._entries.pop(key, None)
        if old is not None:         # raced footprint; keep the fresh one
            self._used -= old.nbytes()
            self._release_entry(old)
        self._entries[key] = entry
        self._used += entry.nbytes()
        self.stats["rehydrates"] += 1
        while (self._used > self.budget_bytes or
               len(self._entries) > self.max_entries):
            if not self._evict_one(protect=key):
                break
        return key, entry

    def residency(self, tokens) -> Optional[tuple]:
        """Read-only probe for the control plane's affinity router:
        ``(origin_engine_id, resident_tokens)`` of the longest cached entry
        whose tokens are a prefix of ``tokens``, or None. Unlike lookup it
        must NOT touch LRU order or hit accounting -- the dispatcher probes
        every candidate placement, and a probe is not a use."""
        tok = np.asarray(tokens, np.int32)
        with self._lock:
            _, best = self._longest_prefix(tok)
        if best is None:
            return None
        return (getattr(best, "origin", None), best.seq_len)

    def page_residency(self, tokens) -> Optional[tuple]:
        """Read-only per-page residency probe: ``(dominant_origin,
        resident_tokens, page_origins)`` of the longest cached prefix of
        ``tokens``, where ``page_origins`` lists the engine id holding each
        page (a conversation extended across cores carries pages of mixed
        origin -- the fractional-affinity signal). ``page_origins`` is None
        for legacy blob entries (binary origin only). No LRU touch, no hit
        accounting."""
        tok = np.asarray(tokens, np.int32)
        with self._lock:
            _, best = self._longest_prefix(tok)
        if best is None:
            return None
        origin = getattr(best, "origin", None)
        pages = getattr(best, "pages", None)
        if pages is None or self.page_store is None:
            return (origin, best.seq_len, None)
        origins = self.page_store.page_origins(pages)
        if origins:
            counts: dict = {}
            for o in origins:
                if o is not None:
                    counts[o] = counts.get(o, 0) + 1
            if counts:
                origin = max(counts, key=lambda o: (counts[o], o == origin))
        return (origin, best.seq_len, origins)

    # -- insert -----------------------------------------------------------------
    def insert(self, snap) -> bool:
        """Insert (or refresh) the snapshot under its full token prefix.
        Page-store entries are write-through persisted to the storage tier
        (unless they just came from it), so eviction -- and process death --
        never loses a hot prefix, only its RAM residency."""
        nbytes = snap.nbytes()
        with span("aios.prefix.insert", tracer=self._tracer(),
                  bytes=nbytes) as sp:
            accepted = (snap.seq_len >= self.min_tokens
                        and nbytes <= self.budget_bytes)
            sp.set(accepted=int(accepted))
            if not accepted:
                return False
            key = self.key_of(snap.prompt)
            if (self.page_store is not None
                    and getattr(snap, "pages", None) is not None
                    and not getattr(snap, "_rehydrated", False)):
                self.page_store.persist_prefix(snap)
            with self._lock:
                old = self._entries.pop(key, None)
                if old is not None:
                    self._used -= old.nbytes()
                    self._release_entry(old)
                self._entries[key] = snap
                self._used += nbytes
                self.stats["inserts"] += 1
                while (self._used > self.budget_bytes or
                       len(self._entries) > self.max_entries):
                    if not self._evict_one(protect=key):
                        break
                return True

    @staticmethod
    def _release_entry(snap):
        """Hand an entry's pages back to the store (refcount-0 durable pages
        demote to the disk tier, so an evicted-then-reused prefix re-hydrates
        instead of re-prefilling). Legacy blob entries just drop."""
        rel = getattr(snap, "release", None)
        if rel is not None:
            rel()

    def discard(self, snap) -> bool:
        """Drop a POISONED entry the engine failed to materialize (page
        blobs swept by a sibling process, corrupt payload, storage fault):
        remove it and release its pages so the next lookup cold-misses
        instead of rediscovering the same corpse. The caller must have
        dropped its lookup pin already. Safe if the entry was already
        evicted (returns False)."""
        key = self.key_of(snap.prompt)
        with self._lock:
            entry = self._entries.pop(key, None)
            self._hit_counts.pop(key, None)
            if entry is None:
                return False
            self._used -= entry.nbytes()
            self._release_entry(entry)
            self.stats["discards"] += 1
            return True

    def _evict_one(self, protect: bytes) -> bool:
        """Oldest never-hit entry first; hit-proven entries (the shared
        prompts this cache exists for) survive churn from one-shot harvest
        inserts and go only when everything unproven is gone. The entry being
        inserted is protected so a proven-full cache still admits newcomers.
        False when nothing but the protected entry remains (caller stops)."""
        victim = next((k for k in self._entries
                       if k != protect and not self._hit_counts.get(k)), None)
        if victim is None:
            victim = next((k for k in self._entries if k != protect), None)
        if victim is None:
            return False
        snap = self._entries.pop(victim)
        self._hit_counts.pop(victim, None)
        self._used -= snap.nbytes()
        self._release_entry(snap)
        self.stats["evictions"] += 1
        return True

    def clear(self):
        with self._lock:
            for snap in self._entries.values():
                self._release_entry(snap)
            self._entries.clear()
            self._hit_counts.clear()
            self._used = 0
