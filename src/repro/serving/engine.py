"""Continuous-batching serving engine with preemption (context snapshot /
restore) -- the TPU data plane under the AIOS kernel's LLM core.

Fixed decode-slot batch: ``max_slots`` sequences decode together in one jit'd
step (shape-stable, no recompiles). Admission is *batched chunked prefill*:
every newly admitted sequence (and every prefix-cache suffix extension) joins
a per-engine prefill queue. In the default UNIFIED mode (``serve_step``),
every scheduler tick is ONE model dispatch: queued prefill jobs consume a
token chunk, every decoding slot rides in the same batch as a length-1 chunk
row at its current position (decode is the degenerate chunk), and untouched
slots are length-0 rows that ``prefill_chunk``'s per-row mask preserves
bit-for-bit -- so the separate decode dispatch AND its whole-tree
inactive-row keep-guard are gone. The legacy interleaved pair (one chunk
dispatch, then one guarded decode dispatch) remains as ``mixed_step=False``
-- the differential baseline the equivalence harness compares against.
Preemption extracts a slot's cache slice to host memory (a ContextSnapshot
-- the paper's logits-based context) and frees the slot.

Sampling invariants (what makes context switch bit-exact, paper Table 7):
  * every sequence has its own PRNG key; draw #n uses fold_in(key, n),
    independent of slot placement and batch composition;
  * ``next_tokens[slot]`` holds the *pending* token: sampled, not yet fed;
  * ``counter`` = number of tokens sampled so far = len(generated) + 1.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import build_model
from repro.obs.profiler import (KIND_DECODE, KIND_IMAGE, KIND_NAMES,
                                KIND_PACKED, KIND_PADDED, KIND_SERIAL,
                                KIND_SPEC)
from repro.obs.trace import PID_ENGINE
from repro.serving import sampler as smp
from repro.serving.paging import PageAllocator


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


def _ngram_draft(ctx: np.ndarray, k: int, n_max: int) -> List[int]:
    """Prompt-lookup / n-gram self-drafting: match the longest suffix n-gram
    of ``ctx`` (n_max down to 1) against the earlier context and propose up
    to ``k`` tokens that followed its most RECENT occurrence. Pure host
    numpy over one sequence's tokens -- no second model, no device work;
    agent traffic (tool-call loops, templated JSON, ReAct scaffolding) is
    repetitive enough that these drafts verify at high acceptance rates."""
    L = len(ctx)
    for n in range(min(n_max, L - 1), 0, -1):
        pat = ctx[L - n:]
        hay = ctx[:L - 1]        # windows that still have a continuation
        if len(hay) < n:
            continue
        win = np.lib.stride_tricks.sliding_window_view(hay, n)
        hits = np.nonzero((win == pat).all(axis=1))[0]
        if len(hits) == 0:
            continue
        i = int(hits[-1])
        return ctx[i + n:i + n + k].tolist()
    return []


@dataclasses.dataclass
class ContextSnapshot:
    """Paper §3.4 context. kind="logits": exact decode state (KV/recurrent
    slices + pending token). kind="text": token ids only; restore re-prefills
    (exact because prefill<->decode are consistent and sampling is replayed
    from the same per-sequence stream). kind="prefix": a prefix-cache entry
    (post-prefill KV slice + last-position logits; no sampling state -- the
    admitting sequence supplies its own key/counter).

    With a KVPageStore attached to the engine, the state travels as ``pages``
    (a PagedKV handle into the shared page table -- bytes owned and
    deduplicated by the store) instead of a private ``state`` blob; exactly
    one of the two is set for logits/prefix kinds."""
    kind: str
    prompt: np.ndarray
    generated: List[int]
    seq_len: int
    seq_key_data: Optional[np.ndarray] = None
    counter: int = 0
    state: Optional[List[np.ndarray]] = None
    pending_token: Optional[int] = None
    logits: Optional[np.ndarray] = None
    origin: Optional[int] = None   # engine_id that produced the state (the
                                   # control plane's prefix-affinity signal)
    pages: Optional[Any] = None    # PagedKV handle (page-store path)

    def nbytes(self) -> int:
        n = self.prompt.nbytes + 8 * len(self.generated)
        if self.state is not None:
            n += sum(v.nbytes for v in self.state)
        if self.pages is not None:
            n += self.pages.nbytes
        if self.logits is not None:
            n += self.logits.nbytes
        return n

    def release(self) -> None:
        """Return this snapshot's pages to the store (idempotent; no-op for
        legacy blob snapshots -- their bytes die with the object)."""
        if self.pages is not None:
            self.pages.release()


class _Slot:
    __slots__ = ("active", "prefilling", "seq_id", "prompt", "generated",
                 "counter", "max_new", "eos_id", "sink", "prefilled",
                 "pending_override")

    def __init__(self):
        self.active = False
        self.prefilling = False   # admitted, prompt not fully consumed yet
        self.seq_id = None
        self.prompt = None
        self.generated: List[int] = []
        self.counter = 0
        self.max_new = 0
        self.eos_id = -1
        self.prefilled = 0        # prompt tokens this admission actually
                                  # prefilled (prefix-cache hits subtract):
                                  # what tenant token metering settles
                                  # alongside generated tokens
        self.sink = None          # per-token callback (streaming syscalls):
                                  # called once per token appended to
                                  # `generated`, so a drained stream is
                                  # bit-equal to the blocking result
        self.pending_override = None   # text-kind restore under spec decode:
                                  # the snapshot's pending token is adopted
                                  # verbatim instead of re-drawn (a rejected-
                                  # draft residual draw is not reproducible
                                  # by the plain sampler)


class _PendingPrefill:
    """One queued chunked-prefill job: feed tokens[done:] into `slot` (the
    cache already holds the first `done` positions -- 0 for a fresh prompt,
    the restored prefix length for a prefix-cache suffix extension).
    ``image_embeds`` rides along for VLM prompts so image rows can join
    mixed chunk batches (stacked per dispatch, masked per row)."""
    __slots__ = ("slot", "tokens", "done", "fresh", "image_embeds")

    def __init__(self, slot: int, tokens: np.ndarray, done: int, fresh: bool,
                 image_embeds=None):
        self.slot = slot
        self.tokens = tokens
        self.done = done
        self.fresh = fresh        # False: prefix-cache suffix extension
        self.image_embeds = image_embeds


class _EngineJits:
    """One compiled program set per (model config, temperature). Every
    ServingEngine replica with the same key shares it (the cores of an
    ``LLMCorePool`` are identical), so adding a core to the pool never
    re-compiles XLA programs -- without this, the Nth core pays full
    prefill/decode compilation inside its first serving request.

    All programs are pure in (params, cache): per-engine state stays in the
    engine; shapes still specialize per call as usual."""

    # fixed chunk-size buckets for batched chunked prefill: one compiled
    # program per chunk size (per max_slots shape), shared across replicas
    PREFILL_CHUNKS = (32, 64, 128, 256)

    # total-token buckets for the packed ragged dispatch: the packed axis is
    # padded up to the next power of two so jit specialization stays bounded
    # (a handful of programs instead of one per total-token count)
    PACKED_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

    def __init__(self, cfg, temperature: float):
        self.model = model = build_model(cfg)
        _, logical = model.init_cache(1, 8)
        self.batch_axes = baxes = jax.tree.map(
            lambda l: l.index("batch") if "batch" in l else None,
            logical,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

        # every program that returns the engine's next cache takes the old
        # one by donation: at full width the cache is 1 GiB, and without
        # donation each tick holds the old and the new one at once
        donate = functools.partial(jax.jit, donate_argnames=("cache",))

        @donate
        def decode(params, tokens, cache, active_mask):
            new, logits = model.decode_step(params, tokens, cache)
            # inactive slots keep their ENTIRE cache row bit-for-bit: decoding
            # must not disturb half-prefilled neighbours (chunked prefill
            # interleaves with decode quanta) and pinned seq_lens can never
            # run away either. Costs ~17% of a CPU decode step (elementwise
            # select per leaf); a per-model-leaf guard could trim it but a
            # seq_lens sentinel alone is NOT enough -- rolling-buffer writes
            # (slot = seq_lens % Wn) wrap back into valid positions.
            def keep(n, o, ax):
                if ax is None:
                    return n
                shape = [1] * n.ndim
                shape[ax] = n.shape[ax]
                return jnp.where(active_mask.reshape(shape), n, o)
            cache = jax.tree.map(keep, new, cache, baxes)
            return cache, logits

        def insert(cache, piece, slot):
            def upd(leaf, src, ax):
                if ax is None:
                    return leaf
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, src.astype(leaf.dtype), slot, axis=ax)
            return jax.tree.map(upd, cache, piece, baxes)

        def extract(cache, slot):
            def get(leaf, ax):
                if ax is None:
                    return leaf
                return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
            return jax.tree.map(get, cache, baxes)

        @functools.partial(donate, static_argnames=("kv",))
        def prefill_chunk(params, tokens, cache, q_offset, lengths, kv):
            """Consume one token chunk for every queued sequence in a single
            dispatch, writing K/V (or recurrent state) straight into the
            cache at per-row position offsets. Decoding slots are length-1
            rows at their current position; rows with lengths == 0 are
            preserved bit-for-bit. `kv` (static) bounds the live context so
            attention/write cost tracks actual positions, not max_len."""
            return model.prefill_chunk(params, tokens, cache,
                                       q_offset=q_offset, lengths=lengths,
                                       kv_width=kv)

        @functools.partial(donate, static_argnames=("kv", "chunk"))
        def prefill_packed(params, tokens, cache, row_starts, q_offset,
                           lengths, kv, chunk):
            """Token-packed ragged chunk dispatch: ``tokens`` [Np] carries
            every participating row's chunk tokens concatenated (row r at
            packed positions row_starts[r] .. row_starts[r]+lengths[r]-1),
            so the model pays FLOPs for the real tokens in the dispatch --
            a decode row costs 1 packed slot, not a C-wide rectangle.
            ``chunk`` (static) is the padded bucket the dispatch would have
            used: the recurrent archs unpack to it internally (their packed
            path delegates), dense attention ignores it."""
            return model.prefill_packed(params, tokens, cache,
                                        row_starts=row_starts,
                                        q_offset=q_offset, lengths=lengths,
                                        chunk=chunk, kv_width=kv)

        @functools.partial(donate, static_argnames=("kv", "upto"))
        def prefill_chunk_spec(params, tokens, cache, q_offset, lengths, kv,
                               upto):
            """Chunk dispatch that ALSO returns per-position logits for the
            first ``upto`` chunk positions of every row -- the speculative
            verify surface: a decode row carrying [pending, d_1..d_m] gets
            the model's distribution after each consumed token, so the
            engine can accept a draft prefix and resample at the first
            rejection in one dispatch."""
            return model.prefill_chunk(params, tokens, cache,
                                       q_offset=q_offset, lengths=lengths,
                                       kv_width=kv, logits_upto=upto)

        @functools.partial(donate, static_argnames=("kv", "chunk", "upto"))
        def prefill_packed_spec(params, tokens, cache, row_starts, q_offset,
                                lengths, kv, chunk, upto):
            """Packed-axis twin of ``prefill_chunk_spec``: per-position
            verify logits gathered from each row's packed slots."""
            return model.prefill_packed(params, tokens, cache,
                                        row_starts=row_starts,
                                        q_offset=q_offset, lengths=lengths,
                                        chunk=chunk, kv_width=kv,
                                        logits_upto=upto)

        @functools.partial(donate, static_argnames=("kv", "chunk"))
        def prefill_packed_img(params, tokens, cache, row_starts, q_offset,
                               lengths, image_embeds, image_mask, kv, chunk):
            """Packed ragged dispatch carrying stacked frontend embeddings:
            masked rows recompute their image K/V (identical bytes to the
            padded layout -- image K/V is position-independent), so VLM
            image bursts keep the packed token savings instead of falling
            back to the [kb, C] rectangle."""
            return model.prefill_packed(params, tokens, cache,
                                        row_starts=row_starts,
                                        q_offset=q_offset, lengths=lengths,
                                        chunk=chunk, kv_width=kv,
                                        image_embeds=image_embeds,
                                        image_mask=image_mask)

        @functools.partial(donate, static_argnames=("kv",))
        def mixed_decode(params, tokens, cache, active_mask, kv):
            """Pure-decode tick of the unified serve path: every active slot
            is a length-1 chunk row at its own ``seq_lens`` position,
            inactive slots are length-0 rows that prefill_chunk's per-row
            mask preserves bit-for-bit -- the legacy decode program's
            whole-tree keep-guard, for free. Shape-stable ([max_slots]
            tokens, static kv bucket), so the host never syncs to build a
            batch: token routing happens device-side."""
            toks = jnp.where(active_mask, tokens, 0)[:, None]
            return model.prefill_chunk(
                params, toks, cache, q_offset=cache["seq_lens"],
                lengths=active_mask.astype(jnp.int32), kv_width=kv)

        @functools.partial(donate, static_argnames=("kv",))
        def prefill_chunk_img(params, tokens, cache, q_offset, lengths,
                              image_embeds, image_mask, kv):
            """Chunk dispatch with stacked frontend embeddings: rows flagged
            in image_mask recompute their image K/V from their row of the
            stack; text and decode rows keep their cached (or freshly
            zeroed) xk/xv -- what folds VLM prompts into mixed batches."""
            return model.prefill_chunk(params, tokens, cache,
                                       q_offset=q_offset, lengths=lengths,
                                       image_embeds=image_embeds,
                                       image_mask=image_mask, kv_width=kv)

        def gather_rows(cache, idx):
            """Compact the rows being prefilled into a small batch: the chunk
            program's cost scales with the burst, not max_slots."""
            def g(leaf, ax):
                if ax is None:
                    return leaf
                return jnp.take(leaf, idx, axis=ax)
            return jax.tree.map(g, cache, baxes)

        def scatter_rows(cache, piece, idx):
            def s(leaf, p, ax):
                if ax is None:
                    return leaf
                lm = jnp.moveaxis(leaf, ax, 0)
                lm = lm.at[idx].set(jnp.moveaxis(p, ax, 0).astype(lm.dtype))
                return jnp.moveaxis(lm, 0, ax)
            return jax.tree.map(s, cache, piece, baxes)

        def reset_rows(piece, zero, mask):
            """Reset masked rows of a gathered piece to pristine state
            (`zero` is a batch-1 init_cache tree, broadcast along batch):
            stateful models must not resume a fresh prompt from a previous
            occupant's recurrent carries."""
            def r(leaf, z, ax):
                if ax is None:
                    return leaf
                shape = [1] * leaf.ndim
                shape[ax] = leaf.shape[ax]
                return jnp.where(mask.reshape(shape), z.astype(leaf.dtype),
                                 leaf)
            return jax.tree.map(r, piece, zero, baxes)

        self.decode = decode
        self.prefill_packed = prefill_packed
        self.insert = donate(insert)
        self.extract = jax.jit(extract)
        self.prefill_chunk = prefill_chunk
        self.prefill_chunk_img = prefill_chunk_img
        self.prefill_chunk_spec = prefill_chunk_spec
        self.prefill_packed_spec = prefill_packed_spec
        self.prefill_packed_img = prefill_packed_img
        self.mixed_decode = mixed_decode
        self.gather_rows = jax.jit(gather_rows)
        self.scatter_rows = donate(scatter_rows)
        self.reset_rows = jax.jit(reset_rows)

        @donate
        def set_seq_len(cache, slot, value):
            return dict(cache, seq_lens=cache["seq_lens"].at[slot].set(value))
        self.set_len = set_seq_len

        @donate
        def set_seq_lens(cache, slots, values):
            """Batched seq_lens write -- the WHOLE speculative rollback:
            truncating a slot's seq_len to its committed position makes the
            rejected drafts' K/V unreachable (masked by the q_offset/causal
            masks, overwritten when the position is re-reached)."""
            return dict(cache,
                        seq_lens=cache["seq_lens"].at[slots].set(values))
        self.set_lens = set_seq_lens

        @jax.jit
        def prefill(params, tokens, cache, lengths):
            return model.prefill(params, tokens, cache, lengths=lengths)

        @jax.jit
        def prefill_img(params, tokens, cache, lengths, image_embeds):
            return model.prefill(params, tokens, cache, lengths=lengths,
                                 image_embeds=image_embeds)

        self.prefill = prefill
        self.prefill_img = prefill_img

        temp = temperature
        vocab = cfg.vocab

        @jax.jit
        def sample1(logits, key, counter):
            logits = smp.mask_padded_vocab(logits, vocab)
            return smp.sample(logits[None], key[None], counter[None], temp)[0]

        @jax.jit
        def sample_all(logits, keys, counters):
            logits = smp.mask_padded_vocab(logits, vocab)
            return smp.sample(logits, keys, counters, temp)

        @jax.jit
        def spec_verify(logits, draft, n_draft, keys, counters):
            logits = smp.mask_padded_vocab(logits, vocab)
            return smp.spec_verify(logits, draft, n_draft, keys, counters,
                                   temp)

        self.sample1 = sample1
        self.sample_all = sample_all
        self.spec_verify = spec_verify


_JIT_CACHE: Dict[Any, _EngineJits] = {}
_JIT_CACHE_LOCK = threading.Lock()


def _jits_for(cfg, temperature: float) -> _EngineJits:
    # the attention backend is read while a program traces, so programs
    # traced for one backend are never handed to an engine on another
    key = (repr(cfg), float(temperature), kops.default_backend())
    with _JIT_CACHE_LOCK:
        js = _JIT_CACHE.get(key)
        if js is None:
            js = _JIT_CACHE[key] = _EngineJits(cfg, temperature)
        return js


class ServingEngine:
    def __init__(self, cfg, *, max_slots: int = 8, max_len: int = 512,
                 temperature: float = 0.0, rng_seed: int = 0,
                 page_size: int = 16, hbm_pages: Optional[int] = None,
                 params=None, prefix_cache=None, serial_prefill: bool = False,
                 prefill_chunk_cap: Optional[int] = None, engine_id: int = 0,
                 page_store=None, mixed_step: Optional[bool] = None,
                 packed_step: Optional[bool] = None, tracer=None,
                 profiler=None, spec_decode: bool = False, spec_k: int = 4,
                 spec_ngram: int = 3):
        self.cfg = cfg
        # observability (repro.obs): both default OFF and cost one attribute
        # check per tick when off; per tick -- never per token -- when on
        self.tracer = tracer         # shared Tracer (engine tick spans)
        self.profiler = profiler     # per-engine TickProfiler ring
        if tracer is not None:
            tracer.name_track(PID_ENGINE, engine_id, f"core{engine_id}")
        self.engine_id = engine_id   # pool position; tags prefix-cache
                                     # entries for affinity routing
        self.serial_prefill = serial_prefill   # True: legacy one-sequence-
                                               # per-XLA-call prefill (the
                                               # baseline bench_prefill beats)
        # unified mixed prefill+decode dispatch: ONE model call per scheduler
        # tick (decode rows are length-1 chunks; no decode keep-guard).
        # Default ON except for the serial baseline; mixed_step=False keeps
        # the PR-2 interleaved chunk-then-decode pair for differential tests.
        self.mixed = (not serial_prefill) if mixed_step is None \
            else bool(mixed_step)
        # token-packed ragged dispatch: when a chunk dispatch's real tokens
        # fit a smaller packed bucket than rows x chunk, issue them on one
        # packed [total_tokens] axis instead of the padded [kb, C] rectangle.
        # Default ON (bitwise-identical layout change); packed_step=False is
        # the escape hatch AND the differential baseline the equivalence
        # harness compares against.
        self.packed = (not serial_prefill) if packed_step is None \
            else bool(packed_step)
        self.prefill_chunk_cap = prefill_chunk_cap   # smaller cap = tighter
                                               # decode-stall bound while a
                                               # long prompt admits
        self._jits = _jits_for(cfg, temperature)
        self.model = self._jits.model
        # speculative multi-token decoding: decode rows generalize from
        # length-1 to length-(1+m) chunk rows carrying self-drafted tokens,
        # verified in the SAME mixed dispatch; acceptance is exact-prefix
        # under greedy and distribution-identical residual sampling under
        # temperature. Default OFF (the differential baseline); requires the
        # unified mixed step and a rollback-capable arch (causal attention --
        # recurrent/rolling-buffer models gate out via supports_spec_decode).
        self.spec = bool(spec_decode) and self.mixed and \
            bool(getattr(self.model, "supports_spec_decode", False))
        self.spec_k = max(1, int(spec_k))        # max drafts per slot/tick
        self.spec_ngram = max(1, int(spec_ngram))  # longest suffix n-gram
        self.last_tick_commits: Dict[int, int] = {}   # slot -> tokens
                                               # committed last tick (the
                                               # scheduler's token-accurate
                                               # quantum accounting)
        self.max_slots = max_slots
        self.max_len = max_len
        self.temperature = temperature
        if params is None:
            # one program draws, scales and casts: the float32 draw never
            # exists as a whole leaf next to the bf16 params on the device
            params = jax.jit(lambda k: self.model.init_params(k)[0])(
                jax.random.key(rng_seed))
        self.params = params
        self.cache, self.cache_logical = self.model.init_cache(max_slots, max_len)
        self._batch_axes = self._jits.batch_axes
        self._piece_treedef = jax.tree.structure(self.cache)
        self.slots = [_Slot() for _ in range(max_slots)]
        self.seq_keys = jax.random.split(jax.random.key(rng_seed + 1), max_slots)
        self.counters = jnp.zeros((max_slots,), jnp.int32)
        self.next_tokens = jnp.zeros((max_slots,), jnp.int32)
        pages = hbm_pages if hbm_pages is not None else max_slots * (
            -(-max_len // page_size))
        self.pager = PageAllocator(pages, page_size)
        self._vlm = bool(getattr(self.model, "is_vlm", False))
        self.prefix_cache = prefix_cache   # shared PrefixCache or None
        self.page_store = page_store       # shared KVPageStore or None (the
                                           # legacy whole-blob snapshot path)
        self._last_logits = None           # device (max_slots, vocab), last step
        self._lock = threading.Lock()
        self._prefill_queue: List[_PendingPrefill] = []
        cap = min(max_len, prefill_chunk_cap or max_len)
        self.prefill_chunks = tuple(
            c for c in _EngineJits.PREFILL_CHUNKS if c <= cap) or \
            (_EngineJits.PREFILL_CHUNKS[0],)
        # coarse live-context buckets: each (batch, chunk, kv) combo is its
        # own XLA program, so kv granularity trades chunk FLOPs against
        # compile count (3 buckets keeps interactive workloads to a handful
        # of programs)
        self.kv_buckets = tuple(sorted({min(64, max_len), min(256, max_len),
                                        max_len}))
        self.stats = {"decode_steps": 0, "prefills": 0, "tokens": 0,
                      "preemptions": 0, "restores": 0,
                      "prefix_hits": 0, "prefix_saved_tokens": 0,
                      "prefix_extend_tokens": 0, "prefix_degraded": 0,
                      "prefill_chunks": 0, "prefill_bursts": 0,
                      "batched_prefill_tokens": 0,
                      # unified serve path: every model forward is counted in
                      # model_dispatches (the 2 -> 1 per-tick signal);
                      # mixed_steps counts unified dispatches, and
                      # mixed_decode_rows the decode tokens they carried
                      "model_dispatches": 0, "mixed_steps": 0,
                      "mixed_decode_rows": 0,
                      # token-packed dispatch: packed_tokens are the real
                      # tokens issued on the flat axis, packed_padded_tokens
                      # the padded [kb, C] cost they would have paid
                      "packed_dispatches": 0, "packed_tokens": 0,
                      "packed_padded_tokens": 0,
                      # speculative decoding: dispatches that carried draft
                      # rows, drafts proposed vs accepted, and drafts
                      # deferred because prefill debt owned the packed
                      # bucket that tick
                      "spec_dispatches": 0, "spec_draft_tokens": 0,
                      "spec_accepted_tokens": 0, "spec_deferred": 0}
        self._build_jits()
        self._init_paging_layout()

    def _init_paging_layout(self):
        """Token-axis layout of the cache tree: leaves whose logical axes
        include ``kv_seq`` spanning the full max_len (transformer K/V) are
        pageable; rolling buffers (kv_seq shorter than max_len), recurrent
        carries and seq_lens travel as un-paged residual. Also derives
        ``kv_bytes_per_token`` -- the control plane's migration cost unit --
        which is meaningful (non-zero) exactly when the model keeps
        token-indexed state."""
        def _is_label(x):
            return isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x)
        labels = jax.tree.leaves(self.cache_logical, is_leaf=_is_label)
        leaves = jax.tree.leaves(self._cache_b1)
        axes = []
        for leaf, lab in zip(leaves, labels):
            ax = lab.index("kv_seq") if "kv_seq" in lab else None
            if ax is not None and leaf.shape[ax] != self.max_len:
                ax = None
            axes.append(ax)
        self._time_axes = axes
        self.kv_bytes_per_token = sum(
            leaf.nbytes // leaf.shape[ax]
            for leaf, ax in zip(leaves, axes) if ax is not None)
        self.pager.bytes_per_token = self.kv_bytes_per_token
        self._layout_key = f"{self.cfg!r}|len{self.max_len}"
        if self.page_store is not None and self.kv_bytes_per_token == 0:
            # no token-indexed state at all (pure-recurrent model): every
            # byte would ride un-shared in the handle residual, pages would
            # be empty, and the spill tier could never demote the real
            # state. The legacy blob path (whole-snapshot pickle, bounded
            # by the context pool budget) is strictly better here.
            self.page_store = None
        if self.page_store is not None:
            self.page_store.register_layout(
                self._layout_key, axes,
                [tuple(leaf.shape) for leaf in leaves],
                [leaf.dtype for leaf in leaves],
                # page-boundary truncation shares the spec-decode rollback
                # contract: valid iff position t's cache depends only on
                # tokens <= t (pure positional K/V, no running carries)
                truncatable=bool(getattr(self.model, "supports_spec_decode",
                                         False)))

    def resident_bytes(self, slot: int) -> int:
        """KV bytes a slot's reserved pages pin in device memory -- the
        numerator of the rebalancer's migration cost model."""
        return (self.pager.held(f"slot{slot}") * self.pager.page_size *
                self.kv_bytes_per_token)

    @staticmethod
    def _state_leaves(snap):
        """Flat host leaves of a snapshot in either representation (legacy
        blob or page-store handle)."""
        return snap.state if snap.state is not None else snap.pages.leaves()

    @staticmethod
    def _unpin_hit(hit):
        """Balance the reference ``PrefixCache.lookup`` pinned on a paged
        entry (held across the lookup -> materialize window so a concurrent
        eviction cannot free the pages mid-read)."""
        pages = getattr(hit, "pages", None)
        if pages is not None:
            pages._store.unpin_pages(pages)

    def _materialize_hit(self, hit, *, seq_id=None):
        """Rebuild a prefix-cache snapshot as a device cache piece, or
        None when its backing state is unreadable -- page blobs swept by a
        sibling process, a corrupt page payload, the storage tier down
        mid-promote. The poisoned entry is discarded from the cache (so
        the next lookup cold-misses instead of rediscovering the corpse)
        and the caller degrades this admission to a cold prefill. The
        lookup's pin is always dropped, success or not."""
        try:
            leaves = [jnp.asarray(x) for x in self._state_leaves(hit)]
            cache1 = jax.tree.unflatten(self._piece_treedef, leaves)
        except Exception as e:  # noqa: BLE001
            self._unpin_hit(hit)
            self.stats["prefix_degraded"] += 1
            if self.prefix_cache is not None:
                try:
                    self.prefix_cache.discard(hit)
                except Exception:  # noqa: BLE001 -- already evicted
                    pass
            if self.tracer is not None:
                self.tracer.instant(
                    "prefix_degraded", PID_ENGINE, self.engine_id,
                    {"seq_id": seq_id, "err": str(e)[:120]})
            return None
        self._unpin_hit(hit)
        return cache1

    # -- jit'd primitives -------------------------------------------------------
    def _build_jits(self):
        js = self._jits
        self._decode_jit = js.decode
        self._insert_jit = js.insert
        self._extract_jit = js.extract
        self._set_len_jit = js.set_len
        self._prefill_jit = js.prefill
        self._prefill_img_jit = js.prefill_img
        self._prefill_chunk_jit = js.prefill_chunk
        self._prefill_chunk_img_jit = js.prefill_chunk_img
        self._prefill_chunk_spec_jit = js.prefill_chunk_spec
        self._prefill_packed_jit = js.prefill_packed
        self._prefill_packed_spec_jit = js.prefill_packed_spec
        self._prefill_packed_img_jit = js.prefill_packed_img
        self._mixed_decode_jit = js.mixed_decode
        self._gather_jit = js.gather_rows
        self._scatter_jit = js.scatter_rows
        self._reset_jit = js.reset_rows
        self._set_lens_jit = js.set_lens
        self._sample1_jit = js.sample1
        self._sample_all_jit = js.sample_all
        self._spec_verify_jit = js.spec_verify
        self._cache_b1, _ = self.model.init_cache(1, self.max_len)

    # -- slot management ----------------------------------------------------------
    def free_slot_count(self) -> int:
        return sum(not s.active for s in self.slots)

    def _find_free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if not s.active:
                return i
        return None

    def active_slots(self) -> List[int]:
        """Slots that decode this step (admitted AND done prefilling)."""
        return [i for i, s in enumerate(self.slots)
                if s.active and not s.prefilling]

    def is_prefilling(self, slot: int) -> bool:
        return self.slots[slot].prefilling

    def prefill_pending(self) -> int:
        """Sequences still consuming prompt chunks (queued prefill jobs)."""
        return len(self._prefill_queue)

    def prefill_debt(self) -> int:
        """Prompt tokens still to consume across all queued prefill jobs --
        the control plane's measure of admission work this core owes."""
        with self._lock:
            return sum(len(j.tokens) - j.done for j in self._prefill_queue)

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        return (self._find_free_slot() is not None and
                prompt_len + max_new <= self.max_len and
                self.pager.can_admit(prompt_len + max_new))

    # -- admission (batched chunked prefill) ----------------------------------------
    def add_sequence(self, prompt, *, seq_id=None, max_new: int = 32,
                     eos_id: int = -1, seq_key=None, image_embeds=None,
                     eager: bool = True, sink=None) -> int:
        return self.add_sequences(
            [dict(prompt=prompt, seq_id=seq_id, max_new=max_new,
                  eos_id=eos_id, seq_key=seq_key, image_embeds=image_embeds,
                  sink=sink)],
            eager=eager)[0]

    def add_sequences(self, requests, *, eager: bool = True) -> List[int]:
        """Admit a burst of sequences. Each request is a dict with ``prompt``
        plus optional ``seq_id``/``max_new``/``eos_id``/``seq_key``/
        ``image_embeds``. Exact prefix-cache hits activate immediately;
        everything else (fresh prompts AND prefix suffix extensions) joins
        the chunked-prefill queue so the whole burst shares one XLA dispatch
        per chunk. With ``eager`` the queue is drained before returning;
        ``eager=False`` lets the caller interleave ``prefill_step()`` with
        decode ``step()`` (the BatchedScheduler worker loop).

        Raises on the first request that cannot be admitted; requests before
        it in the burst stay admitted (and, with ``eager``, prefilled)."""
        slots: List[int] = []
        if len(requests) > 1:
            self.stats["prefill_bursts"] += 1
        admitted, err = [], None
        for r in requests:
            prompt = np.asarray(r["prompt"], dtype=np.int32)
            P = len(prompt)
            max_new = r.get("max_new", 32)
            with self._lock:
                slot = self._find_free_slot()
                if slot is None:
                    err = RuntimeError("no free decode slot")
                    break
                if P + max_new > self.max_len:
                    err = RuntimeError(
                        f"context {P + max_new} > max_len {self.max_len}")
                    break
                if not self.pager.reserve(f"slot{slot}", P + max_new):
                    err = RuntimeError("HBM pages exhausted")
                    break
                s = self.slots[slot]
                s.active = True
                s.prefilling = False
                s.seq_id = r.get("seq_id")
                s.prompt = prompt
                s.generated = []
                s.counter = 0
                s.max_new = max_new
                s.eos_id = r.get("eos_id", -1)
                s.sink = r.get("sink")
                s.prefilled = P   # prefix-hit paths below subtract
                s.pending_override = None
            seq_key = r.get("seq_key")
            if seq_key is None:
                seq_key = jax.random.key(
                    (int(np.sum(prompt)) * 2654435761 + P) % (2**31))
            admitted.append((slot, r, prompt, seq_key))
            slots.append(slot)
        if err is not None:
            # callers of a partially-admitted burst still need handles to the
            # live slots (to drain/free them) -- attach them to the error
            err.admitted_slots = list(slots)
        if not admitted:
            if err is not None:
                raise err
            return []
        # one batched bookkeeping dispatch for the whole burst
        idx = jnp.asarray([a[0] for a in admitted], jnp.int32)
        self.seq_keys = self.seq_keys.at[idx].set(
            jnp.stack([a[3] for a in admitted]))
        self.counters = self.counters.at[idx].set(0)
        for slot, r, prompt, _ in admitted:
            P = len(prompt)
            image_embeds = r.get("image_embeds")
            hit = None
            if self.prefix_cache is not None and image_embeds is None:
                hit = self.prefix_cache.lookup(prompt)
            # materialize the cached state up front: a hit whose pages are
            # GONE (swept by a sibling process, corrupt blob, storage
            # fault) degrades to hit=None -- the cold-prefill branches
            # below -- instead of crashing admission
            cache1 = None
            exact = (hit is not None and hit.seq_len == P
                     and hit.logits is not None)
            if hit is not None and (exact or not self.serial_prefill):
                cache1 = self._materialize_hit(hit, seq_id=r.get("seq_id"))
                if cache1 is None:
                    hit = None
                    exact = False
            if exact:
                # exact hit: restore the cached cache slice + logits, no
                # prompt tokens left to consume. (A truncated disk
                # re-hydration carries NO logits -- even a length-exact one
                # takes the extension path below so its last token
                # re-prefills and yields them.)
                self._activate_slot(slot, cache1, jnp.asarray(hit.logits))
                self.slots[slot].prefilled = 0
                self.stats["prefix_hits"] += 1
                self.stats["prefix_saved_tokens"] += hit.seq_len
                if self.tracer is not None:
                    self.tracer.instant(
                        "prefix_hit", PID_ENGINE, self.engine_id,
                        {"seq_id": r.get("seq_id"), "saved": hit.seq_len,
                         "exact": True})
            elif hit is not None and not self.serial_prefill:
                # suffix extension: restore the prefix, then chunk-prefill
                # only prompt[done:] (ONE chunked-prefill job, not
                # token-scan decode chunks). Safe for VLM rows too: the
                # inserted piece carries the conversation's own image K/V.
                # done is clamped to P-1 so a logits-free hit (truncated
                # re-hydration) re-prefills at least its last token -- a
                # deterministic identical K/V rewrite that yields the
                # last-position logits activation needs.
                done = min(int(hit.seq_len), P - 1)
                self.cache = self._insert_jit(self.cache, cache1, slot)
                # a truncated entry's residual seq_lens still carries the
                # LONGER source prefix's length -- pin it to the tokens the
                # pages actually cover before any attention reads it
                self.cache = self._set_len_jit(self.cache, slot,
                                               jnp.int32(done))
                self.stats["prefix_hits"] += 1
                self.stats["prefix_saved_tokens"] += done
                self.stats["prefix_extend_tokens"] += P - done
                if self.tracer is not None:
                    self.tracer.instant(
                        "prefix_hit", PID_ENGINE, self.engine_id,
                        {"seq_id": r.get("seq_id"), "saved": done,
                         "extend": P - done, "exact": False})
                self.slots[slot].prefilled = P - done
                self._enqueue_prefill(slot, prompt, done=done,
                                      fresh=False)
            elif self.serial_prefill:
                if hit is not None:     # looked up but not used: unpin
                    self._unpin_hit(hit)
                # legacy path: one full single-sequence prefill per XLA call
                # (kept as the bench_prefill baseline)
                self._prefill_into(slot, prompt, image_embeds=image_embeds)
                self.stats["prefills"] += 1
            elif eager and len(admitted) == 1 and not self._prefill_queue:
                # burst of one with nothing to share a dispatch with: the
                # plain single-sequence prefill beats a padded chunk dispatch
                # (non-eager singles still enqueue -- they can join chunks of
                # work already in flight)
                self._prefill_into(slot, prompt, image_embeds=image_embeds)
                self.stats["prefills"] += 1
            else:
                # fresh prompts -- VLM image prompts included -- join the
                # chunked queue: image embeds are stacked per dispatch and
                # masked per row, and fresh rows of models that carry state
                # across chunks (recurrent carries, rolling buffers, image
                # K/V) are reset batch-wise before their first chunk
                self.stats["prefills"] += 1
                self._enqueue_prefill(slot, prompt, done=0, fresh=True,
                                      image_embeds=image_embeds)
        if eager:
            while self._prefill_queue:
                self.prefill_step()
        if err is not None:       # the rejected request; earlier ones are live
            raise err
        return slots

    def _enqueue_prefill(self, slot: int, tokens: np.ndarray, *, done: int,
                         fresh: bool, image_embeds=None):
        # (fresh rows of stateful/VLM models are reset batch-wise inside the
        # chunk dispatch, right after the gather)
        self.slots[slot].prefilling = True
        with self._lock:
            self._prefill_queue.append(
                _PendingPrefill(slot, np.asarray(tokens, np.int32), done,
                                fresh, image_embeds))

    def prefill_step(self) -> List[int]:
        """Consume ONE token chunk for every queued prefill job in a single
        batched dispatch -- the decode-free case of ``_mixed_dispatch``
        (small bursts are compacted gather -> chunk -> scatter into a
        power-of-two batch bucket; the chunk size is the smallest compiled
        bucket covering the longest remaining prompt; the live-context
        width is bucketed statically). Returns the slots whose prompt
        completed this call -- they are activated (pending token sampled)
        and, when a prefix cache is attached, their post-prefill state is
        cached for reuse."""
        with self._lock:
            jobs = list(self._prefill_queue)
        if not jobs:
            return []
        self._mixed_dispatch(jobs, decode=())
        return [j.slot for j in jobs if j.done >= len(j.tokens)]

    def _stack_images(self, rows_jobs, kb: int):
        """Stack the image embeddings of a dispatch's jobs into one
        [kb, T, d] buffer + per-row mask (rows without an image ride as
        zeros and keep their cached xk/xv). Returns (None, None) when no
        job carries an image -- the plain chunk program then leaves every
        row's frontend K/V untouched."""
        with_img = [(r, j) for r, j in rows_jobs if j.image_embeds is not None]
        if not with_img:
            return None, None
        first = np.asarray(with_img[0][1].image_embeds)
        T, d = first.shape[-2], first.shape[-1]
        stack = np.zeros((kb, T, d), first.dtype)
        mask = np.zeros((kb,), bool)
        for r, j in with_img:
            stack[r] = np.asarray(j.image_embeds).reshape(T, d)
            mask[r] = True
        return jnp.asarray(stack), jnp.asarray(mask)

    def warmup(self, buckets=None) -> int:
        """Pre-compile the serving program set: every (batch-bucket, chunk,
        kv-width) combo of the chunked-prefill grid plus the decode /
        sampling / gather-scatter programs they feed -- the combos a bursty
        agent workload hits mid-measurement otherwise. Programs land in the
        process-wide ``_EngineJits`` cache, so every replica sharing this
        engine's (config, temperature) key is warmed too; repeat calls only
        pay the (small) warm-run compute.

        ``buckets`` narrows the grid to the given chunk sizes (default: all
        of ``self.prefill_chunks``). The prefix cache is detached while
        warming so warm prompts never become cache entries. Returns the
        number of warm admissions run."""
        chunks = tuple(buckets) if buckets else self.prefill_chunks
        lens = sorted({min(c - 8, self.max_len - 2) for c in chunks})
        if buckets is None and self.max_len >= 72:
            lens.append(self.max_len - 40)   # exercise the top kv bucket
        lens = [L for L in lens if L >= 1 and L + 2 <= self.max_len]
        pc, self.prefix_cache = self.prefix_cache, None
        ran = 0

        def _drain(slots):
            while any(not self.is_done(s) for s in slots):
                self.step()
            for s in slots:
                self.free(s)

        try:
            rng = np.random.default_rng(4242)

            def prompt(L):
                return rng.integers(1, self.cfg.vocab - 1, L).astype(np.int32)

            # chunked-prefill grid: every (batch-bucket, chunk, kv) combo.
            # eager=False even for n == 1 -- that is the scheduler-worker
            # admission path (eager singles would take the serial program
            # instead and leave the kb=1 chunk programs cold)
            n = 1
            while n <= self.max_slots:
                for L in lens:
                    slots = self.add_sequences(
                        [dict(prompt=prompt(L), max_new=1)
                         for _ in range(n)], eager=False)
                    while self.prefill_pending():
                        self.prefill_step()
                    _drain(slots)
                    ran += n
                n *= 2
            # mixed-dispatch pass (unified serve path): a runner decodes
            # while a burst admits, so the chunk programs that carry BOTH
            # prefill rows and length-1 decode rows compile here (the
            # C == 1 pure-decode grid was already warmed by the drains
            # above, which route through the mixed step)
            if self.mixed and self.max_slots >= 2:
                runner = self.add_sequence(prompt(lens[0]),
                                           max_new=2 * len(lens) + 2)
                self.step()
                nb = min(2, self.max_slots - 1)
                for L in lens:
                    slots = self.add_sequences(
                        [dict(prompt=prompt(L), max_new=1)
                         for _ in range(nb)], eager=False)
                    while self.prefill_pending():
                        self.serve_step()
                    _drain(slots)
                    ran += nb
                _drain([runner])
                ran += 1
            # finishing-size pass: a chunk's FINISHING row count is not
            # bucketed (any 1..max_slots rows can complete together), and
            # the activation ops specialize on it -- without this a size-5
            # finish stalls the serving loop on a mid-run compile
            for n in range(1, self.max_slots + 1):
                if n & (n - 1) == 0:
                    continue               # covered by the grid pass
                slots = self.add_sequences(
                    [dict(prompt=prompt(lens[0]), max_new=1)
                     for _ in range(n)], eager=False)
                while self.prefill_pending():
                    self.prefill_step()
                _drain(slots)
                ran += n
            # serial single-sequence prefill (eager singles, VLM prompts,
            # text-mode restores), one program per prompt-length bucket
            for L in lens:
                _drain([self.add_sequence(prompt(L), max_new=1)])
                ran += 1
            # context-switch programs (extract / insert / set_len): one
            # suspend-restore round trip
            slot = self.add_sequence(prompt(lens[0]), max_new=2)
            self.step()
            snap = self.snapshot(slot)
            slot = self.restore(snap)
            snap.release()   # warm pages must not linger in the store
            _drain([slot])
            ran += 1
            # speculative pass (best-effort): a repetitive prompt makes the
            # n-gram drafter fire, compiling the verify programs (the spec
            # tick routes packed vs padded by the same bucket logic as live
            # traffic, so whichever variant production would hit warms)
            if self.spec:
                pat = np.tile(prompt(4), lens[0] // 4 + 1)[:lens[0]]
                slot = self.add_sequence(pat.astype(np.int32),
                                         max_new=2 * self.spec_k + 4)
                while not self.is_done(slot):
                    self.serve_step()
                self.free(slot)
                ran += 1
        finally:
            self.prefix_cache = pc
        return ran

    def _prefill_into(self, slot: int, tokens: np.ndarray, *, image_embeds=None):
        """Prefill `tokens` into `slot`'s cache and sample the pending token
        with the slot's current counter (draw #counter). A text prompt on a
        VLM model prefills against zero frontend embeddings: zero image K/V
        is the "no image" context (cross-attention contributes exactly 0),
        bit-identical to the chunked path's freshly reset xk/xv rows."""
        P = len(tokens)
        _t0 = self._obs_t0()
        Spad = min(_bucket(P), self.max_len)
        buf = np.zeros((1, Spad), np.int32)
        buf[0, :P] = tokens
        lengths = jnp.array([P], jnp.int32)
        cacheable = image_embeds is None
        if image_embeds is None and self._vlm:
            image_embeds = jnp.zeros(
                (1, self.cfg.num_frontend_tokens, self.cfg.d_model),
                self.cfg.dtype)
        if image_embeds is not None:
            cache1, logits = self._prefill_img_jit(
                self.params, jnp.asarray(buf), self._cache_b1, lengths,
                image_embeds)
        else:
            cache1, logits = self._prefill_jit(
                self.params, jnp.asarray(buf), self._cache_b1, lengths)
        self.stats["model_dispatches"] += 1
        if cacheable and self.prefix_cache is not None:
            self._cache_prefix(tokens, cache1, logits[0])
        self._activate_slot(slot, cache1, logits[0])
        if _t0:
            self._obs_tick(KIND_SERIAL, _t0, _t0, 1, 1, Spad, Spad, P, Spad)

    def _activate_slot(self, slot: int, cache1, logits_vec):
        """Insert a ready batch-1 cache into `slot` and sample its pending
        token with the slot's own key/counter -- the sampling protocol that
        keeps prefill, restore and prefix-cache admission bit-identical."""
        self.cache = self._insert_jit(self.cache, cache1, slot)
        self._activate_in_place(slot, logits_vec)

    def _activate_in_place(self, slot: int, logits_vec):
        """Sample `slot`'s pending token from its last-position logits (the
        cache row is already in place -- chunked prefill writes it directly)
        and mark the slot ready to decode. A restore that stashed a
        ``pending_override`` (text-kind snapshot under speculative decoding)
        adopts that token verbatim instead -- the snapshot's pending may be
        a rejected-draft residual draw the plain sampler cannot replay."""
        s = self.slots[slot]
        s.prefilling = False
        if s.pending_override is not None:
            self.next_tokens = self.next_tokens.at[slot].set(
                jnp.int32(s.pending_override))
            s.pending_override = None
        else:
            pending = self._sample1_jit(logits_vec, self.seq_keys[slot],
                                        jnp.int32(s.counter))
            self.next_tokens = self.next_tokens.at[slot].set(pending)
            s.counter += 1
        self.counters = self.counters.at[slot].set(s.counter)

    # -- prefix cache (restore, then chunk-prefill the suffix) --------------------
    def _cache_prefix(self, tokens: np.ndarray, cache1, logits_vec):
        """Store a batch-1 cache tree + last-position logits under `tokens`.
        Legacy path: leaves stay on device as a private blob. Page-store
        path: the state is paged into the shared table at the device tier
        (charged against the store's PageAllocator budget), so prefixes that
        agree share pages with each other and with the contexts extending
        them, and the entry is write-through persisted for cross-process
        re-hydration."""
        tokens = np.asarray(tokens, np.int32)
        if self.page_store is not None:
            handle = self.page_store.put(
                self._layout_key, jax.tree.leaves(cache1),
                seq_len=len(tokens), origin=self.engine_id, device=True)
            snap = ContextSnapshot(
                kind="prefix", prompt=tokens.copy(), generated=[],
                seq_len=len(tokens), pages=handle,
                logits=np.asarray(logits_vec), origin=self.engine_id)
            if not self.prefix_cache.insert(snap):
                handle.release()
            return
        snap = ContextSnapshot(
            kind="prefix", prompt=tokens.copy(),
            generated=[], seq_len=len(tokens),
            state=list(jax.tree.leaves(cache1)), logits=logits_vec,
            origin=self.engine_id)
        self.prefix_cache.insert(snap)

    def harvest_prefix(self, slot: int):
        """Cache a finishing sequence's full context (prompt + generation) so
        the grown multi-turn resubmission extends instead of re-prefilling.
        Call after the finishing step, before free()."""
        if self.prefix_cache is None or self._last_logits is None:
            return
        s = self.slots[slot]
        if not s.active or not s.generated:
            return
        tokens = np.concatenate([s.prompt, np.asarray(s.generated, np.int32)])
        piece = self._extract_jit(self.cache, slot)
        self._cache_prefix(tokens, piece, jnp.asarray(self._last_logits[slot]))

    # -- observability ----------------------------------------------------------------
    def _obs_t0(self) -> float:
        """Tick start stamp when any observer is attached, else 0.0 (the
        single-branch fast path for untraced engines)."""
        if self.profiler is None and self.tracer is None:
            return 0.0
        return time.perf_counter()

    def _obs_tick(self, kind: int, t0: float, t_build: float, rows: int,
                  kb: int, chunk: int, kv: int, tokens: int,
                  padded: int) -> None:
        """Close one tick sample: ring-buffer scalar stores for the profiler
        plus (when tracing) one engine-lane span. Wall time is host-observed;
        the engine syncs on the NEXT tick's pending-token read, so
        steady-state tick walls are honest without adding a device sync."""
        t1 = time.perf_counter()
        if self.profiler is not None:
            self.profiler.record(kind, t1 - t0, t_build - t0, rows, kb,
                                 chunk, kv, int(tokens), int(padded))
        tr = self.tracer
        if tr is not None:
            dur = (t1 - t0) * 1e6
            tr.complete("tick", PID_ENGINE, self.engine_id,
                        tr.now_us() - dur, dur,
                        {"kind": KIND_NAMES[kind], "rows": rows, "kb": kb,
                         "chunk": chunk, "kv": kv, "tokens": int(tokens)})

    # -- decode / unified serve ------------------------------------------------------
    def step(self) -> Dict[int, int]:
        """One decode step for all active slots: feed each slot's pending
        token (appending it to `generated`) and sample the next pending.
        Returns {slot: token appended this step}. In mixed mode this is the
        degenerate C == 1 chunk dispatch -- no decode program, no whole-tree
        keep-guard (inactive slots are length-0 rows of the per-row mask)."""
        self.last_tick_commits = {}
        active = self.active_slots()
        if not active:
            return {}
        _t0 = self._obs_t0()
        kvb = self.max_len
        mask_np = np.zeros(self.max_slots, bool)
        mask_np[active] = True
        mask = jnp.asarray(mask_np)
        tokens = self.next_tokens
        if self.mixed:
            # min() with max_len: a slot decoding past the cache edge keeps
            # stepping with its write dropped by the position mask, exactly
            # like the legacy decode program's out-of-range token write
            max_end = min(self.max_len,
                          1 + max(len(self.slots[i].prompt) +
                                  len(self.slots[i].generated)
                                  for i in active))
            kv = kvb = next(b for b in self.kv_buckets if b >= max_end)
            self.cache, logits = self._mixed_decode_jit(
                self.params, tokens, self.cache, mask, kv=kv)
            self.stats["mixed_steps"] += 1
            self.stats["mixed_decode_rows"] += len(active)
        else:
            self.cache, logits = self._decode_jit(self.params, tokens,
                                                  self.cache, mask)
        self._last_logits = logits
        nxt = self._sample_all_jit(logits, self.seq_keys, self.counters)
        tok_host = np.asarray(tokens)
        emitted: Dict[int, int] = {}
        for i in active:
            s = self.slots[i]
            t = int(tok_host[i])
            s.generated.append(t)
            if s.sink is not None:
                s.sink(t)
            s.counter += 1
            emitted[i] = t
            self.pager.grow(f"slot{i}", len(s.prompt) + len(s.generated) + 1)
        self.next_tokens = jnp.where(mask, nxt, self.next_tokens)
        self.counters = self.counters + mask.astype(jnp.int32)
        self.stats["decode_steps"] += 1
        self.stats["model_dispatches"] += 1
        self.stats["tokens"] += len(active)
        if _t0:
            self._obs_tick(KIND_DECODE, _t0, _t0, len(active),
                           self.max_slots, 1, kvb, len(active),
                           self.max_slots)
        return emitted

    def serve_step(self) -> Dict[int, int]:
        """One scheduler tick. Mixed mode (the default): every queued
        prefill job consumes a chunk AND every decoding slot advances one
        token in a SINGLE model dispatch. Legacy mode: the PR-2 interleaved
        pair (one chunk dispatch if work is queued, then one guarded decode
        dispatch). Per-sequence token streams are identical either way --
        rows are independent -- which is exactly what the serving-equivalence
        harness asserts. Returns {slot: LAST decode token appended this
        tick} (with speculative decoding a slot can commit several --
        ``last_tick_commits`` has the per-slot counts).

        With ``spec_decode`` on, each decoding slot first proposes up to
        ``spec_k`` self-drafted tokens (n-gram lookup over its own
        prompt+generated stream); slots with drafts ride the dispatch as
        length-(1+m) chunk rows and the whole [pending, drafts] run is
        verified in that ONE model call. Ticks where no slot drafts keep
        the shape-stable pure-decode program -- the spec path costs nothing
        when traffic is not repetitive."""
        self.last_tick_commits = {}
        if not self.mixed:
            if self.prefill_pending():
                self.prefill_step()
            return self.step()
        with self._lock:
            jobs = list(self._prefill_queue)
        if self.spec:
            active = self.active_slots()
            if active:
                drafts = self._propose_drafts(
                    active, np.asarray(self.next_tokens))
                if drafts:
                    return self._mixed_dispatch(jobs, drafts=drafts)
        if not jobs:
            return self.step()     # shape-stable device-routed decode tick
        return self._mixed_dispatch(jobs)

    def _propose_drafts(self, active: List[int],
                        pend_host: np.ndarray) -> Dict[int, List[int]]:
        """Self-draft proposals for this tick: per decoding slot, an n-gram
        lookup over [prompt, generated, pending] proposes up to spec_k
        continuation tokens. Clamps keep every possible commit legal: no
        drafting past max_new - 1 (the pending itself is one commit), past
        the cache edge, or past a pending EOS; a drafted EOS truncates the
        draft (it may be the last element)."""
        drafts: Dict[int, List[int]] = {}
        for slot in active:
            s = self.slots[slot]
            pend = int(pend_host[slot])
            if pend == s.eos_id:
                continue
            budget = min(self.spec_k,
                         s.max_new - len(s.generated) - 1,
                         self.max_len - (len(s.prompt) + len(s.generated)
                                         + 1))
            if budget <= 0:
                continue
            ctx = np.concatenate(
                [s.prompt, np.asarray(s.generated + [pend], np.int32)])
            d = _ngram_draft(ctx, budget, self.spec_ngram)
            if not d:
                continue
            if s.eos_id >= 0 and s.eos_id in d:
                d = d[:d.index(s.eos_id) + 1]
            drafts[slot] = d
        return drafts

    def _mixed_dispatch(self, jobs: List[_PendingPrefill],
                        decode=None, drafts=None) -> Dict[int, int]:
        """The unified dispatch: prefill rows (one chunk each), decode rows
        (length-1 chunks at their current position -- bit-identical to
        decode_step) and untouched rows (length 0, preserved bit-for-bit by
        prefill_chunk's per-row mask) in ONE model call. ``decode`` is the
        set of slots that advance one token this call -- None means every
        active slot (the serve tick); ``prefill_step`` passes () so BOTH
        modes share this one batch-build/bookkeeping pipeline and cannot
        drift apart.

        ``drafts`` ({slot: [draft tokens]}) generalizes decode rows from
        length-1 to length-(1+m) chunks: the row carries [pending, d_1..d_m],
        the model scores every position in this same call, and the verified
        prefix commits at once. Rejected drafts roll back by seq_len
        truncation alone -- stale K/V beyond the committed position is
        masked out and overwritten when the position is genuinely reached.

        When the participants fill most of the batch the dispatch runs on
        the full cache -- the shape the legacy decode program also paid,
        minus its whole-tree keep-guard; a small burst on a mostly-idle
        engine is gathered into a power-of-two bucket so cost tracks the
        work, not max_slots."""
        active = self.active_slots() if decode is None else list(decode)
        if not jobs and not active:
            return {}
        drafts = dict(drafts) if drafts else {}
        if drafts and any(j.image_embeds is not None for j in jobs):
            # no image x spec program variants: image ticks are rare and
            # drafts re-propose next tick, so defer rather than double the
            # compiled-program grid
            self.stats["spec_deferred"] += len(drafts)
            drafts = {}
        _t0 = self._obs_t0()
        _t_build = _t0
        _kind = KIND_PADDED
        if jobs:
            rem = max(len(j.tokens) - j.done for j in jobs)
            C = next((b for b in self.prefill_chunks if b >= rem),
                     self.prefill_chunks[-1])
        elif drafts:
            # draft-only tick: the chunk axis only needs 1 + m_max slots --
            # next power of two keeps the program count at log2(spec_k)
            need = 1 + max(len(d) for d in drafts.values())
            C = 1
            while C < need:
                C *= 2
        else:
            C = 1
        for slot in list(drafts):   # a draft never outgrows the chunk row
            drafts[slot] = drafts[slot][:C - 1]
            if not drafts[slot]:
                del drafts[slot]
        part = [j.slot for j in jobs] + active
        kb = 1
        while kb < len(part):
            kb *= 2
        if kb >= self.max_slots:
            kb = self.max_slots
            idx = None                      # full batch: row == slot
            row_of = {s: s for s in part}
        else:
            idx = list(part)
            spare = [i for i in range(self.max_slots) if i not in set(idx)]
            idx += spare[:kb - len(idx)]
            row_of = {s: r for r, s in enumerate(part)}
        if drafts and jobs and self.packed:
            # draft-length budget vs prefill debt: when the tick carries
            # prefill chunks, drafts ride free only if they don't push the
            # packed token axis into a LARGER bucket -- prefill throughput
            # (the paid-for debt) outranks speculative upside
            al = kops.packed_row_align()

            def _ptot(with_drafts: bool) -> int:
                tot = 0
                for j in jobs:
                    n = min(len(j.tokens) - j.done, C)
                    tot += -(-n // al) * al
                for slot in active:
                    n = 1 + (len(drafts.get(slot, ()))
                             if with_drafts else 0)
                    tot += -(-n // al) * al
                return tot

            b0 = next((b for b in _EngineJits.PACKED_BUCKETS
                       if b >= max(_ptot(False), 1)), None)
            b1 = next((b for b in _EngineJits.PACKED_BUCKETS
                       if b >= max(_ptot(True), 1)), None)
            if b0 is not None and b0 < kb * C and b1 != b0:
                self.stats["spec_deferred"] += len(drafts)
                drafts = {}
        spec = bool(drafts)
        upto = min(C, self.spec_k + 1) if spec else None
        buf = np.zeros((kb, C), np.int32)
        lengths = np.zeros((kb,), np.int32)
        offsets = np.zeros((kb,), np.int32)
        fresh = np.zeros((kb,), bool)
        job_rows = []
        for j in jobs:
            r = row_of[j.slot]
            n = min(len(j.tokens) - j.done, C)
            buf[r, :n] = j.tokens[j.done:j.done + n]
            lengths[r] = n
            offsets[r] = j.done
            fresh[r] = j.fresh and j.done == 0
            job_rows.append((r, j, n))
        if active:          # pure-prefill dispatches never sync the device
            pend_host = np.asarray(self.next_tokens)
        for slot in active:
            r = row_of[slot]
            s = self.slots[slot]
            d = drafts.get(slot, ())
            buf[r, 0] = pend_host[slot]
            if d:
                buf[r, 1:1 + len(d)] = d
            lengths[r] = 1 + len(d)
            offsets[r] = len(s.prompt) + len(s.generated)
        max_end = min(self.max_len, int((offsets + lengths).max()))
        kv = next(b for b in self.kv_buckets if b >= max_end)
        if idx is None:
            piece = self.cache
        else:
            idx_arr = jnp.asarray(np.asarray(idx, np.int32))
            piece = self._gather_jit(self.cache, idx_arr)
        if self.model.reset_fresh_rows and fresh.any():
            piece = self._reset_jit(piece, self._cache_b1,
                                    jnp.asarray(fresh))
        img, imask = self._stack_images(
            [(row_of[j.slot], j) for j in jobs], kb)
        # token-packed ragged dispatch: when the real tokens fit a
        # packed bucket smaller than the [kb, C] rectangle, issue them
        # on one flat axis -- a decode row costs 1 token, a 7-token
        # tail chunk costs 7, not C. Row segments are aligned to the
        # packed kernel's q block on a Pallas backend so block rows
        # never straddle two sequences; the gap slots carry zero pad
        # tokens that the per-row length mask kills. Image rows join the
        # packed axis too (their TEXT tokens pack; the frontend embeddings
        # stay a per-row dense tensor -- padded-within-packed).
        align = kops.packed_row_align()
        row_starts = np.zeros((kb,), np.int32)
        cur = 0
        for r in range(kb):
            row_starts[r] = cur
            cur += -(-int(lengths[r]) // align) * align
        Npb = next((b for b in _EngineJits.PACKED_BUCKETS
                    if b >= max(cur, 1)), None)
        use_packed = self.packed and Npb is not None and Npb < kb * C
        pos_logits = None
        if use_packed:
            flat = np.zeros((Npb,), np.int32)
            for r in range(kb):
                n = int(lengths[r])
                if n:
                    flat[row_starts[r]:row_starts[r] + n] = buf[r, :n]
        if _t0:
            _t_build = time.perf_counter()
        if img is not None:
            _kind = KIND_IMAGE
            if use_packed:
                piece, logits = self._prefill_packed_img_jit(
                    self.params, jnp.asarray(flat), piece,
                    jnp.asarray(row_starts), jnp.asarray(offsets),
                    jnp.asarray(lengths), img, imask, kv=kv, chunk=C)
            else:
                piece, logits = self._prefill_chunk_img_jit(
                    self.params, jnp.asarray(buf), piece,
                    jnp.asarray(offsets), jnp.asarray(lengths), img, imask,
                    kv=kv)
        elif spec:
            _kind = KIND_SPEC
            if use_packed:
                piece, logits, pos_logits = self._prefill_packed_spec_jit(
                    self.params, jnp.asarray(flat), piece,
                    jnp.asarray(row_starts), jnp.asarray(offsets),
                    jnp.asarray(lengths), kv=kv, chunk=C, upto=upto)
            else:
                piece, logits, pos_logits = self._prefill_chunk_spec_jit(
                    self.params, jnp.asarray(buf), piece,
                    jnp.asarray(offsets), jnp.asarray(lengths), kv=kv,
                    upto=upto)
        elif use_packed:
            _kind = KIND_PACKED
            piece, logits = self._prefill_packed_jit(
                self.params, jnp.asarray(flat), piece,
                jnp.asarray(row_starts), jnp.asarray(offsets),
                jnp.asarray(lengths), kv=kv, chunk=C)
        else:
            piece, logits = self._prefill_chunk_jit(
                self.params, jnp.asarray(buf), piece,
                jnp.asarray(offsets), jnp.asarray(lengths), kv=kv)
        if use_packed:
            self.stats["packed_dispatches"] += 1
            self.stats["packed_tokens"] += int(lengths.sum())
            self.stats["packed_padded_tokens"] += kb * C
        if idx is None:
            self.cache = piece
        else:
            self.cache = self._scatter_jit(self.cache, piece, idx_arr)
        self.stats["model_dispatches"] += 1
        if self.mixed:      # unified-path dispatch (legacy engines reuse
            self.stats["mixed_steps"] += 1   # this pipeline for prefill only)
        # prefill bookkeeping
        fin = []
        for r, j, n in job_rows:
            j.done += n
            if j.done >= len(j.tokens):
                fin.append((r, j))
        if jobs:
            self.stats["prefill_chunks"] += 1
            self.stats["batched_prefill_tokens"] += int(
                sum(n for _, _, n in job_rows))
        # one sampling dispatch for finishing-prefill rows AND decode rows:
        # per-row key/counter math identical to the legacy samplers. Spec
        # ticks split the two (decode rows verify against per-position
        # logits instead of sampling one token).
        sample_rows = [r for r, _ in fin]
        sample_slots = [j.slot for _, j in fin]
        if not spec:
            sample_rows += [row_of[s] for s in active]
            sample_slots += active
        emitted: Dict[int, int] = {}
        if sample_rows:
            sl = jnp.asarray(sample_slots, jnp.int32)
            rows_arr = jnp.asarray(sample_rows, jnp.int32)
            picked = logits[rows_arr]
            pend = self._sample_all_jit(picked, self.seq_keys[sl],
                                        self.counters[sl])
            self.next_tokens = self.next_tokens.at[sl].set(pend)
            new_counters = []
            for _, j in fin:
                s = self.slots[j.slot]
                s.prefilling = False
                if s.pending_override is not None:
                    # text-kind restore under spec: adopt the snapshot's
                    # pending verbatim (see _activate_in_place)
                    self.next_tokens = self.next_tokens.at[j.slot].set(
                        jnp.int32(s.pending_override))
                    s.pending_override = None
                else:
                    s.counter += 1
                new_counters.append(s.counter)
            if not spec:
                for slot in active:
                    s = self.slots[slot]
                    t = int(pend_host[slot])
                    s.generated.append(t)
                    if s.sink is not None:
                        s.sink(t)
                    s.counter += 1
                    new_counters.append(s.counter)
                    emitted[slot] = t
                    self.pager.grow(f"slot{slot}",
                                    len(s.prompt) + len(s.generated) + 1)
            self.counters = self.counters.at[sl].set(
                jnp.asarray(new_counters, jnp.int32))
            # keep per-slot last-position logits fresh (harvest_prefix reads
            # them), mirroring what the legacy decode dispatch kept
            if (self._last_logits is None or
                    self._last_logits.shape != (self.max_slots,
                                                logits.shape[-1])):
                self._last_logits = jnp.zeros(
                    (self.max_slots, logits.shape[-1]), logits.dtype)
            self._last_logits = self._last_logits.at[sl].set(picked)
        if spec:
            # speculative commit: one verify dispatch scores every decode
            # row's [pending, d_1..d_m] run; the accepted prefix (plus the
            # pending itself) commits in order, the next pending comes out
            # of the same call, and seq_lens truncation erases the rest
            srows = jnp.asarray([row_of[s] for s in active], jnp.int32)
            ssl = jnp.asarray(active, jnp.int32)
            m_arr = np.zeros((len(active),), np.int32)
            dbuf = np.zeros((len(active), upto - 1), np.int32)
            for i, slot in enumerate(active):
                d = drafts.get(slot, ())
                m_arr[i] = len(d)
                if d:
                    dbuf[i, :len(d)] = d
            n_acc_d, pend_d = self._spec_verify_jit(
                pos_logits[srows], jnp.asarray(dbuf), jnp.asarray(m_arr),
                self.seq_keys[ssl], self.counters[ssl])
            n_acc = np.asarray(n_acc_d)
            self.next_tokens = self.next_tokens.at[ssl].set(pend_d)
            new_counters = []
            new_lens = []
            tot_commit = 0
            for i, slot in enumerate(active):
                s = self.slots[slot]
                d = drafts.get(slot, ())
                commit = [int(pend_host[slot])] + list(d[:int(n_acc[i])])
                for t in commit:
                    s.generated.append(t)
                    if s.sink is not None:
                        s.sink(t)
                s.counter += len(commit)   # draws consumed: n_acc + 1
                new_counters.append(s.counter)
                emitted[slot] = commit[-1]
                self.last_tick_commits[slot] = len(commit)
                tot_commit += len(commit)
                new_lens.append(len(s.prompt) + len(s.generated))
                self.pager.grow(f"slot{slot}",
                                len(s.prompt) + len(s.generated) + 1)
            self.counters = self.counters.at[ssl].set(
                jnp.asarray(new_counters, jnp.int32))
            # ROLLBACK: the model wrote seq_len = offset + 1 + m; truncate
            # every spec row to its committed position
            self.cache = self._set_lens_jit(
                self.cache, ssl, jnp.asarray(new_lens, jnp.int32))
            if (self._last_logits is None or
                    self._last_logits.shape != (self.max_slots,
                                                logits.shape[-1])):
                self._last_logits = jnp.zeros(
                    (self.max_slots, logits.shape[-1]), logits.dtype)
            self._last_logits = self._last_logits.at[ssl].set(
                pos_logits[srows, n_acc_d])
            self.stats["spec_dispatches"] += 1
            self.stats["spec_draft_tokens"] += int(m_arr.sum())
            self.stats["spec_accepted_tokens"] += int(n_acc.sum())
            self.stats["tokens"] += tot_commit
            if self.tracer is not None:
                self.tracer.instant(
                    "spec", PID_ENGINE, self.engine_id,
                    {"rows": len(active), "drafted": int(m_arr.sum()),
                     "accepted": int(n_acc.sum())})
        if active:
            self.stats["decode_steps"] += 1
            if not spec:
                self.stats["tokens"] += len(active)
            self.stats["mixed_decode_rows"] += len(active)
        if self.prefix_cache is not None:
            for r, j in fin:
                if j.image_embeds is not None:
                    continue   # token keys cannot name an image's K/V
                piece1 = self._extract_jit(self.cache, j.slot)
                self._cache_prefix(j.tokens, piece1, logits[r])
        if fin:
            with self._lock:
                done_set = {j.slot for _, j in fin}
                self._prefill_queue = [jj for jj in self._prefill_queue
                                       if jj.slot not in done_set]
        if _t0:
            self._obs_tick(_kind, _t0, _t_build, len(part), kb, C, kv,
                           int(lengths.sum()), kb * C)
        return emitted

    def probe_failed_load(self, prompt) -> None:
        """The 'without AIOS' trial-and-error cost (paper §1): speculatively
        load a prompt with no admission control -- a real prefill's worth of
        compute is burned and the result discarded, as when a GPU load OOMs."""
        prompt = np.asarray(prompt, dtype=np.int32)
        P = len(prompt)
        Spad = min(_bucket(P), self.max_len)
        buf = np.zeros((1, Spad), np.int32)
        buf[0, :P] = prompt
        _, logits = self._prefill_jit(self.params, jnp.asarray(buf),
                                      self._cache_b1,
                                      jnp.array([P], jnp.int32))
        jax.block_until_ready(logits)
        self.stats["model_dispatches"] += 1
        self.stats.setdefault("failed_loads", 0)
        self.stats["failed_loads"] += 1

    def is_done(self, slot: int) -> bool:
        s = self.slots[slot]
        if not s.active:
            return True
        if s.prefilling:
            return False
        if len(s.generated) >= s.max_new:
            return True
        return bool(s.generated) and s.generated[-1] == s.eos_id

    def result(self, slot: int) -> List[int]:
        return list(self.slots[slot].generated)

    def free(self, slot: int):
        with self._lock:
            self.slots[slot].active = False
            self.slots[slot].prefilling = False
            self.slots[slot].sink = None
            self._prefill_queue = [j for j in self._prefill_queue
                                   if j.slot != slot]
            self.pager.release(f"slot{slot}")
            self.cache = self._set_len_jit(self.cache, slot, 0)

    # -- context switch (paper §3.4) ---------------------------------------------
    def snapshot(self, slot: int, *, kind: str = "logits") -> ContextSnapshot:
        """Suspend a sequence: capture its state and free the slot."""
        s = self.slots[slot]
        assert s.active and not s.prefilling
        state = pages = None
        seq_len = len(s.prompt) + len(s.generated)
        pending = int(self.next_tokens[slot])
        if kind == "logits":
            piece = self._extract_jit(self.cache, slot)
            leaves = [np.asarray(x) for x in jax.tree.leaves(piece)]
            if self.page_store is not None:
                # suspend state enters the page table at the host tier: the
                # pages covering a cached prefix of this context dedupe
                # against the prefix entry's pages (copy-on-write sharing)
                pages = self.page_store.put(self._layout_key, leaves,
                                            seq_len=seq_len,
                                            origin=self.engine_id)
            else:
                state = leaves
        snap = ContextSnapshot(
            kind=kind, prompt=s.prompt.copy(), generated=list(s.generated),
            seq_len=seq_len,
            seq_key_data=np.asarray(jax.random.key_data(self.seq_keys[slot])),
            counter=s.counter, state=state, pending_token=pending,
            pages=pages, origin=self.engine_id)
        max_new, eos = s.max_new, s.eos_id
        snap.max_new, snap.eos_id = max_new, eos  # dynamic attrs for callers
        self.free(slot)
        self.stats["preemptions"] += 1
        return snap

    def restore(self, snap: ContextSnapshot, *, seq_id=None,
                eager: bool = True, sink=None) -> int:
        """Resume a suspended sequence into a free slot (exact continuation).
        A text-kind snapshot re-prefills its context; with ``eager=False``
        that re-prefill only joins the chunked queue, so a scheduler worker
        can interleave it with decode instead of stalling on a full
        prefill."""
        with self._lock:
            slot = self._find_free_slot()
            if slot is None:
                raise RuntimeError("no free decode slot")
            if not self.pager.reserve(f"slot{slot}", snap.seq_len + 1):
                raise RuntimeError("HBM pages exhausted")
            s = self.slots[slot]
            s.active = True
            s.seq_id = seq_id
            s.prompt = snap.prompt
            s.generated = list(snap.generated)
            s.max_new = getattr(snap, "max_new", 32)
            s.eos_id = getattr(snap, "eos_id", -1)
            s.sink = sink   # snapshots never carry the channel: already-
                            # streamed tokens live in `generated`, only NEW
                            # tokens flow (exactly-once across migrations)
            s.prefilled = 0   # a resume re-materializes state it already
                              # paid for at first admission: tenant token
                              # metering must not double-charge the prompt
            s.pending_override = None
        key = jax.random.wrap_key_data(jnp.asarray(snap.seq_key_data))
        self.seq_keys = self.seq_keys.at[slot].set(key)
        if snap.kind == "logits":
            piece = jax.tree.unflatten(
                self._piece_treedef,
                [jnp.asarray(x) for x in self._state_leaves(snap)])
            self.cache = self._insert_jit(self.cache, piece, slot)
            self.next_tokens = self.next_tokens.at[slot].set(snap.pending_token)
            s.counter = snap.counter
            self.counters = self.counters.at[slot].set(snap.counter)
        else:  # text-based: re-prefill prompt + generated prefix, re-draw pending
            if self.spec and snap.pending_token is not None:
                # a spec stream's pending may be a rejected-draft residual
                # draw: not reproducible by the plain sampler, so the
                # snapshot's token is adopted verbatim after the re-prefill
                s.counter = snap.counter
                s.pending_override = int(snap.pending_token)
            else:
                s.counter = snap.counter - 1   # pending token is re-drawn
            self.counters = self.counters.at[slot].set(s.counter)
            ctx = np.concatenate([snap.prompt,
                                  np.asarray(snap.generated, np.int32)]) \
                if snap.generated else snap.prompt
            # (VLM text-kind restores re-prefill against zero image K/V on
            # both paths -- the snapshot kind does not carry embeddings)
            if self.serial_prefill:
                self._prefill_into(slot, ctx)
            else:
                self._enqueue_prefill(slot, ctx, done=0, fresh=True)
                while eager and self.slots[slot].prefilling:
                    self.prefill_step()
        self.stats["restores"] += 1
        return slot
