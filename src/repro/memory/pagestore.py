"""KVPageStore: the single page-granular owner of every KV byte outside a
live decode slot (AIOS §3.5 -- the kernel, not the callers, owns LLM memory).

Before this store, KV lived in three disconnected holders: `serving/paging.py`
counted pages with no identity, `core/context.py` snapshotted whole contexts
as opaque host blobs, and `serving/prefix_cache.py` kept its own byte-budgeted
LRU of full snapshots. Now a context snapshot, a prefix-cache entry and a
migration hand-off are all *page lists* (``PagedKV`` handles) into one table:

  * identical token prefixes dedupe to the same pages (content-addressed ids),
    so a cached prefix and the conversations extending it share bytes
    copy-on-write instead of duplicating snapshots;
  * device-resident pages are charged against a ``PageAllocator`` budget (the
    serving layer's existing accounting mechanism) and demote to host RAM
    under pressure; host bytes run under a separate watermark and demote to
    the storage manager's blob tier;
  * prefix pages are write-through persisted (page blobs + a token-key
    manifest), so a fresh process -- a second ``AIOSKernel`` on the same
    storage root -- re-hydrates hot prefixes from disk instead of
    re-prefilling them.

Paging is along the token axis: an engine registers a *layout* describing
which flat cache leaves carry a full-context time axis (the transformer K/V
leaves); those are sliced into ``page_size``-token pages. Everything else
(rolling attention buffers, seq_lens, VLM frontend K/V) travels un-paged in
the handle's ``residual`` -- tracked in ``residual_bytes``, but only paged
bytes can demote under the watermark; models with NO token-indexed state at
all (pure-recurrent) skip the store entirely at the engine and keep the
legacy blob path. Restores rebuild full-width leaves with zeros
beyond ``seq_len``; attention masks those positions, so generated tokens are
bit-identical to the legacy whole-blob path (asserted by tests and
bench_memory on every run).
"""
from __future__ import annotations

import hashlib
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.memory.pagetable import KVPage, PageTable
from repro.obs.trace import PID_MEMORY as _PID_MEMORY
from repro.obs.trace import span
from repro.serving.paging import PageAllocator

# int8 per-channel scales are stored bf16 (fp32 exponent range, 2 bytes --
# jax ships ml_dtypes); fall back to fp32 where unavailable
try:
    import ml_dtypes
    _SCALE_DTYPE = np.dtype(ml_dtypes.bfloat16)
except Exception:               # noqa: BLE001
    _SCALE_DTYPE = np.dtype(np.float32)


class PageLayout:
    """Which flat leaves of a cache tree are pageable, and how to rebuild
    them. ``time_axes[i]`` is the token-axis index of leaf i (None = travels
    in the residual); shapes/dtypes describe the full batch-1 leaves."""

    __slots__ = ("key", "time_axes", "shapes", "dtypes", "paged_idx",
                 "residual_idx", "bytes_per_token", "truncatable")

    def __init__(self, key: str, time_axes: Sequence[Optional[int]],
                 shapes: Sequence[Tuple[int, ...]], dtypes: Sequence[Any],
                 truncatable: bool = False):
        self.key = key
        # a page-boundary cut of this layout is a valid shorter context:
        # true for pure positional K/V (token t's pages depend only on
        # tokens <= t), false when residual leaves carry running state
        # (recurrent carries, rolling windows) that no page cut can rewind
        self.truncatable = bool(truncatable)
        self.time_axes = list(time_axes)
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = list(dtypes)
        self.paged_idx = [i for i, a in enumerate(self.time_axes)
                          if a is not None]
        self.residual_idx = [i for i, a in enumerate(self.time_axes)
                             if a is None]
        bpt = 0
        for i in self.paged_idx:
            n = int(np.prod(self.shapes[i])) * np.dtype(self.dtypes[i]).itemsize
            bpt += n // self.shapes[i][self.time_axes[i]]
        self.bytes_per_token = bpt


class PagedKV:
    """Handle to one snapshot's pages: what a context, a prefix entry or a
    migration hand-off holds instead of raw bytes. ``nbytes`` is the
    handle's *attributed* size (all pages counted fully -- deterministic for
    LRU accounting; the dedup saving shows up in the store's stats, and the
    real RAM budgets are enforced store-side where shared pages count once).
    Release is idempotent."""

    __slots__ = ("layout_key", "page_ids", "residual", "seq_len", "nbytes",
                 "_store", "_released")

    def __init__(self, store: "KVPageStore", layout_key: str,
                 page_ids: List[str], residual: List[np.ndarray],
                 seq_len: int, nbytes: int):
        self._store = store
        self.layout_key = layout_key
        self.page_ids = page_ids
        self.residual = residual
        self.seq_len = seq_len
        self.nbytes = nbytes
        self._released = False

    def leaves(self) -> List[np.ndarray]:
        """Rebuild the full flat leaf list (promoting disk pages)."""
        return self._store.leaves(self)

    def release(self) -> None:
        self._store.release(self)


class PagedPrefixEntry:
    """A prefix-cache entry re-hydrated from the disk manifest of another
    process (or an earlier life of this one). Duck-types the slice of
    ``ContextSnapshot`` the engine and cache touch (kept un-imported to stay
    free of a serving dependency)."""

    kind = "prefix"

    def __init__(self, prompt: np.ndarray, seq_len: int, pages: PagedKV,
                 logits: np.ndarray, origin: Optional[int]):
        self.prompt = prompt
        self.generated: List[int] = []
        self.seq_len = seq_len
        self.pages = pages
        self.logits = logits
        self.origin = origin
        self.state = None

    def nbytes(self) -> int:
        n = self.prompt.nbytes + self.pages.nbytes
        if self.logits is not None:
            n += self.logits.nbytes
        return n

    def release(self) -> None:
        self.pages.release()


class KVPageStore:
    """Facade over the page table + tier budgets + the storage KV namespace.

    ``device_pages``/``page_size`` size the device budget (a PageAllocator --
    the same reservation mechanism serving admission uses, so device-resident
    prefix bytes are *accounted*, not hoped for); ``host_budget_bytes`` is
    the host watermark; ``storage`` (a StorageManager) enables the disk tier
    and cross-process prefix persistence."""

    def __init__(self, *, page_size: int = 16, device_pages: int = 1024,
                 host_budget_bytes: int = 256 << 20, storage=None,
                 persist: bool = True, index_ttl_s: float = 1.0,
                 max_manifests: int = 1024, kv_quant: str = "off",
                 gate_tokens: int = 4):
        assert page_size > 0
        assert kv_quant in ("off", "int8"), kv_quant
        self.page_size = page_size
        # precision as a tier property: with kv_quant="int8", pages landing
        # on (or demoting to) the host/disk tiers hold int8 data plus
        # per-channel scales; device-resident pages always stay full
        # precision. Quantization happens EXACTLY ONCE, always from the
        # original fp bytes -- a promoted page keeps its int8 form in host
        # RAM (dequantized only at leaves()), so error never compounds.
        self.kv_quant = kv_quant
        self.gate_tokens = max(1, gate_tokens)   # prefix-probe gate depth
        self._gate: Optional[Tuple[set, List[int]]] = None
        self.max_manifests = max_manifests   # persisted-prefix cap: oldest
                                             # manifests prune FIFO so a
                                             # long-running kernel's disk
                                             # index stays bounded
        self.table = PageTable()
        self.device_pager = PageAllocator(max(1, device_pages), page_size)
        self.host_budget_bytes = host_budget_bytes
        self.storage = storage
        self.persist_enabled = persist and storage is not None
        self.index_ttl_s = index_ttl_s   # manifest-index staleness bound:
                                         # how quickly another process's
                                         # inserts become visible here
        self._index_cache: Optional[Dict[str, int]] = None
        self._index_time = float("-inf")
        self._layouts: Dict[str, PageLayout] = {}
        self._host_used = 0
        self._device_bytes = 0
        self._residual_bytes = 0   # un-paged leaf bytes riding in handles
                                   # (tracked for visibility; only paged
                                   # bytes can demote under the watermark)
        self._clock = 0
        self.tracer = None   # repro.obs.Tracer (set by the kernel); tier
                             # moves emit instants on the memory lane
        self.stats = {
            "put_handles": 0, "put_pages": 0, "put_bytes": 0, "dedup_hits": 0,
            "dedup_saved_bytes": 0, "released_handles": 0, "freed_pages": 0,
            "retired_pages": 0, "demotions_host": 0, "demotions_disk": 0,
            "promotions": 0, "persisted_entries": 0, "rehydrated_entries": 0,
            "device_rejections": 0, "gc_swept_blobs": 0, "gc_runs": 0,
            "quantized_pages": 0, "quant_saved_bytes": 0, "gated_probes": 0,
            "truncated_rehydrates": 0, "corrupt_manifests": 0,
            "index_errors": 0, "persist_errors": 0,
        }
        self._beacon_thread: Optional[threading.Thread] = None
        self._beacon_stop: Optional[threading.Event] = None

    # -- layouts -----------------------------------------------------------------
    def register_layout(self, key: str, time_axes: Sequence[Optional[int]],
                        shapes: Sequence[Tuple[int, ...]],
                        dtypes: Sequence[Any],
                        truncatable: bool = False) -> PageLayout:
        with self.table.lock:
            lay = self._layouts.get(key)
            if lay is None:
                lay = self._layouts[key] = PageLayout(key, time_axes, shapes,
                                                      dtypes, truncatable)
            return lay

    def layout(self, key: str) -> Optional[PageLayout]:
        return self._layouts.get(key)

    # -- internals (caller holds table.lock) -------------------------------------
    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    @staticmethod
    def _digest(layout_key: str, slices: List[np.ndarray]) -> str:
        h = hashlib.sha1(layout_key.encode())
        for a in slices:
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        return h.hexdigest()

    def _charge_device(self, pid: str, width: int) -> bool:
        return self.device_pager.reserve(pid, width)

    # -- int8 tier precision -------------------------------------------------------
    @staticmethod
    def _quantize_slices(slices, taxes):
        """Symmetric per-channel int8: the scale reduces over the TIME axis
        only (shape = slice shape with that axis at size 1), so every
        channel keeps its own dynamic range across the page's tokens.
        All-zero channels get scale 1 (quantize to exact zeros). Scales are
        stored bf16 (fp32 range, 2 bytes): a bf16-rounded scale shifts
        q = clip(rint(f/s*127)) by at most one step -- noise the int8
        rounding already carries -- and halving the per-channel metadata is
        what keeps the bytes win near 2x for bf16 source caches."""
        qs, scales = [], []
        for a, ax in zip(slices, taxes):
            f = np.asarray(a, np.float32)
            s = np.max(np.abs(f), axis=ax, keepdims=True)
            s = np.where(s == 0.0, 1.0, s)
            s = np.asarray(s, _SCALE_DTYPE)
            qs.append(np.clip(np.rint(f / s.astype(np.float32) * 127.0),
                              -127, 127).astype(np.int8))
            scales.append(s)
        return qs, scales

    @staticmethod
    def _page_leaf(page: KVPage, j: int, dtype) -> np.ndarray:
        """Slice j of a page in the layout's dtype, dequantizing int8
        pages on the way out."""
        a = page.data[j]
        if page.scales is not None:
            a = (a.astype(np.float32)
                 * (page.scales[j].astype(np.float32) / 127.0))
        return np.asarray(a, dtype)

    @staticmethod
    def _data_bytes(page: KVPage) -> int:
        """Actual bytes of the page's CURRENT in-RAM representation (0 on
        the disk tier) -- what host/device watermarks charge. Equals
        page.nbytes for fp pages; smaller for quantized ones."""
        if page.data is None:
            return 0
        n = sum(a.nbytes for a in page.data)
        if page.scales is not None:
            n += sum(a.nbytes for a in page.scales)
        return n

    def _quantize_page(self, page: KVPage) -> None:
        """In-place demotion of a page's precision (fp -> int8 + scales).
        Only ever called on pages still holding ORIGINAL fp data; the
        caller re-charges the owning tier with the new _data_bytes."""
        if page.scales is not None or page.data is None \
                or page.taxes is None:
            return
        qs, scales = self._quantize_slices(page.data, page.taxes)
        page.data, page.scales = qs, scales
        self.stats["quantized_pages"] += 1
        self.stats["quant_saved_bytes"] += page.nbytes - \
            self._data_bytes(page)
        if self.tracer is not None:
            self.tracer.instant("quantize", _PID_MEMORY, 0,
                                {"pid": page.pid, "bytes": page.nbytes,
                                 "now": self._data_bytes(page)})

    def _make_page(self, pid: str, slices: List[np.ndarray], width: int,
                   origin: Optional[int], want_device: bool,
                   taxes=None) -> KVPage:
        nbytes = sum(a.nbytes for a in slices)
        tier = "host"
        if want_device:
            if self._charge_device(pid, width):
                tier = "device"
            else:
                # device budget full: demote the LRU device page to host and
                # take its reservation; if nothing is demotable, fall through
                # to the host tier (accounted there instead)
                victims = self.table.by_lru("device")
                if victims:
                    self._demote_device_to_host(victims[0])
                if self._charge_device(pid, width):
                    tier = "device"
                else:
                    self.stats["device_rejections"] += 1
        page = KVPage(pid, slices, nbytes, width, origin, tier)
        page.taxes = tuple(taxes) if taxes is not None else None
        page.last_use = self._tick()
        if tier == "device":
            self._device_bytes += nbytes
        else:
            # landing off-device: quantize straight from the original fp
            # slices before the host watermark is charged
            if self.kv_quant == "int8":
                self._quantize_page(page)
            self._host_used += self._data_bytes(page)
        self.table.add(page)
        self.stats["put_pages"] += 1
        return page

    def _demote_device_to_host(self, page: KVPage) -> None:
        self.device_pager.release(page.pid)
        self._device_bytes -= page.nbytes   # device copies are always fp
        if self.kv_quant == "int8":
            self._quantize_page(page)
        page.tier = "host"
        self._host_used += self._data_bytes(page)
        self.stats["demotions_host"] += 1
        if self.tracer is not None:
            self.tracer.instant("demote_host", _PID_MEMORY, 0,
                                {"pid": page.pid,
                                 "bytes": self._data_bytes(page)})

    def _flush(self, page: KVPage) -> bool:
        """Write the page's disk blob. Versioned format: v2 is a dict
        ``{"v": 2, "q": "off"|"int8", "data": [...], "scales": ...,
        "taxes": ...}``; v1 blobs (a bare leaf list) are still readable by
        ``_promote``. Under kv_quant="int8" a still-fp (device-tier) page
        quantizes a COPY into the blob only -- its resident data stays full
        precision."""
        if page.flushed:
            return True
        if self.storage is None or page.data is None:
            return False
        data, scales = page.data, page.scales
        if scales is None and self.kv_quant == "int8" \
                and page.taxes is not None:
            data, scales = self._quantize_slices(page.data, page.taxes)
        payload = {"v": 2, "q": "off" if scales is None else "int8",
                   "data": data, "scales": scales, "taxes": page.taxes}
        self.storage.kv_page_save(page.pid, pickle.dumps(payload))
        page.flushed = True
        return True

    def _demote_to_disk(self, page: KVPage) -> bool:
        if not self._flush(page):
            return False
        if page.tier == "device":
            self.device_pager.release(page.pid)
            self._device_bytes -= page.nbytes
        elif page.tier == "host":
            self._host_used -= self._data_bytes(page)
        page.data = None
        page.scales = None
        page.tier = "disk"
        self.stats["demotions_disk"] += 1
        if self.tracer is not None:
            self.tracer.instant("demote_disk", _PID_MEMORY, 0,
                                {"pid": page.pid, "bytes": page.nbytes})
        return True

    def _free(self, page: KVPage) -> None:
        # the disk BLOB (if any) is left in place even for non-durable
        # pages: blobs are content-addressed and shared by identity, so a
        # persisted manifest in another process (or a retired durable page
        # re-put as non-durable) may still list this pid -- deleting here
        # would poison its re-hydration. ``gc_orphan_blobs`` reclaims the
        # unreferenced ones (mark-and-sweep over surviving manifests).
        if page.tier == "device":
            self.device_pager.release(page.pid)
            self._device_bytes -= page.nbytes
        elif page.tier == "host":
            self._host_used -= self._data_bytes(page)
        self.table.remove(page.pid)
        self.stats["freed_pages"] += 1

    def _retire(self, page: KVPage) -> None:
        """Drop a refcount-0 DURABLE page from the in-RAM table, keeping its
        disk blob (it is listed in a persisted manifest, so a later
        rehydrate recreates the table entry from the manifest metadata).
        Without this the table would accumulate one dead KVPage per evicted
        prefix page forever."""
        if not self._flush(page):
            # disk tier guarantees the blob exists; host/device pages flush
            # here. A durable page always has a storage to flush to.
            if page.tier != "disk":
                return
        if page.tier == "device":
            self.device_pager.release(page.pid)
            self._device_bytes -= page.nbytes
        elif page.tier == "host":
            self._host_used -= self._data_bytes(page)
        self.table.remove(page.pid)
        self.stats["retired_pages"] += 1

    def _drop_ref(self, pid: str) -> None:
        """Decrement one reference; refcount-0 pages retire (durable: blob
        stays, table entry goes) or free (everything else). Caller holds
        table.lock."""
        page = self.table.get(pid)
        if page is None:
            return
        self.table.decref(pid)
        if page.refs > 0:
            return
        if page.durable:
            self._retire(page)
        else:
            self._free(page)

    def _enforce_host_budget(self, pinned: set) -> None:
        if self._host_used <= self.host_budget_bytes:
            return
        with span("aios.pagestore.demote", tracer=self.tracer) as sp:
            # demote LRU host pages to disk; refcount-0 persisted pages
            # first (their blob already exists), then referenced ones
            # (prefix entries / suspended contexts re-hydrate on next use)
            victims = sorted(self.table.by_lru("host"),
                             key=lambda p: (p.refs > 0, p.last_use))
            pages = to_disk = 0
            for page in victims:
                if self._host_used <= self.host_budget_bytes:
                    break
                if page.pid in pinned:
                    continue
                if page.refs == 0:
                    if page.durable:
                        self._retire(page)
                    else:
                        self._free(page)
                    continue
                if self._demote_to_disk(page):
                    pages += 1
                    to_disk += page.nbytes
                # else no storage tier attached: page stays resident
            sp.set(pages=pages, bytes_to_disk=to_disk)

    # -- put / leaves / release ----------------------------------------------------
    def put(self, layout_key: str, leaves: Sequence[Any], *, seq_len: int,
            origin: Optional[int] = None, device: bool = False) -> PagedKV:
        """Page a flat leaf list (a batch-1 cache slice) covering token
        positions [0, seq_len). Identical content dedups against resident
        pages; new pages enter at the device tier when ``device`` (prefix
        entries -- device-resident on real hardware) else host (suspend
        snapshots)."""
        lay = self._layouts[layout_key]
        ps = self.page_size
        # no pageable leaves -> no pages (an empty-slice page per range
        # would alias to one degenerate pid); everything rides residual
        npages = -(-max(0, int(seq_len)) // ps) if lay.paged_idx else 0
        with span("aios.pagestore.put", tracer=self.tracer,
                  pages=npages) as sp:
            host = [np.asarray(x) for x in leaves]
            page_ids: List[str] = []
            new = 0
            with self.table.lock:
                for p in range(npages):
                    t0 = p * ps
                    width = min(ps, seq_len - t0)
                    slices = []
                    for i in lay.paged_idx:
                        ax = lay.time_axes[i]
                        leaf = host[i]
                        sl = [slice(None)] * leaf.ndim
                        sl[ax] = slice(t0, t0 + width)
                        slices.append(np.ascontiguousarray(leaf[tuple(sl)]))
                    # identity (and hence dedup) is ALWAYS over the original
                    # fp bytes -- quantization changes a page's
                    # representation, never its id
                    pid = self._digest(layout_key, slices)
                    page = self.table.get(pid)
                    if page is not None:
                        page.last_use = self._tick()
                        self.stats["dedup_hits"] += 1
                        self.stats["dedup_saved_bytes"] += page.nbytes
                    else:
                        new += 1
                        page = self._make_page(
                            pid, slices, width, origin, device,
                            taxes=[lay.time_axes[i] for i in lay.paged_idx])
                    # logical (pre-dedup) bytes
                    self.stats["put_bytes"] += page.nbytes
                    self.table.incref(pid)
                    page_ids.append(pid)
                residual = [host[i] for i in lay.residual_idx]
                nbytes = sum(self.table.get(pid).nbytes
                             for pid in set(page_ids))
                nbytes += sum(a.nbytes for a in residual)
                self._residual_bytes += sum(a.nbytes for a in residual)
                # fully assembled: even the new pages are fair demotion
                # victims under the watermark (a read re-hydrates them from
                # disk)
                self._enforce_host_budget(set())
                self.stats["put_handles"] += 1
                handle = PagedKV(self, layout_key, page_ids, residual, seq_len,
                                 nbytes)
            sp.set(new_pages=new, dedup_hits=npages - new, bytes=nbytes)
        return handle

    def leaves(self, handle: PagedKV) -> List[np.ndarray]:
        """Rebuild the full flat leaf list of a handle: paged leaves are
        zero-initialized at full width and filled page by page (positions
        beyond seq_len are masked by attention, so zeros there are
        token-exact); disk pages promote to host on the way."""
        lay = self._layouts[handle.layout_key]
        out: List[Optional[np.ndarray]] = [None] * len(lay.time_axes)
        full = [np.zeros(lay.shapes[i], lay.dtypes[i]) for i in lay.paged_idx]
        with self.table.lock:
            pinned = set(handle.page_ids)
            promoted = False
            for p, pid in enumerate(handle.page_ids):
                page = self.table.get(pid)
                if page is None:
                    raise KeyError(f"kv page {pid} lost")
                if page.data is None:
                    self._promote(page)
                    promoted = True
                page.last_use = self._tick()
                t0 = p * self.page_size
                for j, i in enumerate(lay.paged_idx):
                    ax = lay.time_axes[i]
                    sl = [slice(None)] * full[j].ndim
                    sl[ax] = slice(t0, t0 + page.width)
                    full[j][tuple(sl)] = self._page_leaf(
                        page, j, lay.dtypes[i])
            if promoted:
                self._enforce_host_budget(pinned)
        for j, i in enumerate(lay.paged_idx):
            out[i] = full[j]
        for j, i in enumerate(lay.residual_idx):
            out[i] = handle.residual[j]
        return out  # type: ignore[return-value]

    def _promote(self, page: KVPage) -> None:
        blob = self.storage.kv_page_load(page.pid) if self.storage else None
        if blob is None:
            raise KeyError(f"kv page {page.pid} not on disk")
        obj = pickle.loads(blob)
        if isinstance(obj, dict) and obj.get("v") == 2:
            page.data = list(obj["data"])
            page.scales = (list(obj["scales"])
                           if obj.get("q") == "int8" else None)
            if page.taxes is None and obj.get("taxes") is not None:
                page.taxes = tuple(obj["taxes"])
        else:   # v1 blob: bare fp leaf list
            page.data = obj
            page.scales = None
        page.tier = "host"
        self._host_used += self._data_bytes(page)
        self.stats["promotions"] += 1
        if self.tracer is not None:
            self.tracer.instant("promote", _PID_MEMORY, 0,
                                {"pid": page.pid,
                                 "bytes": self._data_bytes(page)})

    def release(self, handle: PagedKV) -> None:
        """Drop a holder's references (idempotent per handle). Refcount-0
        pages retire to their disk blob when durable (a persisted prefix
        stays re-hydratable) and are freed outright otherwise."""
        with self.table.lock:
            if handle._released:
                return
            freed0 = self.stats["freed_pages"]
            retired0 = self.stats["retired_pages"]
            with span("aios.pagestore.release", tracer=self.tracer) as sp:
                handle._released = True
                self.stats["released_handles"] += 1
                self._residual_bytes -= sum(a.nbytes
                                            for a in handle.residual)
                for pid in handle.page_ids:
                    self._drop_ref(pid)
                sp.set(freed=self.stats["freed_pages"] - freed0,
                       retired=self.stats["retired_pages"] - retired0)

    def pin_pages(self, handle: PagedKV) -> None:
        """Short-lived extra reference covering the window between a cache
        lookup returning a paged entry and the engine materializing it --
        without the pin, a concurrent insert/eviction on another core could
        free the entry's non-durable pages mid-read. Balanced by
        ``unpin_pages`` (independent of handle.release)."""
        with self.table.lock:
            for pid in handle.page_ids:
                self.table.incref(pid)

    def unpin_pages(self, handle: PagedKV) -> None:
        with self.table.lock:
            for pid in handle.page_ids:
                self._drop_ref(pid)

    def demote_handle(self, handle: PagedKV) -> bool:
        """Push this handle's EXCLUSIVE RAM-resident pages to the disk tier
        (the context spill path). Pages shared with other holders (refs > 1
        -- e.g. a hot prefix-cache entry this context dedups against) stay
        resident: spilling one cold context must not cost the other holders
        their residency or device accounting. Returns False when no storage
        tier is attached (caller keeps the snapshot resident)."""
        if self.storage is None:
            return False
        with self.table.lock:
            for pid in handle.page_ids:
                page = self.table.get(pid)
                if (page is not None and page.tier != "disk"
                        and page.refs <= 1):
                    self._demote_to_disk(page)
        return True

    # -- prefix persistence (cross-process sharing) --------------------------------
    @staticmethod
    def _prefix_key(tokens: np.ndarray) -> str:
        return np.ascontiguousarray(
            np.asarray(tokens, np.int32)).tobytes().hex()

    def persist_prefix(self, snap) -> bool:
        """Write-through persist a prefix entry: flush its pages (marked
        durable) and store a manifest under the token key, so a fresh
        process on the same storage root re-hydrates this prefix instead of
        re-prefilling it.

        Best-effort by contract: persistence runs inline on the decode path
        (``_cache_prefix`` after a prefill completes), so a storage-tier
        fault here must degrade to "not persisted" -- it must NOT propagate
        and fail (or retry) a generation whose tokens never needed the
        disk. Pages already flushed before the fault stay durable (their
        blobs are valid; the orphan sweep reclaims them if no manifest ever
        lands)."""
        if not self.persist_enabled:
            return False
        handle: PagedKV = snap.pages
        key = self._prefix_key(snap.prompt)
        try:
            with self.table.lock:
                meta_pages = []
                for pid in handle.page_ids:
                    page = self.table.get(pid)
                    if page is None or not self._flush(page):
                        return False
                    page.durable = True
                    meta_pages.append((pid, page.nbytes, page.width,
                                       page.origin))
            logits = None if snap.logits is None else np.asarray(snap.logits)
            manifest = {
                "prompt": np.asarray(snap.prompt, np.int32),
                "seq_len": int(snap.seq_len),
                "layout_key": handle.layout_key,
                "origin": getattr(snap, "origin", None),
                "logits": logits,
                "pages": meta_pages,
                "residual": [np.asarray(a) for a in handle.residual],
            }
            idx = self.storage.kv_manifest_save(key, pickle.dumps(manifest),
                                                int(snap.seq_len),
                                                max_entries=self.max_manifests)
        except Exception:  # noqa: BLE001 -- storage down: skip the persist
            self.stats["persist_errors"] += 1
            return False
        with self.table.lock:
            # the save returns the post-prune index: mirror it so misses
            # keep hitting the cache instead of re-reading the blob
            self._index_cache = dict(idx)
            self._index_time = time.monotonic()
            self._gate = self._build_gate(self._index_cache)
        self.stats["persisted_entries"] += 1
        return True

    def _manifest_index(self) -> Dict[str, int]:
        """Manifest index with a small TTL cache: the disk read + unpickle
        would otherwise run on EVERY prefix-cache miss (under the cache's
        pool-wide lock). Own inserts update the cache in place; other
        processes' inserts become visible within ``index_ttl_s``."""
        now = time.monotonic()
        if (self._index_cache is None
                or now - self._index_time > self.index_ttl_s):
            try:
                self._index_cache = self.storage.kv_manifest_index()
            except Exception:  # noqa: BLE001 -- storage tier down: serve
                # the stale cache (or nothing) instead of crashing the
                # admission path that called through the prefix cache
                self.stats["index_errors"] += 1
                if self._index_cache is None:
                    self._index_cache = {}
            self._index_time = now
            self._gate = self._build_gate(self._index_cache)
        return self._index_cache

    def _build_gate(self, index: Dict[str, int]) -> Tuple[set, List[int]]:
        """Exact first-``gate_tokens`` gate over the manifest index: keys
        are hex-encoded int32 token prefixes (8 hex chars per token), so
        clipping a key at ``8 * min(n, gate_tokens)`` chars gives the
        leading tokens without decoding. A probe whose own leading tokens
        miss every clip can have NO manifest match (a match key[:8n] ==
        tok[:n] implies its clip equals the probe's clip), so the O(index)
        longest-prefix scan is skipped entirely -- the common cold-miss
        path on a busy front door."""
        G = self.gate_tokens
        prefixes = set()
        clips = set()
        for key, n in index.items():
            m = min(int(n), G)
            prefixes.add(key[:8 * m])
            clips.add(m)
        return prefixes, sorted(clips)

    def rehydrate_prefix(self, tokens: np.ndarray, *, min_tokens: int = 4
                         ) -> Optional[PagedPrefixEntry]:
        """Longest persisted prefix of ``tokens`` (>= min_tokens), rebuilt
        from the disk manifest: known pages are re-referenced in place,
        unknown ones enter the table at the disk tier and load lazily on
        first restore.

        Falls back to SUB-prefix re-hydration when no whole manifest fits:
        a persisted entry that *extends* the probe (stored ``[probe...,
        more]``) shares its leading pages with the probe up to a page
        boundary, so the first ``floor(len(probe)/page_size)`` pages are
        reused as a truncated entry. Truncated entries carry no last-token
        logits (the stored logits follow a longer context) and a stale
        residual seq_lens -- the admission path re-prefills from the
        truncation point and rewrites the slot's seq_len, so neither is
        ever consumed."""
        if not self.persist_enabled:
            return None
        tok = np.ascontiguousarray(np.asarray(tokens, np.int32))
        with self.table.lock:     # snapshot: persist_prefix mutates in place
            index = list(self._manifest_index().items())
            gate = self._gate
        if gate is not None:
            prefixes, clips = gate
            tokb = tok.tobytes()
            if not any(len(tok) >= m and tokb[:4 * m].hex() in prefixes
                       for m in clips):
                self.stats["gated_probes"] += 1
                return None
        best_key, best_n = None, 0
        needles: Dict[int, str] = {}   # one hex conversion per distinct
                                       # length, not per index entry
        for key, n in index:
            if n < min_tokens or n <= best_n or n > len(tok):
                continue
            needle = needles.get(n)
            if needle is None:
                needle = needles[n] = tok[:n].tobytes().hex()
            if needle == key:
                best_key, best_n = key, n
        trunc = 0
        if best_key is None:
            # page-boundary truncation: a stored prompt sharing the probe's
            # first t tokens (t = the largest page boundary inside their
            # common prefix) donates its first t/page_size pages. Keys are
            # 8 hex chars per token, so key[:8t] == hex(tok[:t]) tests the
            # share without decoding. Gate note: t >= min_tokens >=
            # gate_tokens, so viable donors always pass the gate above.
            ps = self.page_size
            best_t = max(int(min_tokens), 1) - 1
            for key, n in index:
                hi = (min(len(tok), n) // ps) * ps
                for t in range(hi, best_t, -ps):
                    if t >= n:
                        continue   # whole-manifest prefix: exact scan's job
                    needle = needles.get(t)
                    if needle is None:
                        needle = needles[t] = tok[:t].tobytes().hex()
                    if key.startswith(needle):
                        best_key, best_t = key, t
                        break
            if best_key is None:
                return None
            trunc = best_t
        try:
            blob = self.storage.kv_manifest_load(best_key)
            if blob is None:
                return None
            man = pickle.loads(blob)
            # force-validate the page tuples here so malformed entries
            # surface inside this guard, not in the table transaction below
            meta_pages = [(str(p), int(b), int(w), o)
                          for p, b, w, o in man["pages"]]
            seq_len = int(man["seq_len"])
            prompt, logits = man["prompt"], man["logits"]
            residual = list(man["residual"])
            layout_key, entry_origin = man["layout_key"], man["origin"]
        except Exception:  # noqa: BLE001 -- truncated/corrupt manifest blob
            # (torn write, version skew, storage fault): a STRUCTURED miss
            # -- the caller cold-prefills -- never an admission crash
            self.stats["corrupt_manifests"] += 1
            return None
        lay = self._layouts.get(layout_key)
        if lay is None:
            return None   # no engine with this layout in this process
        if trunc:
            if not lay.truncatable:
                return None   # residual state can't rewind to the boundary
            npg = trunc // self.page_size
            meta_pages = meta_pages[:npg]
            if len(meta_pages) < npg or \
                    any(w != self.page_size for _, _, w, _ in meta_pages):
                return None   # donor pages don't tile the boundary
            seq_len, prompt, logits = trunc, prompt[:trunc], None
        with self.table.lock:
            page_ids = []
            nbytes = 0
            for pid, pnb, width, porigin in meta_pages:
                page = self.table.get(pid)
                if page is None:
                    page = KVPage(pid, None, pnb, width, porigin, "disk")
                    page.durable = page.flushed = True
                    page.last_use = self._tick()
                    self.table.add(page)
                self.table.incref(pid)
                page_ids.append(pid)
                nbytes += pnb
            handle = PagedKV(self, layout_key, page_ids,
                             residual, seq_len,
                             nbytes + sum(a.nbytes for a in residual))
            self._residual_bytes += sum(a.nbytes for a in residual)
        self.stats["rehydrated_entries"] += 1
        if trunc:
            self.stats["truncated_rehydrates"] += 1
        return PagedPrefixEntry(prompt, seq_len, handle,
                                logits, entry_origin)

    def gc_orphan_blobs(self, grace_s: float = 60.0) -> Dict[str, int]:
        """Reclaim orphan page blobs (ROADMAP follow-on (k)): manifest
        pruning (FIFO past ``max_manifests``) deletes manifest blobs but
        must leave their page blobs in place -- a page may be shared with a
        live manifest. This mark-and-sweep walks the SURVIVING manifests
        (under the cross-process manifest lock) plus this process's in-RAM
        page table -- which keeps disk-tier pages of live handles (spilled
        contexts, demoted prefix entries) out of the sweep even when no
        manifest lists them. The table snapshot is taken by a callback
        UNDER the manifest lock (no stale-snapshot window vs this process's
        own writers), and unreferenced blobs younger than ``grace_s`` are
        skipped -- a page mid-persist or mid-demote (flushed, not yet in a
        manifest or re-listed) is by construction recent, so it survives.

        Caveat: another process's un-persisted spilled contexts older than
        the grace period are not visible here; run the sweep from the
        kernel that owns the storage root, or only when sibling processes
        are quiesced."""
        if self.storage is None:
            return {"swept": 0, "kept": 0, "recent": 0, "live_pids": 0}

        def _live():
            with self.table.lock:
                return [p.pid for p in self.table.pages()]

        res = self.storage.kv_orphan_sweep(_live, grace_s=grace_s)
        with self.table.lock:
            self.stats["gc_swept_blobs"] += res["swept"]
            self.stats["gc_runs"] += 1
        return res

    # -- liveness beacon (ROADMAP follow-on (n)) -----------------------------------
    def _table_pids(self) -> List[str]:
        with self.table.lock:
            return [p.pid for p in self.table.pages()]

    def beacon_now(self) -> None:
        """Write one beacon beat immediately (every page id the in-RAM
        table references). The kernel's background thread calls this each
        interval; tests call it directly to make liveness visible without
        waiting out an interval."""
        if self.storage is not None:
            self.storage.kv_beacon_write(self._table_pids())

    def start_beacon(self, interval_s: float = 2.0) -> None:
        """Advertise this process's live KV pages to sibling sweepers (the
        cross-process half of ``gc_orphan_blobs``'s caveat): a heartbeat
        file under the storage root -- same shape as
        ``training.fault_tolerance.Heartbeat`` -- refreshed every
        ``interval_s`` with the current page-table ids, so another
        kernel's ``kv_orphan_sweep`` keeps them even past its mtime
        grace. Idempotent; no-op without a storage tier."""
        if self.storage is None or self._beacon_thread is not None:
            return
        self.beacon_now()     # visible before the first interval elapses
        stop = threading.Event()

        def _beat():
            while not stop.wait(interval_s):
                try:
                    self.beacon_now()
                except Exception:  # noqa: BLE001 -- a sick storage tier
                    pass           # must not kill the heartbeat thread

        self._beacon_stop = stop
        self._beacon_thread = threading.Thread(target=_beat, daemon=True,
                                               name="aios-kv-beacon")
        self._beacon_thread.start()

    def stop_beacon(self, clear: bool = True) -> None:
        """Stop the heartbeat; ``clear`` removes the beacon file so a
        clean shutdown stops pinning blobs instantly (a crash leaves the
        file, and the dead-pid check invalidates it)."""
        if self._beacon_thread is None:
            return
        self._beacon_stop.set()
        self._beacon_thread.join(timeout=5.0)
        self._beacon_thread = None
        self._beacon_stop = None
        if clear and self.storage is not None:
            try:
                self.storage.kv_beacon_clear()
            except Exception:  # noqa: BLE001
                pass

    # -- queries -------------------------------------------------------------------
    def page_origins(self, handle: PagedKV) -> List[Optional[int]]:
        with self.table.lock:
            return self.table.origins(handle.page_ids)

    def host_used(self) -> int:
        return self._host_used

    def device_used(self) -> int:
        return self._device_bytes

    def metrics(self) -> Dict[str, Any]:
        with self.table.lock:
            tiers = self.table.tier_counts()
            page_bytes = sum(p.nbytes for p in self.table.pages())
            return dict(self.stats, pages=len(self.table),
                        kv_quant=self.kv_quant,
                        page_bytes=page_bytes,
                        host_bytes=self._host_used,
                        residual_bytes=self._residual_bytes,
                        device_bytes=self._device_bytes,
                        device_pages_used=self.device_pager.used_pages,
                        device_pages_free=self.device_pager.free_pages,
                        **{f"{t}_pages": n for t, n in tiers.items()})
