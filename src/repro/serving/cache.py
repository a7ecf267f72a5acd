"""Paged tiered KV cache: the first-class cache API (ROADMAP "Tiered KV").

The paper's Eq. 2 admission story says tokens accumulate in HOST memory and
only the working set lives on device — but a monolithic ``(B, max_seq)``
KV buffer pins every sequence's full extent on device, so admission gates
on device memory long before the host tier is exhausted.  This module
pages the KV cache into fixed-size ``page_tokens`` blocks behind a
``KVPageTable`` that owns the slot<->page mapping and free lists:

* **Mode A (fully device-resident).**  When the device pool budget covers
  every frame (``device_pool_bytes=None`` or large), the table is
  bookkeeping only: the engine keeps its contiguous per-layer buffers and
  the fused donated decode path stays BIT-identical — paging costs nothing
  when everything fits (the fused/streamed path-selection contract).
* **Mode B (host tier).**  When the budget covers only ``P`` frames, the
  remaining frames live in numpy host pools.  Decode falls back to the
  per-layer loop (exactly like streamed weights): each attention layer's
  host frames stream device-ward through the SAME double-buffered async
  ``device_put`` window ``ParamStore`` uses for weights
  (``serving.weights.StreamWindow``), and the gather reassembles each
  row's ``span`` from device pool + streamed frames.  Frames are allocated
  device tier first, spilling into the host tier.

On top of the page table, ``PrefixStore`` caches shared prompt prefixes at
page granularity: a hit is admitted by copying stored page rows instead of
recomputing prefill for the shared span (the engine's suffix-prefill
launches are independent of the prefix length).

Ownership/donation contract: the table owns the page pools the way the
engine owns the cache pytree — pool buffers are DONATED to the paged
decode modules and rebound from their results each launch; callers must
never retain references into ``pool_k``/``pool_v`` across a decode tick
(take ``np.asarray`` copies instead).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults
from repro.analysis import runtime as sanitizer
from repro.configs.base import ModelConfig
from repro.serving.weights import StreamWindow


@dataclass(frozen=True)
class CacheConfig:
    """Cache-side knobs, frozen (the ``ServeConfig`` of the KV tier).

    ``page_tokens=0`` disables paging entirely (the legacy contiguous
    cache).  ``device_pool_bytes=None`` keeps every page frame on device
    (Mode A); a finite budget sizes the device pool and spills the
    remainder to the host tier (Mode B).  ``prefix_cache`` enables the
    ``PrefixStore`` (requires ``page_tokens > 0``; prefixes are keyed at
    page granularity)."""

    page_tokens: int = 0
    device_pool_bytes: Optional[float] = None
    prefix_cache: bool = False
    prefix_entries: int = 64
    prefetch: bool = True
    prefetch_depth: int = 2

    def __post_init__(self) -> None:
        assert self.page_tokens >= 0, self.page_tokens
        if self.prefix_cache:
            assert self.page_tokens > 0, (
                "prefix_cache requires paging (page_tokens > 0): prefixes "
                "are shared at page granularity"
            )

    @property
    def enabled(self) -> bool:
        return self.page_tokens > 0


class KVPageTable:
    """Slot<->page mapping, free lists, and the tiered page pools.

    One table serves every attention layer of the engine's schema: the
    ``page_map`` (batch, pages_per_seq) is shared — a batch row's page *i*
    lives in the same frame id across layers — while each attention layer
    owns its own pool buffers (frames hold per-layer K/V values).

    Frame-id encoding in ``page_map``: ``-1`` free/unallocated;
    ``0 <= f < device_frames`` device frame ``f``; ``f >= device_frames``
    host frame ``f - device_frames``.  The device pools carry ONE extra
    frame at index ``device_frames`` — the **null frame**, a write sink
    for rows whose written page lives host-side (their in-launch scatter
    lands there and is discarded; the real value is written into the host
    pool by the engine).  Nothing live ever reads the null frame.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        schema: Sequence[Tuple[str, str]],
        batch: int,
        max_seq: int,
        cache_cfg: CacheConfig,
    ) -> None:
        assert cache_cfg.enabled, "KVPageTable requires page_tokens > 0"
        self.cfg = cfg
        self.cc = cache_cfg
        self.batch = batch
        self.attn_layers: List[int] = [
            li for li, (kind, _) in enumerate(schema) if kind == "attn"
        ]
        self.n_layers = len(schema)
        sw = cfg.sliding_window
        self.span = min(max_seq, sw) if sw else max_seq
        pt = cache_cfg.page_tokens
        self.page_tokens = pt
        self.pages_per_seq = -(-self.span // pt)          # ceil
        self.total_frames = batch * self.pages_per_seq
        K, hd = cfg.num_kv_heads, cfg.head_dim
        self._dtype = jnp.dtype(cfg.dtype)
        itemsize = self._dtype.itemsize
        # one frame across every attention layer, K + V
        self.frame_bytes = (
            len(self.attn_layers) * 2 * pt * K * hd * itemsize
        )
        budget = cache_cfg.device_pool_bytes
        if budget is None:
            self.device_frames = self.total_frames
        else:
            self.device_frames = max(
                0, min(self.total_frames, int(budget // max(1, self.frame_bytes)))
            )
        self.host_frames = self.total_frames - self.device_frames
        # -1 = free; [0, P) device; P + h = host frame h
        self.page_map = np.full((batch, self.pages_per_seq), -1, np.int32)
        self._free_dev: List[int] = list(range(self.device_frames))[::-1]
        self._free_host: List[int] = list(range(self.host_frames))[::-1]
        self.pool_k: Dict[int, jax.Array] = {}
        self.pool_v: Dict[int, jax.Array] = {}
        self.host_k: Dict[int, np.ndarray] = {}
        self.host_v: Dict[int, np.ndarray] = {}
        self._window: Optional[StreamWindow] = None
        self._epoch: Dict[int, int] = {}
        self.dtoh_bytes = 0
        if not self.fully_resident:
            P = self.device_frames
            for li in self.attn_layers:
                # +1: the null write-sink frame at index P
                self.pool_k[li] = jnp.zeros((P + 1, pt, K, hd), self._dtype)
                self.pool_v[li] = jnp.zeros((P + 1, pt, K, hd), self._dtype)
                self.host_k[li] = np.zeros((self.host_frames, pt, K, hd),
                                           self._dtype)
                self.host_v[li] = np.zeros((self.host_frames, pt, K, hd),
                                           self._dtype)
                self._epoch[li] = 0
            self._window = StreamWindow(
                self._fetch_layer, depth=cache_cfg.prefetch_depth,
                enabled=True, tag="kv-pages",
            )

    # -- residency -------------------------------------------------------
    @property
    def fully_resident(self) -> bool:
        """True when every page frame fits the device pool — the paging
        analogue of ``ParamStore.fully_resident``, and (with it) the
        precondition for the engine's fused decode path: host-tier pages
        keep the per-layer loop so the page stream has a layer boundary to
        hide behind."""
        return self.host_frames == 0

    def device_pool_bytes(self) -> int:
        if self.fully_resident:
            return self.total_frames * self.frame_bytes
        return (self.device_frames + 1) * self.frame_bytes

    def host_pool_bytes(self) -> int:
        return self.host_frames * self.frame_bytes

    def describe(self) -> str:
        live = int((self.page_map >= 0).sum())
        host_live = int((self.page_map >= self.device_frames).sum())
        return (
            f"pages {self.page_tokens} tok x {self.pages_per_seq}/seq: "
            f"{self.device_frames}/{self.total_frames} frames device "
            f"({self.device_pool_bytes() / 1e9:.3f}GB), "
            f"{self.host_frames} host, live={live} (host {host_live})"
        )

    # -- allocation ------------------------------------------------------
    def _alloc_frame(self) -> int:
        """A free frame: the device tier first, then the host tier."""
        if self._free_dev:
            return self._free_dev.pop()
        if not self._free_host:
            raise faults.PageAllocOOM(
                "page table out of frames (batch rows exceed capacity?)")
        return self.device_frames + self._free_host.pop()

    def ensure_rows(self, rows: Sequence[int]) -> None:
        """Allocate page frames for ``rows`` (no-op for already-allocated
        rows — re-inserting into a live slot reuses its placement), device
        frames first, spilling into the host tier when they run out.

        Allocation is transactional per row: on ``PageAllocOOM`` (real
        frame exhaustion, or an injected fault from the armed plan) the
        partially-allocated row is rolled back before the error
        propagates, so the admission layer can defer/degrade and retry
        without leaking frames."""
        for r in rows:
            if self.page_map[r, 0] >= 0:
                continue
            fp = faults.current()
            if fp is not None and fp.page_oom():
                raise faults.PageAllocOOM(
                    f"injected page-alloc OOM (row {r})")
            try:
                for pp in range(self.pages_per_seq):
                    self.page_map[r, pp] = self._alloc_frame()
            except faults.PageAllocOOM:
                self.free_rows([r])
                raise
        self._bump_all()

    def free_rows(self, rows: Sequence[int]) -> None:
        """Return ``rows``' frames to the free lists (slot recycling)."""
        for r in rows:
            for pp in range(self.pages_per_seq):
                f = int(self.page_map[r, pp])
                if f < 0:
                    continue
                if f < self.device_frames:
                    self._free_dev.append(f)
                else:
                    self._free_host.append(f - self.device_frames)
                self.page_map[r, pp] = -1
        self._bump_all()

    def _bump_all(self) -> None:
        for li in self._epoch:
            self._epoch[li] += 1

    # -- page content (Mode B) -------------------------------------------
    def _paged(self, aligned: jax.Array) -> jax.Array:
        """(n, span, K, hd) -> (n, pages_per_seq, page_tokens, K, hd)."""
        n, span, K, hd = aligned.shape
        full = self.pages_per_seq * self.page_tokens
        if full > span:
            aligned = jnp.pad(aligned,
                              ((0, 0), (0, full - span), (0, 0), (0, 0)))
        return aligned.reshape(n, self.pages_per_seq, self.page_tokens, K, hd)

    def insert_rows(self, li: int, nk: jax.Array, nv: jax.Array,
                    rows: Sequence[int]) -> None:
        """Write span-aligned KV ``(n, span, K, hd)`` into ``rows``' pages
        of layer ``li`` (admission: the whole row is overwritten, same
        invariant as ``kvcache.insert_prefill_rows``).  Host-frame pages
        are copied down to the host pools (device->host, accounted)."""
        if self.fully_resident:
            return                      # Mode A: content lives in the
        #                                 engine's contiguous buffers
        pk, pv = self._paged(nk), self._paged(nv)
        dev_f: List[int] = []
        dev_i: List[Tuple[int, int]] = []
        for i, r in enumerate(rows):
            for pp in range(self.pages_per_seq):
                f = int(self.page_map[r, pp])
                assert f >= 0, (r, pp)
                if f < self.device_frames:
                    dev_f.append(f)
                    dev_i.append((i, pp))
                else:
                    h = f - self.device_frames
                    page_k = np.asarray(pk[i, pp])
                    page_v = np.asarray(pv[i, pp])
                    self.host_k[li][h] = page_k
                    self.host_v[li][h] = page_v
                    self.dtoh_bytes += page_k.nbytes + page_v.nbytes
        if dev_f:
            idx = jnp.asarray(dev_f)
            sel = jnp.asarray(dev_i)
            self.pool_k[li] = self.pool_k[li].at[idx].set(
                pk[sel[:, 0], sel[:, 1]]
            )
            self.pool_v[li] = self.pool_v[li].at[idx].set(
                pv[sel[:, 0], sel[:, 1]]
            )
        self._epoch[li] += 1

    def write_host_slot(self, li: int, host_frame: int, offset: int,
                        k_new: np.ndarray, v_new: np.ndarray) -> None:
        """Single-slot decode write into a host frame (the engine calls
        this for rows whose written page lives host-side)."""
        self.host_k[li][host_frame, offset] = k_new
        self.host_v[li][host_frame, offset] = v_new
        self.dtoh_bytes += k_new.nbytes + v_new.nbytes
        self._epoch[li] += 1

    def read_row(self, li: int, row: int, n: int) -> Tuple[np.ndarray,
                                                           np.ndarray]:
        """Gather the first ``n`` token slots of ``row``'s layer-``li`` KV
        as numpy (prefix capture / preemption checkpoints)."""
        pt = self.page_tokens
        K, hd = self.cfg.num_kv_heads, self.cfg.head_dim
        out_k = np.zeros((self.pages_per_seq * pt, K, hd), self._dtype)
        out_v = np.zeros_like(out_k)
        for pp in range(-(-n // pt)):
            f = int(self.page_map[row, pp])
            if f < 0:
                continue
            if f < self.device_frames:
                k = np.asarray(self.pool_k[li][f])
                v = np.asarray(self.pool_v[li][f])
                self.dtoh_bytes += k.nbytes + v.nbytes
            else:
                h = f - self.device_frames
                k, v = self.host_k[li][h], self.host_v[li][h]
            out_k[pp * pt:(pp + 1) * pt] = k
            out_v[pp * pt:(pp + 1) * pt] = v
        return out_k[:n], out_v[:n]

    # -- decode-time gather plumbing (Mode B) ----------------------------
    def gather_indices(self, rows: Sequence[int]) -> np.ndarray:
        """Frame ids remapped for the paged decode module's gather over
        ``concat([device pool (P+1 incl. null), streamed host frames (H)])``:
        device frame f -> f; host frame h -> P+1+h; unallocated -> the null
        frame P (dead rows gather inert values their masks discard)."""
        P = self.device_frames
        out = np.empty((len(rows), self.pages_per_seq), np.int32)
        for i, r in enumerate(rows):
            for pp in range(self.pages_per_seq):
                f = int(self.page_map[r, pp])
                if f < 0:
                    out[i, pp] = P                      # null sink
                elif f < P:
                    out[i, pp] = f
                else:
                    out[i, pp] = P + 1 + (f - P)
        return out

    def write_targets(self, rows: Sequence[int],
                      wpage: np.ndarray) -> Tuple[np.ndarray, List]:
        """Per-row scatter targets for the decode write: the device pool
        frame (the null frame for host/unallocated pages), plus the list of
        ``(i, host_frame)`` pairs the engine must mirror host-side."""
        P = self.device_frames
        wframe = np.full(len(rows), P, np.int32)
        host_writes: List[Tuple[int, int]] = []
        for i, r in enumerate(rows):
            f = int(self.page_map[r, int(wpage[i])])
            if 0 <= f < P:
                wframe[i] = f
            elif f >= P:
                host_writes.append((i, f - P))
        return wframe, host_writes

    def _fetch_layer(self, li: int):
        """StreamWindow fetch closure: the async htod copy of layer
        ``li``'s ENTIRE host pool (fixed shape (H, pt, K, hd) — stable
        trace keys for the paged decode module), stamped with the layer
        epoch so a stale prefetch is detected at acquire."""
        k = jax.device_put(self.host_k[li])
        v = jax.device_put(self.host_v[li])
        nbytes = self.host_k[li].nbytes + self.host_v[li].nbytes
        return (self._epoch[li], k, v), nbytes

    def prefetch(self, li: int) -> None:
        """Stage layer ``li``'s host-pool transfer a layer ahead (issued
        by the engine before the previous layer's FFN launch, like weight
        prefetch).  No-op in Mode A or for non-attention layers."""
        if self._window is None or not self.cc.prefetch:
            return
        li = li % max(1, self.n_layers)
        if li not in self._epoch:
            return
        self._window.prefetch(li)

    def acquire(self, li: int) -> Tuple[jax.Array, jax.Array]:
        """Layer ``li``'s host frames on device ``(H, pt, K, hd)`` x2,
        consuming the in-flight prefetch; a prefetch made stale by an
        admission/eviction between ticks is discarded and re-fetched on
        demand (epoch check)."""
        assert self._window is not None
        epoch, k, v = self._window.acquire(li)
        if epoch != self._epoch[li]:
            with sanitizer.allowed("kv-pages", key=li):
                (epoch, k, v), nbytes = self._fetch_layer(li)
            self._window.htod_bytes += nbytes
            self._window.demand += 1
            jax.block_until_ready((k, v))
        return k, v

    # -- memory-pressure degradation -------------------------------------
    def demote_device_frames(self, limit: int) -> int:
        """Move up to ``limit`` live DEVICE frames to free host frames
        (stage 2 of the admission degradation ladder: relieve device-pool
        pressure instead of raising).  Deterministic victim order —
        highest batch row, highest page first (the coldest end of the
        admission order).  Mode A has no host tier, so this is a no-op
        there; returns the number of frames actually moved.

        Placement-only: Mode B math is independent of which tier a page
        lives in (the gather reassembles either), so demotion never
        changes tokens — only where the bytes sit."""
        if self._window is None or limit <= 0:
            return 0
        moved = 0
        for r in reversed(range(self.batch)):
            for pp in reversed(range(self.pages_per_seq)):
                if moved >= limit or not self._free_host:
                    break
                f = int(self.page_map[r, pp])
                if not (0 <= f < self.device_frames):
                    continue
                h = self._free_host.pop()
                with sanitizer.allowed("paged-host-writeback"):
                    for li in self.attn_layers:
                        k = np.asarray(self.pool_k[li][f])
                        v = np.asarray(self.pool_v[li][f])
                        self.host_k[li][h] = k
                        self.host_v[li][h] = v
                        self.dtoh_bytes += k.nbytes + v.nbytes
                self.page_map[r, pp] = self.device_frames + h
                self._free_dev.append(f)
                moved += 1
            if moved >= limit or not self._free_host:
                break
        if moved:
            faults.note("recovered:page-demotion", moved)
            self._bump_all()
        return moved

    # -- accounting ------------------------------------------------------
    def take_counters(self) -> Tuple[int, int]:
        """Drain (htod_bytes, dtoh_bytes) since last call (the page
        window's waits are its ``stream.wait`` spans)."""
        htod = (self._window.take_counters()[0]
                if self._window is not None else 0)
        dtoh = self.dtoh_bytes
        self.dtoh_bytes = 0
        return htod, dtoh

    def take_fault_counters(self) -> Tuple[int, int]:
        """Drain (transfer retries, watchdog timeouts) of the page stream
        window since the last call."""
        return (self._window.take_fault_counters()
                if self._window is not None else (0, 0))


class PrefixStore:
    """LRU prefix cache over page-aligned prompt prefixes.

    Keys are the EXACT prefix token bytes (no hash collisions by
    construction) at the largest page multiple strictly below the prompt
    length — at least one suffix token always remains, so a hit still
    produces the request's first-token logits through the engine's
    suffix prefill.  Values are per-attention-layer ``(k, v)`` numpy
    arrays of the prefix span; admission copies them into the hit row's
    page/cache rows instead of recomputing prefill (KV at position p
    depends only on tokens <= p, so copied rows are exactly what the full
    prefill would write).

    Restricted to all-attention models without a sliding window: SSM state
    and ring-aligned windows make a stored prefix non-transplantable.
    """

    def __init__(self, page_tokens: int, entries: int = 64) -> None:
        assert page_tokens > 0
        self.page_tokens = page_tokens
        self.entries = max(1, entries)
        self._store: "OrderedDict[bytes, List]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def supported(cfg: ModelConfig) -> bool:
        return cfg.sliding_window == 0 and all(
            cfg.layer_kind(i) == "attn" for i in range(cfg.num_layers)
        )

    def key(self, prompt: np.ndarray) -> Optional[Tuple[bytes, int]]:
        """(key bytes, prefix span) for ``prompt``, or None when no full
        page fits strictly inside it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pspan = ((len(prompt) - 1) // self.page_tokens) * self.page_tokens
        if pspan <= 0:
            return None
        return prompt[:pspan].tobytes(), pspan

    def get(self, key: bytes) -> Optional[List]:
        kvs = self._store.get(key)
        if kvs is None:
            self.misses += 1
            return None
        self._store.move_to_end(key)
        self.hits += 1
        return kvs

    def put(self, key: bytes, kvs: List) -> None:
        if key in self._store:
            self._store.move_to_end(key)
            return
        self._store[key] = kvs
        while len(self._store) > self.entries:
            self._store.popitem(last=False)

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0
