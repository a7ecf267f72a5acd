"""KV-cache conversion and (host-)offloaded cache management.

``model.prefill`` returns raw per-layer K/V stacked over layer groups;
decode expects pre-allocated (possibly ring-buffer) caches.  This module
converts between the two, handling sliding-window ring alignment (absolute
position p lives in slot ``p % window``), and provides the slot-wise
insert/evict primitives the continuous scheduler uses to recycle batch
slots mid-flight (a finished sequence's KV rows and SSM state are
overwritten by the next admitted request).

These free functions are the CONTIGUOUS-buffer primitives of the cache
API; the paged tier (``serving.cache.KVPageTable``) builds on them —
``aligned_kv`` produces the span-aligned rows its page splitter consumes,
and ``insert_prefill_rows``/``evict_rows`` remain the Mode A (fully
device-resident) fast path the engine routes through.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import TraceKeySet, register_jit
from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import model as model_mod


def aligned_kv(
    cfg: ModelConfig, k: jax.Array, v: jax.Array, span: int
) -> Tuple[jax.Array, jax.Array]:
    """Raw prefill K/V ``(..., S, K, hd)`` -> decode-ready ``(..., span, ...)``.

    Pads/truncates to ``span`` slots; with a sliding window longer prompts
    are ring-aligned (absolute position p -> slot ``p % span``).
    """
    *lead, S, K, hd = k.shape
    kf = k.reshape((-1, S, K, hd))
    vf = v.reshape((-1, S, K, hd))
    n = min(S, span)
    nk = jnp.zeros((kf.shape[0], span, K, hd), k.dtype)
    nv = jnp.zeros_like(nk)
    if cfg.sliding_window and S > span:
        pos = jnp.arange(S - n, S)
        slots = pos % span
        nk = nk.at[:, slots].set(kf[:, -n:])
        nv = nv.at[:, slots].set(vf[:, -n:])
    else:
        nk = nk.at[:, :n].set(kf[:, -n:])
        nv = nv.at[:, :n].set(vf[:, -n:])
    shape = tuple(lead) + (span, K, hd)
    return nk.reshape(shape), nv.reshape(shape)


def cache_from_prefill(
    cfg: ModelConfig, caches: List, seq_len: int, max_seq: int
) -> List:
    """Convert prefill caches into decode-ready buffers of span ``max_seq``."""
    kinds = model_mod.lead_pattern(cfg) + model_mod.layer_pattern(cfg)
    out = []
    for j, (kind, _) in enumerate(kinds):
        slot = caches[j]
        if kind == "mla":
            out.append({"ckv": attn_mod.mla_pack(
                cfg, slot["ckv"], attn_mod.mla_span(max_seq))})
            continue
        if kind != "attn":
            out.append(slot)                       # SSM state passes through
            continue
        span = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
        nk, nv = aligned_kv(cfg, slot["k"], slot["v"], span)
        out.append({"k": nk, "v": nv})
    return out


@register_jit("kvcache.insert_latent", donated=("ckv",))
@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("ckv",))
def _insert_latent_module(cfg, ckv, rows, new):
    """Pack a micro-batch's per-position latent rows ``new`` (n, S, C)
    into the decode layout (``attention.mla_pack``) and write them into
    batch rows ``rows`` of the layer's cache, which is DONATED: updated in
    place, never copied whole.  One launch: packing eagerly would upload
    its slice bounds and pad value on every call."""
    packed = attn_mod.mla_pack(cfg, new, 2 * ckv.shape[1])
    return ckv.at[rows].set(packed.astype(ckv.dtype))


def insert_prefill_rows(
    cfg: ModelConfig, kind: str, layer_cache: dict, entry: dict,
    rows: Sequence[int],
) -> dict:
    """Insert ONE layer's raw prefill cache ``entry`` into batch rows
    ``rows`` of a decode buffer (ring-aligning KV to the buffer span).

    The slot-insertion invariant lives here and only here: each newcomer's
    FULL row is overwritten — KV beyond its prompt is zeroed, so no state
    of an evicted sequence survives slot recycling.  Shared by the engine's
    layer-major prefill and ``scatter_prefill_rows``.
    """
    rows = jnp.asarray(rows)
    if kind == "mla":
        layer_cache["ckv"] = _insert_latent_module(
            cfg, layer_cache["ckv"], rows, entry["ckv"])
    elif kind == "attn":
        span = layer_cache["k"].shape[1]
        nk, nv = aligned_kv(cfg, entry["k"], entry["v"], span)
        layer_cache["k"] = layer_cache["k"].at[rows].set(nk)
        layer_cache["v"] = layer_cache["v"].at[rows].set(nv)
    else:
        for key in ("h", "conv"):
            layer_cache[key] = layer_cache[key].at[rows].set(entry[key])
    return layer_cache


def scatter_prefill_rows(
    cfg: ModelConfig, cache: List, caches: List, rows: Sequence[int]
) -> List:
    """Insert newcomers' prefill caches into engine decode buffers at ``rows``.

    ``cache`` is the engine's per-layer (flattened over groups) buffer list;
    ``caches`` the raw ``model.prefill`` output for the newcomer micro-batch
    (stacked over groups).  See ``insert_prefill_rows`` for the invariant.
    """
    lead = model_mod.lead_pattern(cfg)
    pattern = model_mod.layer_pattern(cfg)
    n_lead, n_pat = len(lead), len(pattern)
    for li, (kind, _) in enumerate(lead):
        cache[li] = insert_prefill_rows(cfg, kind, cache[li], caches[li],
                                        rows)
    G = (len(cache) - n_lead) // n_pat
    for g in range(G):
        for j, (kind, _) in enumerate(pattern):
            li = n_lead + g * n_pat + j
            slot = jax.tree.map(lambda a: a[g], caches[n_lead + j])
            cache[li] = insert_prefill_rows(cfg, kind, cache[li], slot, rows)
    return cache


@register_jit("kvcache.evict", donated=("cache",))
@functools.partial(jax.jit, donate_argnames=("cache",))
def _evict_module(cache, rows):
    return jax.tree.map(
        lambda a: a.at[rows].set(jnp.zeros((), a.dtype)), cache
    )


# distinct padded eviction widths seen: each width is ONE cached trace of
# _evict_module (per cache pytree structure).  Backed by the analysis
# registry's named TraceKeySet — ``evict_retraces()`` is now a thin shim
# over it, and the sanitizer report picks the count up by name.
_EVICT_WIDTHS = TraceKeySet("kvcache.evict_rows")


def evict_retraces() -> int:
    """Number of distinct padded ``rows`` widths ``evict_rows`` has jitted
    with since import (eviction-set sizes 1..8 all share width 8)."""
    return _EVICT_WIDTHS.count


def _pad_evict_rows(rows: Sequence[int]) -> np.ndarray:
    """Pad an eviction set to a fixed trace width (multiple of 8, min 8)
    by repeating the first row as a sentinel: ``rows`` is a traced shape
    in ``_evict_module``, so un-padded calls retrace per distinct set
    size.  Duplicate indices are harmless — zeroing a row twice is
    idempotent."""
    rows = np.asarray(rows, np.int32).reshape(-1)
    width = max(8, int(-(-rows.size // 8) * 8))
    padded = np.full(width, rows[0], np.int32)
    padded[: rows.size] = rows
    _EVICT_WIDTHS.add(width)
    return padded


def evict_rows(cache: List, rows: Sequence[int]) -> List:
    """Zero batch rows across every layer buffer (slot recycling).

    Not required for correctness — decode masks by per-sequence position
    and insertion overwrites whole rows — but keeps freed slots inert
    between eviction and the next admission.  (In the paged Mode B the
    attention entries are empty dicts and the page table recycles frames
    instead — ``ModuleBatchingEngine.evict_slots`` routes both.)

    One jitted launch with the cache pytree DONATED: the rows are zeroed in
    place instead of functionally copying every (B, S, ...) buffer per
    eviction.  The caller's cache reference is consumed — assign the return
    value back (the engine owns the cache between ticks; see the ROADMAP
    donation contract).  The row set is padded to a fixed width so slot
    recycling stays one cached launch across eviction-set sizes
    (``evict_retraces``).
    """
    rows = np.asarray(rows).reshape(-1)
    if rows.size == 0:
        return list(cache)
    return list(_evict_module(tuple(cache), jnp.asarray(_pad_evict_rows(rows))))


def snapshot_row(layer_cache: dict, row: int) -> dict:
    """Host snapshot of one batch row of a contiguous layer buffer.

    The checkpoint unit of request preemption: every decode-buffer entry
    is batch-leading (attn ``{"k","v"}: (B, span, K, hd)``; SSM
    ``{"h","conv"}``; MLA ``{"ckv"}: (B, span/2, 2C)``), so one row per layer
    captures a sequence's full recurrent state.  Rows come back as NumPy
    (host) arrays — checkpoints live in host memory while the device slot
    is recycled.
    """
    return {key: np.asarray(val[row]) for key, val in layer_cache.items()}


def restore_row(layer_cache: dict, row: int, state: dict) -> dict:
    """Write a ``snapshot_row`` checkpoint back into batch row ``row``.

    The inverse of ``snapshot_row`` for contiguous buffers; Mode B paged
    attention restores through ``KVPageTable.insert_rows`` instead (the
    row's frames were freed with the slot)."""
    return {
        key: layer_cache[key].at[row].set(jnp.asarray(state[key]))
        for key in layer_cache
    }


def cache_bytes(cache: List) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
