"""The MoE-Gen engine: executable module-based batching (paper §4.2).

This is the real thing, not the cost model: given a model's parameters and a
``Plan``, the engine runs generative inference by launching **per-module**
batched computations —

* the attention module consumes micro-batches of ``b_a`` sequences; outputs
  accumulate until all ``B`` sequences are ready.  Every row attends on the
  engine's device: the paper's host-CPU attention share ω is modelled by
  the planner but not built here, so an engine refuses a plan with ω > 0;
* the decode MoE stage runs as ONE **ragged grouped dispatch**
  (``moe.ragged_dispatch``): each expert's routed copies are gathered on
  device in whole row tiles, pushed through a single grouped FFN launch
  (Pallas on TPU, XLA einsum elsewhere — ``kernels.ops``), and
  scatter-added back weighted by their gates.  Routing indices never leave
  the device, so a decode step issues no host syncs; copies beyond the
  plan's per-expert budget ``b_e`` are dropped and accounted in
  ``EngineStats``;
* dense modules (SSM blocks, shared FFNs, lm_head) run at full batch.

**Fused donated decode (the §4/Fig. 5 few-large-launches thesis applied to
the decode hot path).**  When every weight is device-resident
(``ParamStore.fully_resident``), decode leaves the per-module dispatch loop
entirely: ``decode_chunk`` runs embed → the whole layer schema → head →
per-slot sampling as ONE jitted launch (``_fused_decode_chunk``), with the
KV/SSM cache pytree passed in and out under buffer DONATION and written in
place — no functional whole-cache copies survive.  A ``lax.scan`` over
``T`` decode ticks keeps the sampled tokens, per-slot positions and sampler
token-indices entirely in-carry on device, so steady-state decode costs one
Python dispatch per ``T`` tokens instead of O(layers·modules·T).  Path
selection is automatic (``fused_eligible``): streamed residency, host-tier
KV pages and a mesh keep the per-layer loop (the htod prefetch needs the
layer boundary to hide behind).  Fused and per-module decode are
property-tested token-for-token identical (tests/test_fused_decode.py,
tests/test_properties.py).

**Donation contract.**  The engine OWNS the cache pytree between ticks:
``decode_chunk`` (and the per-micro-batch attention/SSM modules, and
``kvcache.evict_rows``) donate the cache buffers to XLA, which invalidates
the previous arrays — callers must never retain references into
``engine.cache`` across a decode tick (take ``np.asarray`` copies instead).
Weights are never donated (they are reused by every launch).

**Weight residency (the paper's S_Params / S_Expert, Fig. 6).**  Every
module stage pulls its parameters through a ``serving.weights.ParamStore``
handle instead of captured dicts.  By default the store pins everything on
device (``resident_bytes=None``); with ``stream_weights=True`` it realizes
``Plan.s_params`` as a greedy resident set (base embed/head first, then
mixers/norms, then expert stacks — ``workload.plan_residency``, the same
policy the planner's cost model charges misses with) and keeps the rest
host-side, served through a double-buffered in-flight window sized by
``Plan.s_expert``: the engine issues the async htod prefetch of layer
*l+1*'s streamed modules as soon as layer *l*'s acquire returns, so the
copy hides behind *l*'s compute with no host syncs.  Streamed generation
is token-for-token identical to fully-resident generation (property-tested
in tests/test_weights.py); transfer bytes and stall seconds are folded
into ``EngineStats`` by ``sync_stats()``.

Prefill shares the layer-major structure: each layer's weights are acquired
ONCE and reused across all ``b_a``-sequence micro-batches (module-based
batching's weight amortization), and each MoE layer splits into a
mixer+route launch and a ragged grouped-FFN launch over the routed copies
of the micro-batch's real prompt tokens, at a capacity of its token count,
so no routed copy is ever dropped and no host read sizes the launch
(``grouped_prefill=True``, the default);
``grouped_prefill=False`` opts prefill back into the exact dense-combine
reference MoE.

Outputs are bit-compatible with the reference ``models.decode_step`` up to
bf16 accumulation order (asserted in tests/test_engine.py).  Every module is
a separately jitted function — the JAX analogue of the paper's per-module
CUDA launches — except the fused chunk, which is the paper's point: one.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis import runtime as sanitizer
from repro.analysis.markers import hot_path
from repro.analysis.registry import TraceKeySet, register_jit
from repro.analysis.spans import span
from repro.configs.base import ModelConfig
from repro.core import workload as W
from repro.core.dag_builder import Plan
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.blocks import ffn_apply, layer_forward
from repro.models.layers import rms_norm
from repro.serving.sampling import sample_tokens
from repro.serving.weights import ParamStore, unstack_layers  # noqa: F401
from repro.sharding.specs import ShardCtx


# ---------------------------------------------------------------------------
# Dispatch accounting
# ---------------------------------------------------------------------------
_DISPATCHES = 0


def dispatch_count() -> int:
    """Python-side device-dispatch counter: every engine module launch
    (jitted callable invoked from the interpreter) increments it once.  The
    fused decode chunk is exactly ONE dispatch per ``T`` tokens — asserted
    by the regression test in tests/test_fused_decode.py."""
    return _DISPATCHES


def _counted(fn):
    """Wrap a jitted module so each Python-level launch is counted."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        global _DISPATCHES
        _DISPATCHES += 1
        return fn(*args, **kwargs)

    return wrapped


# ---------------------------------------------------------------------------
# Jitted module launches (the per-module path)
# ---------------------------------------------------------------------------
@_counted
@register_jit("engine.attn_decode", donated=("k", "v"))
@functools.partial(jax.jit, static_argnames=("cfg", "lo"),
                   donate_argnames=("k", "v"))
def _attn_decode_module(cfg, lo, p, x_mb, k, v, pos):
    """Decode attention over the micro-batch of batch rows ``[lo, lo+n)``.

    ``k``/``v`` are the layer's FULL ``(B, span, ...)`` cache buffers,
    DONATED: the micro-batch's rows are sliced out, updated, and written
    back with ``lax.dynamic_update_slice`` so XLA updates the cache in
    place instead of materializing a fresh copy per micro-batch (the seed's
    ``k.at[lo:hi].set`` whole-cache copy)."""
    n = x_mb.shape[0]
    h = rms_norm(x_mb[:, None, :], p["norm1"], cfg.norm_eps)
    ck = lax.dynamic_slice_in_dim(k, lo, n, axis=0)
    cv = lax.dynamic_slice_in_dim(v, lo, n, axis=0)
    y, cache = attn_mod.attn_decode(cfg, p["attn"], h, {"k": ck, "v": cv}, pos)
    k = lax.dynamic_update_slice_in_dim(k, cache["k"], lo, axis=0)
    v = lax.dynamic_update_slice_in_dim(v, cache["v"], lo, axis=0)
    return y[:, 0], k, v


@_counted
@register_jit("engine.mla_decode", donated=("ckv",))
@functools.partial(jax.jit, static_argnames=("cfg", "lo"),
                   donate_argnames=("ckv",))
def _mla_decode_module(cfg, lo, p, x_mb, ckv, pos):
    """Absorbed latent-attention decode over batch rows ``[lo, lo+n)``,
    with the layer's full latent cache DONATED and the rows written back
    in place (the contract of ``_attn_decode_module``)."""
    n = x_mb.shape[0]
    h = rms_norm(x_mb[:, None, :], p["norm1"], cfg.norm_eps)
    c = lax.dynamic_slice_in_dim(ckv, lo, n, axis=0)
    y, cache = attn_mod.mla_decode(cfg, p["mla"], h, {"ckv": c}, pos)
    ckv = lax.dynamic_update_slice_in_dim(ckv, cache["ckv"], lo, axis=0)
    return y[:, 0], ckv


@_counted
@register_jit("engine.ssm_decode", donated=("h", "conv"))
@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("h", "conv"))
def _ssm_decode_module(cfg, p, x, h, conv):
    """SSM decode over every batch row, with the state buffers donated and
    returned updated (the cache contract of the attention modules)."""
    z = rms_norm(x[:, None, :], p["norm1"], cfg.norm_eps)
    y, state = ssm_mod.ssm_decode(cfg, p["ssm"], z, {"h": h, "conv": conv})
    return y[:, 0], state["h"], state["conv"]


def _grouped_expert_math(cfg, p, x, capacity):
    """The whole decode MoE stage, traceable: norm -> route -> ragged
    gather by expert (``moe.ragged_dispatch``, at most ``capacity`` copies
    an expert) -> grouped FFN -> weighted scatter-add, plus the shared
    experts over every row where the layer has them.  Returns (y, kept,
    dropped, load, rows); the counters — including the (E,) per-expert
    routed histogram feeding the planner's measured-skew b_e search and
    the rows the ragged kernel computes — stay on device.  Launched
    standalone by the per-module path (``_grouped_expert_module``) and
    inlined by the fused decode chunk — ONE implementation, so both paths
    are bit-identical."""
    moe = p["moe"]
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    gates, idx, _ = moe_mod.route(cfg, moe["router"], h)
    y, kept, dropped, load = moe_mod.ragged_dispatch(
        cfg, h, gates, idx,
        moe["experts_w_gate"], moe["experts_w_up"], moe["experts_w_down"],
        capacity,
    )
    if "shared" in moe:
        y = y + moe_mod.shared_experts(moe, h)
    return y, kept, dropped, load, moe_mod.ragged_rows(
        load, capacity, x.shape[0], cfg.experts_per_token)


_grouped_expert_module = _counted(
    register_jit("engine.grouped_expert")(
        functools.partial(jax.jit, static_argnames=("cfg", "capacity"))(
            _grouped_expert_math
        )
    )
)


@_counted
@register_jit("engine.route_predict")
@functools.partial(jax.jit, static_argnames=("cfg", "khat"))
def _route_predict_module(cfg, khat, norm2_w, router_w, next_router_w, x):
    """Routing + next-layer expert prediction for the predictive-streamed
    MoE stage: norm2 -> route THIS layer, then score the NEXT streamed MoE
    layer's router on the current hidden state (``moe.predict_experts``).

    Returns ``(h, gates, idx, packed)`` where ``packed`` is one int32
    vector — the (E,) routed-copy counts of this layer (which experts'
    weights the grouped FFN actually needs, and the load histogram the
    capacity re-planner consumes) concatenated with the (k-hat,) predicted
    ids for the next layer — so the engine reads back EVERYTHING it needs
    under ONE planned transfer per layer."""
    h = rms_norm(x, norm2_w, cfg.norm_eps)
    gates, idx, _ = moe_mod.route(cfg, router_w, h)
    used = jnp.zeros((cfg.num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)
    pred = moe_mod.predict_experts(cfg, next_router_w, x, khat)
    return h, gates, idx, jnp.concatenate([used, pred])


@_counted
@register_jit("engine.grouped_expert_ffn")
@functools.partial(jax.jit, static_argnames=("cfg", "capacity"))
def _grouped_ffn_module(cfg, capacity, h, gates, idx, wg, wu, wd,
                        moe_pinned=None):
    """Grouped FFN over PRE-ROUTED tokens with externally assembled expert
    stacks — the second half of the predictive-streamed MoE stage, by the
    ragged dispatch of ``_grouped_expert_math`` and with its returns.  The
    stacks carry true weights for every expert with a routed copy and the
    zeros filler elsewhere (``ParamStore.acquire_experts``), which is
    bit-identical to the full stack: an unrouted expert takes no rows and
    its weights are never read.  The shared experts (pinned beside the
    router, ``moe_pinned``) are added over every row."""
    y, kept, dropped, load = moe_mod.ragged_dispatch(
        cfg, h, gates, idx, wg, wu, wd, capacity)
    if moe_pinned:
        y = y + moe_mod.shared_experts(moe_pinned, h)
    return y, kept, dropped, load, moe_mod.ragged_rows(
        load, capacity, h.shape[0], cfg.experts_per_token)


@_counted
@register_jit("engine.ffn")
@functools.partial(jax.jit, static_argnames=("cfg",))
def _ffn_module(cfg, p, x):
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    return ffn_apply(p["ffn"], h)


def _head_math(cfg, tie, params, x):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if tie else params["lm_head"]
    return h @ w


_head_module = _counted(
    register_jit("engine.head")(
        functools.partial(jax.jit, static_argnames=("cfg", "tie"))(_head_math)
    )
)


@jax.jit
def _last_positions(x, lengths):
    """Row ``b``'s hidden state at its true last prompt token, position
    ``lengths[b] - 1``, in one launch (eager indexing uploads its
    constants)."""
    return x[jnp.arange(x.shape[0]), lengths - 1]


@_counted
@register_jit("engine.embed")
@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed_module(cfg, embed, tokens):
    return jnp.take(embed, tokens, axis=0)


@_counted
@register_jit("engine.prefill_layer")
@functools.partial(jax.jit, static_argnames=("cfg", "kind", "ffn", "sctx"))
def _prefill_layer_module(cfg, kind, ffn, sctx, p, x, positions, lengths):
    """One full layer (mixer + FFN stage) over a prefill micro-batch.

    Prefill's per-layer launch unit: the engine iterates layers in the
    outer loop (weights acquired once per layer, reused by every
    micro-batch) and micro-batches in the inner loop.  ``sctx`` selects the
    MoE path — grouped prefill passes ``moe_capacity`` = the micro-batch
    token count, so no routed copy is dropped."""
    return layer_forward(cfg, kind, ffn, p, x, sctx, positions, lengths)


@_counted
@register_jit("engine.prefill_mixer_route")
@functools.partial(jax.jit, static_argnames=("cfg", "kind"))
def _prefill_mixer_route_module(cfg, kind, p, x, positions, lengths):
    """Mixer half of a grouped-prefill MoE layer, plus routing: norm1 ->
    attention/SSM -> residual -> norm2 -> route.  Also returns the
    micro-batch's max per-expert load, which a mesh engine reads back to
    size its collective dispatch's capacity at the next power of two over
    it (zero-drop holds for ANY capacity >= the max load); the
    single-device stage needs no capacity read."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        y, entry = attn_mod.attn_forward(cfg, p["attn"], h, ShardCtx(),
                                         positions, lengths)
    elif kind == "mla":
        y, entry = attn_mod.mla_forward(cfg, p["mla"], h, ShardCtx(),
                                        positions, lengths)
    else:
        y, entry = ssm_mod.ssm_forward(cfg, p["ssm"], h, ShardCtx(), lengths)
    x = x + y
    hh = rms_norm(x, p["norm2"], cfg.norm_eps)
    xt = hh.reshape(-1, x.shape[-1])
    gates, idx, _ = moe_mod.route(cfg, p["moe"]["router"], xt)
    load = jnp.zeros((cfg.num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)
    return x, entry, xt, gates, idx, jnp.max(load), aux


@_counted
@register_jit("engine.prefill_moe_ffn")
@functools.partial(jax.jit, static_argnames=("cfg", "capacity"))
def _prefill_moe_ffn_module(cfg, capacity, moe_p, x, xt, gates, idx,
                            lengths=None):
    """Expert half of the split prefill MoE layer: the ragged dispatch
    (``moe.ragged_dispatch``) of the routed copies the mixer half chose,
    at ``capacity`` >= the micro-batch's token count, so no copy drops.
    With ``lengths`` (the micro-batch's prompt lengths) only the copies of
    real positions take kernel rows; a pad position's expert output is
    zero, and nothing reads it (its key is masked, its logits unread).
    The shared experts, where the layer has them, run here over every row,
    outside the ragged kernel.  Returns the residual-added output, the
    dropped copies and the rows the ragged kernel computed."""
    B, S, D = x.shape
    valid = (None if lengths is None
             else (jnp.arange(S)[None, :] < lengths[:, None]).reshape(-1))
    y, _, dropped, load = moe_mod.ragged_dispatch(
        cfg, xt, gates, idx,
        moe_p["experts_w_gate"], moe_p["experts_w_up"],
        moe_p["experts_w_down"], capacity, valid=valid,
    )
    if "shared" in moe_p:
        y = y + moe_mod.shared_experts(moe_p, xt)
    rows = moe_mod.ragged_rows(load, capacity, B * S, cfg.experts_per_token)
    return x + y.reshape(B, S, D).astype(x.dtype), dropped, rows


# ---------------------------------------------------------------------------
# Paged decode modules (Mode B: KV host tier — serving.cache.KVPageTable)
# ---------------------------------------------------------------------------
@_counted
@register_jit("engine.paged_attn_decode", donated=("pk", "pv"))
@functools.partial(jax.jit, static_argnames=("cfg", "span"),
                   donate_argnames=("pk", "pv"))
def _paged_attn_decode_module(cfg, span, p, x_mb, pk, pv, ek, ev, frames,
                              pos, wpage, wframe):
    """Decode attention over paged KV.

    ``pk``/``pv`` are the layer's DONATED device page pools
    ``(P+1, pt, K, hd)`` (frame ``P`` is the null write sink); ``ek``/``ev``
    the layer's streamed host frames ``(H, pt, K, hd)`` (the page-tier
    analogue of a streamed weight module — fetched through the same
    ``StreamWindow``).  ``frames`` (n, PP) indexes the concat of both, so
    the gather reassembles each row's ``span`` exactly as the contiguous
    buffer holds it; the attention math is then bit-for-bit
    ``attn_decode`` on identical values.  The written page is extracted
    per row and scattered back at ``wframe`` (host-destined rows scatter
    into the null sink; the engine mirrors their write host-side from the
    returned ``k_new``/``v_new``)."""
    n = x_mb.shape[0]
    pt = pk.shape[1]
    PP = frames.shape[1]
    allk = jnp.concatenate([pk, ek], axis=0)
    allv = jnp.concatenate([pv, ev], axis=0)
    tail = pk.shape[2:]
    gk = allk[frames].reshape((n, PP * pt) + tail)[:, :span]
    gv = allv[frames].reshape((n, PP * pt) + tail)[:, :span]
    # the barrier pins the gather as a standalone producer, so the attn
    # subgraph compiles exactly like the contiguous module's (bit-identity)
    gk, gv = lax.optimization_barrier((gk, gv))
    h = rms_norm(x_mb[:, None, :], p["norm1"], cfg.norm_eps)
    y, upd = attn_mod.attn_decode(cfg, p["attn"], h, {"k": gk, "v": gv}, pos)
    posv = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (n,)
    )
    slot = jnp.where(cfg.sliding_window > 0, posv % span,
                     jnp.minimum(posv, span - 1))
    rows = jnp.arange(n)
    k_new = upd["k"][rows, slot]
    v_new = upd["v"][rows, slot]
    pad = PP * pt - span
    uk, uv = upd["k"], upd["v"]
    if pad:
        uk = jnp.pad(uk, ((0, 0), (0, pad), (0, 0), (0, 0)))
        uv = jnp.pad(uv, ((0, 0), (0, pad), (0, 0), (0, 0)))
    uk = uk.reshape((n, PP, pt) + tail)
    uv = uv.reshape((n, PP, pt) + tail)
    sel = wpage[:, None, None, None, None]
    wk_page = jnp.take_along_axis(uk, sel, axis=1)[:, 0]
    wv_page = jnp.take_along_axis(uv, sel, axis=1)[:, 0]
    pk = pk.at[wframe].set(wk_page)
    pv = pv.at[wframe].set(wv_page)
    return y[:, 0], pk, pv, k_new, v_new


@_counted
@register_jit("engine.suffix_layer")
@functools.partial(jax.jit, static_argnames=("cfg", "ffn", "sctx"))
def _suffix_layer_module(cfg, ffn, sctx, p, x, pk, pv, pos0):
    """One layer of SUFFIX prefill against a cached prefix (prefix-cache
    hit admission): the suffix queries attend the stored prefix KV
    concatenated with their own, offset to absolute positions ``pos0..``.
    KV at position p depends only on tokens <= p, so the produced suffix
    rows (and logits) are exactly what a full-prompt prefill would
    compute — the shared span costs ZERO prefill launches."""
    from repro.models.layers import apply_rope

    B, S, _ = x.shape
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    q, k, v = attn_mod._project_qkv(cfg, p["attn"], h)
    positions = pos0 + jnp.arange(S)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    ck = jnp.concatenate([pk, k], axis=1)
    cv = jnp.concatenate([pv, v], axis=1)
    out = attn_mod.naive_attention(q, ck, cv, causal=True, q_offset=pos0)
    o = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    x = x + o @ p["attn"]["wo"]
    if ffn == "moe":
        hh = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, _ = moe_mod.moe_apply(cfg, p["moe"], hh, sctx)
        x = x + y
    elif cfg.d_ff > 0 and "ffn" in p:
        hh = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + ffn_apply(p["ffn"], hh)
    return x, k, v


# ---------------------------------------------------------------------------
# The fused decode macro-step (ONE launch per T-token chunk)
# ---------------------------------------------------------------------------
@_counted
@register_jit("engine.fused_decode_chunk", donated=("cache",))
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "schema", "tie", "capacity", "pos_cap",
                     "use_topk", "greedy_only", "T"),
    donate_argnames=("cache",),
)
def _fused_decode_chunk(cfg, schema, tie, capacity, pos_cap, use_topk,
                        greedy_only, T, base, layer_params, tokens, pos,
                        live, cache, keys, steps, temps, topks):
    """The fused donated decode macro-step: embed → every layer of the
    schema (unrolled — the schema mixes attn/SSM and moe/dense stages) →
    head → per-slot sampling, scanned over ``T`` decode ticks entirely on
    device.  ONE launch per chunk.

    * ``cache`` is the engine's FULL-batch layer cache pytree, DONATED —
      each tick's KV/SSM writes land via ``lax.dynamic_update_slice`` /
      per-row scatter on the aliased buffers, so no whole-cache copy is
      ever materialized, and the caller's previous cache arrays are
      invalidated (the engine owns the pytree between ticks).
    * ``tokens``/``pos`` are the batch rows' current tokens and positions;
      both advance in-carry, with positions clamped at ``pos_cap`` exactly
      like the per-module scheduler tick.
    * ``live`` (B,) bool marks rows owned by an unfinished request: a dead
      (recycled/free) row's carry is HELD — it re-feeds its stale token at
      its stale position every tick, exactly like per-tick stepping, where
      the scheduler never updates a free slot's ``_cur``/``_pos``.  This is
      what keeps chunked decode tick-identical to per-tick decode even
      when expert-capacity drops couple rows through the grouped dispatch.
    * ``keys/steps/temps/topks`` are the rows' ``BatchSampler`` state;
      sampling inlines ``serving.sampling.sample_tokens`` (the SAME
      function the per-module sampler launches) with the token indices
      advancing in-carry, so seeded streams are bit-identical to
      per-module decode.

    Returns ``(toks (B, T), cache, kept, dropped, load, rows)`` —
    ``dropped`` a per-MoE-layer (n_moe,) vector, ``load`` the (n_moe, E)
    per-expert routed-copy histogram and ``rows`` the ragged expert
    kernel's computed rows, all accumulated in-carry on device.
    """
    n_moe = sum(1 for _, f in schema if f == "moe")
    E = max(1, cfg.num_experts)
    # optimization barriers mark the per-module boundaries inside the one
    # launch: XLA may not fuse across them, so every module subgraph
    # compiles exactly like its standalone per-module counterpart — which
    # is what makes the fused chunk BIT-identical to per-module decode
    # (cross-module fusion reassociates bf16 reductions otherwise).  The
    # barriers do not split the dispatch: the chunk is still one launch.
    bar = lax.optimization_barrier

    def tick(carry, _):
        toks, pos, cache, steps, kept, dropped, load, rows = carry
        cache = list(cache)
        x = bar(jnp.take(base["embed"], toks, axis=0))
        posv = jnp.minimum(pos, pos_cap)
        moe_j = 0
        for li, (kind, ffn) in enumerate(schema):
            p = layer_params[li]
            if kind == "attn":
                h = rms_norm(x[:, None, :], p["norm1"], cfg.norm_eps)
                y, upd = attn_mod.attn_decode(cfg, p["attn"], h, cache[li],
                                              posv)
                y, nk, nv = bar((y[:, 0], upd["k"], upd["v"]))
                cache[li] = {"k": nk, "v": nv}
                x = bar(x + y)
            elif kind == "mla":
                h = rms_norm(x[:, None, :], p["norm1"], cfg.norm_eps)
                y, upd = attn_mod.mla_decode(cfg, p["mla"], h, cache[li],
                                             posv)
                y, nc = bar((y[:, 0], upd["ckv"]))
                cache[li] = {"ckv": nc}
                x = bar(x + y)
            else:
                z = rms_norm(x[:, None, :], p["norm1"], cfg.norm_eps)
                y, st = ssm_mod.ssm_decode(cfg, p["ssm"], z, cache[li])
                y, nh, nc = bar((y[:, 0], st["h"], st["conv"]))
                cache[li] = {"h": nh, "conv": nc}
                x = bar(x + y)
            if ffn == "moe":
                y, kp, dr, ld, rw = _grouped_expert_math(cfg, p, x,
                                                         capacity)
                y, kp, dr, ld, rw = bar((y, kp, dr, ld, rw))
                kept = kept + kp
                rows = rows + rw
                dropped = dropped.at[moe_j].add(dr)
                load = load.at[moe_j].add(ld)
                moe_j += 1
                x = bar(x + y)
            elif cfg.d_ff > 0 and "ffn" in p:
                y = bar(ffn_apply(p["ffn"],
                                  rms_norm(x, p["norm2"], cfg.norm_eps)))
                x = bar(x + y)
        logits = bar(_head_math(cfg, tie, base, x))
        if greedy_only:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            nxt = sample_tokens(logits, keys, steps, temps, topks, use_topk)
        carry_tok = jnp.where(live, nxt, toks)     # dead rows hold stale tok
        carry_pos = pos + live.astype(pos.dtype)   # ...at their stale pos
        return (carry_tok, carry_pos, tuple(cache), steps + 1, kept,
                dropped, load, rows), nxt

    zero = jnp.zeros((), jnp.int32)
    carry0 = (tokens, pos, tuple(cache), steps, zero,
              jnp.zeros((n_moe,), jnp.int32), jnp.zeros((n_moe, E), jnp.int32),
              zero)
    (_, _, cache, _, kept, dropped, load, rows), ys = lax.scan(
        tick, carry0, None, length=T
    )
    return jnp.swapaxes(ys, 0, 1), cache, kept, dropped, load, rows


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
@dataclass
class EngineStats:
    attn_microbatches: int = 0
    expert_launches: int = 0             # grouped: one per MoE layer per step
    expert_tokens: int = 0               # routed token-copies processed
    expert_tokens_dropped: int = 0       # routed copies over the b_e capacity
    device_attn_tokens: int = 0          # rows through attn_decode
    weight_htod_bytes: int = 0           # streamed weight bytes copied htod
    prefetch_wait_s: float = 0.0         # stall waiting on weight transfers
    fused_dispatches: int = 0            # fused decode launches issued
    fused_ticks: int = 0                 # decode ticks served by fused launches
    decode_retraces: int = 0             # distinct fused (B, path, chunk) keys
    kv_htod_bytes: int = 0               # streamed KV-page bytes copied htod
    kv_dtoh_bytes: int = 0               # KV bytes spilled device->host
    expert_tokens_dropped_by_layer: Optional[np.ndarray] = None
    #                                      (n_moe,) int64 per-MoE-layer drops;
    #                                      sums to expert_tokens_dropped
    expert_load: Optional[np.ndarray] = None
    #                                      (n_moe, E) int64 routed-copy
    #                                      histogram (pre-capacity) — the
    #                                      measured-skew input to the
    #                                      planner's capacity_for_load
    expert_pred_hits: int = 0            # routed experts found prefetched
    expert_pred_misses: int = 0          # routed experts demand-fetched
    expert_lru_hits: int = 0             # routed experts served from the LRU
    expert_lru_bytes: int = 0            # device bytes the hot-expert LRU pins
    a2a_bytes: int = 0                   # interconnect bytes the mesh MoE
    #                                      stage exchanged (a2a dispatch +
    #                                      return; 0 off-mesh / psum path)
    collective_dispatches: int = 0       # mesh MoE stage launches (a2a/psum)
    transfer_retries: int = 0            # transient stream-fetch failures
    #                                      recovered by the retry policy
    #                                      (weight + expert + KV-page windows)
    transfer_timeouts: int = 0           # watchdog-expired acquire waits
    #                                      recovered by demand re-fetch
    prefill_expert_rows: int = 0         # rows the prefill expert kernel
    #                                      computed (ragged: used tiles x
    #                                      tile rows; mesh: E x capacity),
    #                                      per MoE layer per micro-batch
    prefill_routed_copies: int = 0       # routed copies of real prompt
    #                                      tokens those buffers carried
    decode_expert_rows: int = 0          # rows the ragged decode expert
    #                                      kernel computed (used tiles x
    #                                      tile rows), per MoE layer per tick


class ModuleBatchingEngine:
    """Executes a batching ``Plan`` over a real model.

    **MoE stage.**  Decode runs one jitted ragged grouped-dispatch launch
    per MoE layer (or one collective dispatch on a mesh ``sctx``); routing
    stays on device and ``plan.b_e`` bounds the routed copies an expert
    takes.  Prefill shares the ragged implementation
    (``grouped_prefill=True``, the default) over its real prompt tokens at
    a capacity of the micro-batch's token count, so zero
    ``expert_tokens_dropped`` at prefill by construction; pass
    ``grouped_prefill=False`` for the exact-reference dense-combine prefill.

    **Attention.**  Every batch row attends on the engine's device, in
    micro-batches of ``plan.b_a`` rows on the per-module path.  The
    planner's host-attention share ω is not built (ROADMAP A8): a plan
    with ω > 0 is refused.

    **Fused decode selection.**  ``decode_chunk``/``decode_step_sampled``
    take the fused one-launch path automatically when ``fused_decode=True``
    (default), there is no mesh, and the store and the KV pages are fully
    resident (``fused_eligible()``).  Streamed residency falls back to the
    per-module loop (the prefetch needs the layer boundary).
    ``fused_decode=False`` forces the per-module path — the oracle the
    fused path is property-tested against.

    **Weight residency.**  All module stages read parameters through
    ``self.store`` (a ``serving.weights.ParamStore``).  By default every
    weight is device-resident.  ``stream_weights=True`` keeps only the plan's
    ``s_params`` greedy resident set on device and streams the rest from
    host through a double-buffered async prefetch window (``prefetch=False``
    degrades to serialized on-demand fetches); ``resident_bytes`` overrides
    the budget.  A pre-built ``store`` can be passed directly.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict,
        plan: Plan,
        max_seq: int = 512,
        grouped_prefill: bool = True,
        store: Optional[ParamStore] = None,
        stream_weights: bool = False,
        resident_bytes: Optional[float] = None,
        prefetch: bool = True,
        fused_decode: bool = True,
        cache_config=None,
        sctx: Optional[ShardCtx] = None,
        ep_chunks: int = 1,
        ep_serial: bool = False,
    ) -> None:
        if plan.omega > 0:
            raise ValueError(
                f"plan.omega={plan.omega}: host-side attention is not built "
                "(ROADMAP A8); every row attends on the engine's device, so "
                "plan with omega=0"
            )
        self.cfg = cfg
        self.plan = plan
        self.max_seq = max_seq
        self.grouped_prefill = grouped_prefill
        self.fused_decode = fused_decode
        # mesh engine (ShardCtx threading — the moe_dispatch='a2a'/'psum'
        # paths were unreachable from the engine before): a ShardCtx with a
        # mesh + model axis routes the grouped MoE stage through the
        # collective dispatch in repro.distributed.ep_engine; everything
        # else (attention, prefill, sampling) stays the single-device path
        self.sctx = (sctx if sctx is not None and sctx.mesh is not None
                     and sctx.model_axis is not None else None)
        self.ep_chunks = max(1, int(ep_chunks))
        self.ep_serial = bool(ep_serial)
        self._ep_params: Dict = {}       # per-layer mesh-placed MoE params
        if self.sctx is not None:
            from repro.distributed.ep_engine import validate_ep_shard

            validate_ep_shard(cfg, self.sctx)
            if self.sctx.moe_dispatch == "a2a" and plan.predict_topk > 0:
                raise ValueError(
                    "moe_dispatch='a2a' does not compose with predictive "
                    "per-expert streaming (predict_topk > 0) for now: the "
                    "a2a stage needs every rank's expert shard resident"
                )
            if stream_weights:
                raise ValueError(
                    "stream_weights does not compose with a mesh ShardCtx: "
                    "the collective stage needs resident expert shards"
                )
            if not grouped_prefill:
                raise ValueError(
                    "a mesh ShardCtx prefills through the collective MoE "
                    "stage; grouped_prefill=False (the dense-combine "
                    "reference) is single-device only"
                )
            if cache_config is not None and cache_config.prefix_cache:
                raise ValueError(
                    "prefix-cache hits prefill the suffix single-device; "
                    "they do not compose with a mesh ShardCtx"
                )
        # KV paging (serving.cache): None / disabled keeps the legacy
        # contiguous buffers; the table is (re)built per init_cache batch
        self.cache_config = cache_config
        self.pages = None
        if store is None:
            store = ParamStore.build(
                cfg, params, plan, stream_weights=stream_weights,
                resident_bytes=resident_bytes, prefetch=prefetch,
                sctx=self.sctx,
            )
        self.store = store
        if self.sctx is not None and not store.fully_resident:
            raise ValueError(
                "a mesh ShardCtx needs a fully resident ParamStore: the "
                "collective MoE stage shards whole expert stacks across "
                "the model axis and cannot stream them"
            )
        # [(kind, ffn)] per layer: the leading dense layers (pinned in the
        # store's ``lead``, outside its layer index), then the store's own
        self.n_lead = len(store.lead)
        self.schema = store.lead_schema + store.schema
        # kept for introspection/back-compat: (kind, ffn, _) triples
        self.layers: List[Tuple[str, str, None]] = [
            (k, f, None) for k, f in self.schema
        ]
        self.cache: Optional[List] = None
        self.stats = EngineStats()
        # device-side counters, folded into `stats` by sync_stats(); keeping
        # them lazy is what lets decode_step run without a single host sync.
        # Drops and routed-load histograms accumulate PER MoE LAYER — the
        # vectors stay on device (vector += vector only, no indexing inside
        # decode regions, which would upload index scalars under the
        # transfer guard).
        self._moe_layers = [li for li, (_, f) in enumerate(self.schema)
                            if f == "moe"]
        self._moe_index = {li: j for j, li in enumerate(self._moe_layers)}
        n_moe = len(self._moe_layers)
        E = max(1, cfg.num_experts)
        self._kept_dev = jnp.zeros((), jnp.int32)
        self._rows_dev = jnp.zeros((), jnp.int32)
        self._prefill_rows_dev = jnp.zeros((), jnp.int32)
        self._dropped_dev_l = [jnp.zeros((), jnp.int32)
                               for _ in range(n_moe)]
        self._load_dev_l = [jnp.zeros((E,), jnp.int32) for _ in range(n_moe)]
        self._dropped_chunk_dev = jnp.zeros((n_moe,), jnp.int32)
        self._load_chunk_dev = jnp.zeros((n_moe, E), jnp.int32)
        # online capacity re-plan hook (serving.Server): overrides the
        # plan's b_e when measured routing skew drifts; None = plan value
        self._b_e_override: Optional[int] = None
        # predictive-streaming test seam: when set, a callable
        # ``predictor(next_layer, khat) -> iterable expert ids`` replaces
        # the device-computed prediction for PREFETCH decisions only —
        # correctness is predictor-independent (mispredictions demand-fetch)
        self.predictor = None
        self._batch = 0
        # fused-path bookkeeping: per-layer param tuple (aliases the
        # resident arrays) and the set of (B, path, chunk) trace keys seen
        # (a new key = one XLA retrace, surfaced as stats.decode_retraces;
        # the TraceKeySet registers with repro.analysis so the sanitizer
        # report folds it in next to the XLA compile counts)
        self._fused_params: Optional[Tuple[Dict, ...]] = None
        self._fused_keys = TraceKeySet("engine.fused_decode_chunk")

    def _acquire(self, li: int, experts: bool = True) -> Dict:
        """Layer ``li``'s parameters: a leading dense layer from the store's
        pinned ``lead``, any later one through ``store.acquire`` (whose
        index starts after them)."""
        if li < self.n_lead:
            return self.store.lead[li]
        return self.store.acquire(li - self.n_lead, experts=experts)

    def _prefetch(self, li: int) -> None:
        """``store.prefetch`` of layer ``li`` (wrapping past the last layer
        to the store's first); the pinned leading layers need none."""
        if li >= self.n_lead:
            self.store.prefetch(li - self.n_lead)

    def _expert_capacity(self, batch: int) -> int:
        """Per-expert capacity C: the plan's b_e (or the online re-plan
        override), clamped to the most tokens any one expert can receive
        (top-k indices are distinct per token)."""
        b_e = (self.plan.b_e if self._b_e_override is None
               else self._b_e_override)
        return max(1, min(b_e, batch))

    def set_expert_capacity(self, b_e: Optional[int]) -> None:
        """Online capacity re-plan entry point (``Server`` calls this when
        measured routing skew drifts): override the plan's ``b_e`` for
        subsequent decode dispatches.  ``None`` restores the plan value.
        Changing capacity changes the dispatch-buffer shape, so the next
        fused chunk retraces ONCE (counted in ``decode_retraces``)."""
        self._b_e_override = None if b_e is None else max(1, int(b_e))

    def sync_stats(self) -> EngineStats:
        """Materialize the device-side expert counters (one host sync) and
        drain the store's transfer + predictive-streaming accounting."""
        kept, rows, prows = jax.device_get(
            (self._kept_dev, self._rows_dev, self._prefill_rows_dev))
        self.stats.expert_tokens += int(kept)
        self.stats.decode_expert_rows += int(rows)
        self.stats.prefill_expert_rows += int(prows)
        self._kept_dev = jnp.zeros((), jnp.int32)
        self._rows_dev = jnp.zeros((), jnp.int32)
        self._prefill_rows_dev = jnp.zeros((), jnp.int32)
        n_moe = len(self._moe_layers)
        if n_moe:
            E = self._load_chunk_dev.shape[1]
            dropped = np.asarray(self._dropped_chunk_dev, np.int64) + np.array(
                [int(d) for d in self._dropped_dev_l], np.int64
            )
            load = np.asarray(self._load_chunk_dev, np.int64) + np.stack(
                [np.asarray(v, np.int64) for v in self._load_dev_l]
            )
            self.stats.expert_tokens_dropped += int(dropped.sum())
            if self.stats.expert_tokens_dropped_by_layer is None:
                self.stats.expert_tokens_dropped_by_layer = np.zeros(
                    n_moe, np.int64
                )
                self.stats.expert_load = np.zeros((n_moe, E), np.int64)
            self.stats.expert_tokens_dropped_by_layer += dropped
            self.stats.expert_load += load
            self._dropped_dev_l = [jnp.zeros((), jnp.int32)
                                   for _ in range(n_moe)]
            self._load_dev_l = [jnp.zeros((E,), jnp.int32)
                                for _ in range(n_moe)]
            self._dropped_chunk_dev = jnp.zeros((n_moe,), jnp.int32)
            self._load_chunk_dev = jnp.zeros((n_moe, E), jnp.int32)
        htod, wait = self.store.take_counters()
        self.stats.weight_htod_bytes += htod
        self.stats.prefetch_wait_s += wait
        take_ec = getattr(self.store, "take_expert_counters", None)
        if take_ec is not None:
            ec = take_ec()
            self.stats.expert_pred_hits += ec["pred_hits"]
            self.stats.expert_pred_misses += ec["pred_misses"]
            self.stats.expert_lru_hits += ec["lru_hits"]
            self.stats.expert_lru_bytes = ec["lru_bytes_used"]
        if self.pages is not None:
            kv_htod, kv_dtoh = self.pages.take_counters()
            self.stats.kv_htod_bytes += kv_htod
            self.stats.kv_dtoh_bytes += kv_dtoh
        for taker in (getattr(self.store, "take_fault_counters", None),
                      getattr(self.pages, "take_fault_counters", None)):
            if taker is not None:
                retries, timeouts = taker()
                self.stats.transfer_retries += retries
                self.stats.transfer_timeouts += timeouts
        return self.stats

    # -- cache management ---------------------------------------------
    def init_cache(self, batch: int) -> None:
        from repro.models.blocks import init_layer_cache

        self.cache = []
        self._batch = batch
        self.pages = None
        cc = self.cache_config
        if (cc is not None and cc.enabled
                and any(k == "attn" for k, _ in self.schema)):
            from repro.serving.cache import KVPageTable

            self.pages = KVPageTable(
                self.cfg, self.schema, batch, self.max_seq, cc
            )
        paged_b = self.pages is not None and not self.pages.fully_resident
        for kind, _ in self.schema:
            if kind == "attn" and paged_b:
                # Mode B: KV content lives in the page pools; the empty
                # dict keeps the cache pytree tree.map/evict-safe
                self.cache.append({})
            else:
                self.cache.append(
                    init_layer_cache(self.cfg, kind, batch, self.max_seq)
                )

    def _write_cache_rows(self, li: int, kind: str, entry: Dict, rows,
                          rows_dev=None) -> None:
        """Insert a micro-batch's raw prefill cache into batch rows ``rows``
        of layer ``li``'s decode buffer (``kvcache.insert_prefill_rows``,
        given ``rows_dev``, the same rows on the device, where the caller
        has them) — or, under paging, into the rows' page frames
        (allocated on first touch, device tier first)."""
        from repro.serving.kvcache import aligned_kv, insert_prefill_rows

        if kind == "attn" and self.pages is not None:
            rows_l = [int(r) for r in np.asarray(rows).reshape(-1)]
            self.pages.ensure_rows(rows_l)
            if not self.pages.fully_resident:
                nk, nv = aligned_kv(
                    self.cfg, entry["k"], entry["v"], self.pages.span
                )
                self.pages.insert_rows(li, nk, nv, rows_l)
                return
        self.cache[li] = insert_prefill_rows(
            self.cfg, kind, self.cache[li], entry,
            rows if rows_dev is None else rows_dev
        )

    def evict_slots(self, rows) -> None:
        """Recycle batch slots: zero the contiguous rows (one donated
        ``kvcache.evict_rows`` launch) and return any page frames to the
        table's free lists.  THE slot-recycling entry point — callers must
        not evict the cache list directly once paging is on."""
        from repro.serving.kvcache import evict_rows

        assert self.cache is not None
        stale = self._stale_snapshot()
        self.cache = evict_rows(self.cache, rows)
        if self.pages is not None:
            self.pages.free_rows([int(r) for r in np.asarray(rows).reshape(-1)])
        self._poison_stale(stale)

    def reserve_slot_rows(self, rows) -> None:
        """Pre-admission page-frame reservation for batch rows ``rows``
        (no-op without paging; idempotent — ``_write_cache_rows`` reuses
        the placement).  Raises ``faults.PageAllocOOM`` when the table is
        out of frames (or an armed fault plan injects one) BEFORE any
        prefill compute is spent, so the scheduler can degrade gracefully
        (defer / demote / shrink) instead of aborting mid-wave."""
        if self.pages is None:
            return
        self.pages.ensure_rows([int(r) for r in np.asarray(rows).reshape(-1)])

    # -- preemption checkpoints -------------------------------------------
    def checkpoint_slot(self, slot: int) -> List[Dict[str, np.ndarray]]:
        """Snapshot batch row ``slot``'s FULL per-layer decode state as
        host-side numpy (attention KV rows — contiguous or paged — and SSM
        h/conv state): the KV half of a request preemption checkpoint.
        Host copies are donation-safe to retain across later ticks."""
        from repro.serving.kvcache import snapshot_row

        assert self.cache is not None
        slot = int(slot)
        out: List[Dict[str, np.ndarray]] = []
        with sanitizer.allowed("ckpt-save"):
            for li, (kind, _) in enumerate(self.schema):
                if (kind == "attn" and self.pages is not None
                        and not self.pages.fully_resident):
                    k, v = self.pages.read_row(li, slot, self.pages.span)
                    out.append({"k": k, "v": v})
                else:
                    out.append(snapshot_row(self.cache[li], slot))
        return out

    def restore_slot(self, slot: int, state: List[Dict[str, np.ndarray]]) -> None:
        """Write a ``checkpoint_slot`` snapshot back into batch row
        ``slot`` (resume): page frames are re-reserved (may raise
        ``PageAllocOOM`` — the resume then stays queued) and every layer's
        rows are restored eagerly.  With the sampler key/step and ``pos``
        restored by the scheduler, decode continues bit-identical to the
        unpreempted run — zero prefill relaunches."""
        from repro.serving.kvcache import restore_row

        assert self.cache is not None
        slot = int(slot)
        if self.pages is not None:
            self.reserve_slot_rows([slot])
        with sanitizer.allowed("ckpt-restore"):
            for li, (kind, _) in enumerate(self.schema):
                st = state[li]
                if (kind == "attn" and self.pages is not None
                        and not self.pages.fully_resident):
                    self.pages.insert_rows(
                        li, jnp.asarray(st["k"])[None],
                        jnp.asarray(st["v"])[None], [slot]
                    )
                    continue
                self.cache[li] = restore_row(self.cache[li], slot, st)

    # -- sanitizer hooks -------------------------------------------------
    def _stale_snapshot(self) -> Optional[List]:
        """Pre-launch array leaves of every buffer the decode tick may
        donate (cache pytree + page pools) — captured only in poison mode
        so ``_poison_stale`` can invalidate whatever XLA didn't consume."""
        san = sanitizer.current()
        if san is None or not san.poison or self.cache is None:
            return None
        trees = [self.cache]
        if self.pages is not None:
            trees.extend([self.pages.pool_k, self.pages.pool_v])
        return jax.tree.leaves(trees)

    def _poison_stale(self, stale: Optional[List]) -> None:
        """Debug-mode stale-buffer poisoner (ROADMAP cache-donation
        contract): delete pre-launch buffers that are neither part of the
        rebound cache/pools nor already consumed by donation, so retained
        references into ``engine.cache``/``pool_k``/``pool_v`` across a
        tick fail loudly instead of reading garbage."""
        if stale is None:
            return
        trees = [self.cache]
        if self.pages is not None:
            trees.extend([self.pages.pool_k, self.pages.pool_v])
        sanitizer.poison_stale(stale, trees)

    # -- phases ---------------------------------------------------------
    def _prefill_sctx(self, mb_tokens: int) -> ShardCtx:
        """MoE path for prefill: the grouped dispatch shared with decode,
        with per-expert capacity auto-raised to the micro-batch token count
        — an upper bound on any expert's routed load, so zero drops (and
        thus exactness) by construction, at most E/k x the balanced
        per-expert load at B*S for the planner's b_a."""
        if self.grouped_prefill and self.cfg.has_moe:
            return ShardCtx(moe_dispatch="grouped",
                            moe_capacity=max(1, mb_tokens))
        return ShardCtx()

    def prefill(self, tokens: jax.Array, frontend_emb=None, lengths=None) -> jax.Array:
        """Prefill (attention micro-batched by b_a sequences), filling the
        engine cache.  Returns last logits.

        ``lengths`` (B,) makes a ragged right-padded batch exact: pads are
        masked out of attention/SSM state and each sequence's logits come
        from its true last token.
        """
        B, S = tokens.shape
        self.init_cache(B)
        return self.prefill_slots(
            tokens, np.arange(B), lengths=lengths, frontend_emb=frontend_emb
        )

    def prefill_slots(
        self, tokens: jax.Array, rows, lengths=None, frontend_emb=None
    ) -> jax.Array:
        """Prefill ``tokens`` (n, S) into existing batch rows ``rows`` (n,).

        Layer-major module batching: the outer loop walks layers — each
        layer's weights are pulled through the store ONCE (streamed modules
        prefetched a layer ahead) and reused by every ``b_a``-sequence
        micro-batch of the inner loop.  Also the continuous scheduler's
        admission path: newcomers are prefilled into the slots freed by
        finished sequences, overwriting those rows' KV-cache and SSM state
        while every other slot's state is untouched.  Returns the
        newcomers' last-token logits (n, V).
        """
        cfg, plan = self.cfg, self.plan
        assert self.cache is not None, "init_cache/prefill before prefill_slots"
        n, S = tokens.shape
        assert S <= self.max_seq
        if cfg.sliding_window:
            assert S <= cfg.sliding_window, "engine prefill requires prompt <= window"
        rows = np.asarray(rows)
        # the host copy counts prompt tokens without a device sync (the
        # server passes numpy lengths)
        lens_host = None if lengths is None else np.asarray(lengths)
        lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)
        b_a = max(1, min(plan.b_a, n))
        spans = [(lo, min(n, lo + b_a)) for lo in range(0, n, b_a)]
        positions = jnp.arange(S)[None, :]
        # cut the per-micro-batch inputs once, at static bounds: an eager
        # row slice uploads its start index, and the layer loop would make
        # one such upload (and one of its cache rows) per layer and
        # micro-batch
        cut = [lo for lo, _ in spans[1:]]
        lens_mb = None if lengths is None else jnp.split(lengths, cut)
        rows_mb = jnp.split(jnp.asarray(rows, jnp.int32), cut)
        xs = []
        for (lo, hi), tok in zip(spans, jnp.split(tokens, cut)):
            x = _embed_module(cfg, self.store.base["embed"], tok)
            if frontend_emb is not None:
                fe = frontend_emb[lo:hi]
                F = fe.shape[1]
                x = jnp.concatenate([fe.astype(x.dtype), x[:, F:]], axis=1)
            xs.append(x)
        for li, (kind, ffn) in enumerate(self.schema):
            with span("engine.layer", layer=li, phase="prefill",
                      mixer=kind, ffn=ffn):
                xs = self._prefill_layer(li, kind, ffn, xs, spans, rows,
                                         rows_mb, positions, lens_mb,
                                         lens_host, S)
        self.stats.attn_microbatches += len(spans)
        # each micro-batch's last real position, without a copy of the
        # whole wave's activations
        if lengths is None:
            h_last = jnp.concatenate([x[:, -1] for x in xs], axis=0)
        else:
            h_last = jnp.concatenate(
                [_last_positions(x, ln) for x, ln in zip(xs, lens_mb)],
                axis=0)
        return _head_module(cfg, cfg.tie_embeddings, self.store.base, h_last)

    def _prefill_layer(self, li, kind, ffn, xs, spans, rows, rows_mb,
                       positions, lens_mb, lens_host, S) -> List[jax.Array]:
        """Layer ``li`` of ``prefill_slots`` over every micro-batch
        (``rows_mb``/``lens_mb``: each micro-batch's cache rows and prompt
        lengths, already on the device)."""
        cfg = self.cfg
        p = self._acquire(li)
        self._prefetch(li + 1)          # hide l+1's copy behind this layer
        # grouped-prefill MoE layers split into mixer+route / expert
        # launches: on one device the expert launch dispatches ragged over
        # the real tokens' copies at a capacity of the micro-batch's token
        # count, read back nowhere; a mesh engine reads the measured max
        # expert load to size its collective dispatch at the next pow2
        split_moe = ffn == "moe" and self.grouped_prefill
        outs = []
        for m, (lo, hi) in enumerate(spans):
            # drop the caller's reference as the micro-batch is consumed:
            # the wave's activations are then held once, not twice
            x, xs[m] = xs[m], None
            ln = None if lens_mb is None else lens_mb[m]
            if split_moe:
                x_mid, entry, xt, gates, idx, max_load, _ = (
                    _prefill_mixer_route_module(
                        cfg, kind, p, x, positions, ln
                    )
                )
                tokens = ((hi - lo) * S if lens_host is None
                          else int(lens_host[lo:hi].sum()))
                self.stats.prefill_routed_copies += (
                    cfg.experts_per_token * tokens)
                if self.sctx is not None:
                    with sanitizer.allowed("prefill-capacity-probe"):
                        cap = W.next_pow2(int(np.asarray(max_load)))
                    self.stats.prefill_expert_rows += cfg.num_experts * cap
                    y = x_mid + self._mesh_moe(li, p, x_mid, cap,
                                               (xt, gates, idx))
                else:
                    y, _, kernel_rows = _prefill_moe_ffn_module(
                        cfg, (hi - lo) * S, p["moe"], x_mid, xt, gates,
                        idx, ln
                    )
                    self._prefill_rows_dev = (self._prefill_rows_dev
                                              + kernel_rows)
            else:
                sctx = self._prefill_sctx((hi - lo) * S)
                y, entry, _ = _prefill_layer_module(
                    cfg, kind, ffn, sctx, p, x, positions, ln
                )
            self._write_cache_rows(li, kind, entry, rows[lo:hi], rows_mb[m])
            outs.append(y)
        return outs

    # -- prefix caching ---------------------------------------------------
    def read_prefix_rows(self, slot: int, pspan: int) -> List:
        """Copy the first ``pspan`` KV slots of batch row ``slot`` out of
        every attention layer as numpy ``(k, v)`` pairs — the capture side
        of the prefix cache (host-side copies, safe to retain across the
        donated decode ticks)."""
        out = []
        for li, (kind, _) in enumerate(self.schema):
            assert kind == "attn", "prefix capture requires attention-only"
            if self.pages is not None and not self.pages.fully_resident:
                out.append(self.pages.read_row(li, slot, pspan))
            else:
                out.append((np.asarray(self.cache[li]["k"][slot, :pspan]),
                            np.asarray(self.cache[li]["v"][slot, :pspan])))
        return out

    def prefill_prefix_hit(self, slot: int, prompt, prefix_kvs,
                           pos0: int) -> jax.Array:
        """Admit a prefix-cache HIT into batch row ``slot``: the stored
        prefix KV rows are copied in (KV at position p depends only on
        tokens <= p, so they equal what full prefill would write) and only
        the suffix ``prompt[pos0:]`` is prefilled, its queries attending
        prefix+suffix at absolute positions.  Launch count is
        ``n_layers + 2`` (embed + one suffix module per layer + head) —
        INDEPENDENT of the prefix length: the shared span costs zero
        prefill launches.  Returns the (1, V) last-token logits."""
        cfg = self.cfg
        assert self.cache is not None
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert 0 < pos0 < len(prompt), (pos0, len(prompt))
        suffix = jnp.asarray(prompt[pos0:])[None, :]
        S_suf = int(suffix.shape[1])
        x = _embed_module(cfg, self.store.base["embed"], suffix)
        sctx = self._prefill_sctx(S_suf)
        pos0j = jnp.asarray(pos0, jnp.int32)
        for li, (kind, ffn) in enumerate(self.schema):
            assert kind == "attn", "prefix cache requires attention-only"
            p = self._acquire(li)
            self._prefetch(li + 1)
            pk = jnp.asarray(prefix_kvs[li][0])[None]
            pv = jnp.asarray(prefix_kvs[li][1])[None]
            x, ks, vs = _suffix_layer_module(cfg, ffn, sctx, p, x, pk, pv,
                                             pos0j)
            entry = {"k": jnp.concatenate([pk, ks], axis=1),
                     "v": jnp.concatenate([pv, vs], axis=1)}
            self._write_cache_rows(li, "attn", entry, [slot])
        self.stats.attn_microbatches += 1
        return _head_module(cfg, cfg.tie_embeddings, self.store.base,
                            x[:, -1])

    # -- path selection ---------------------------------------------------
    def fused_eligible(self) -> bool:
        """True when decode can take the fused one-launch path: fused
        decode enabled and EVERY weight resident on device (streamed layers
        keep the per-layer dispatch loop so the htod prefetch has a layer
        boundary to overlap with).  Same contract for KV pages: a
        fully-device-resident page pool (Mode A) keeps the fused path
        BIT-identical, any host-tier page falls back to the per-layer loop
        like streamed weights.  A mesh engine (``sctx``) always decodes
        per-module: the collective MoE stage needs its own launch boundary
        between the attention and FFN stages."""
        return (self.fused_decode and self.sctx is None
                and self.store.fully_resident
                and (self.pages is None or self.pages.fully_resident))

    def _fused_layer_params(self) -> Tuple[Dict, ...]:
        if self._fused_params is None:
            self._fused_params = (tuple(self.store.lead)
                                  + self.store.fused_layer_params())
        return self._fused_params

    # -- decode -----------------------------------------------------------
    def decode_step(self, tokens: jax.Array, pos) -> jax.Array:
        """One PER-MODULE decode step for all B sequences; returns logits.

        ``pos`` is the write/attend position: a scalar for uniform batches,
        or a per-sequence (B,) vector for ragged batches and the continuous
        scheduler (each slot decodes at its own sequence position).

        Streamed layers pipeline with compute: layer *l+1*'s weight
        prefetch is issued as soon as ``acquire(l)`` returns, before layer
        *l*'s mixer stage, so the htod copy is in flight while the host
        dispatches *l*'s attention / SSM and FFN / grouped-GEMM launches
        (the last layer's wraps to layer 0 for the next step).  (The
        fused one-launch path lives in ``decode_chunk``; this method is
        the per-module oracle and the streamed execution path.)
        """
        stale = self._stale_snapshot()
        with sanitizer.allowed("decode-inputs"):
            pos = jnp.asarray(pos, jnp.int32)
            tokens = jnp.asarray(tokens)
        with sanitizer.decode_region():
            logits = self._decode_rows(tokens, pos)
        self._poison_stale(stale)
        return logits

    @hot_path
    def _decode_rows(self, tokens, pos, pos_host=None) -> jax.Array:
        """Per-module decode over every batch row — ``tokens`` (B,) and
        ``pos`` (B,)/scalar.

        ``pos_host`` is the rows' positions as a host (numpy) mirror —
        Mode B paging does host-side position math for its page-table
        bookkeeping, and threading the mirror from the caller keeps that
        at ONE planned readback per tick instead of one per layer."""
        cfg = self.cfg
        if (pos_host is None and self.pages is not None
                and not self.pages.fully_resident):
            with sanitizer.allowed("decode-pos-host-mirror"):
                pos_host = np.asarray(pos, np.int32)  # lint: allow[MG101] planned once-per-tick position readback for the page table
        x = _embed_module(cfg, self.store.base["embed"], tokens)
        for li, (kind, ffn) in enumerate(self.schema):
            with span("engine.layer", layer=li, phase="decode",
                      mixer=kind, ffn=ffn):
                x = self._decode_layer(li, kind, ffn, x, pos, pos_host)
        return _head_module(cfg, cfg.tie_embeddings, self.store.base, x)

    @hot_path
    def _decode_layer(self, li, kind, ffn, x, pos, pos_host) -> jax.Array:
        """Layer ``li`` of ``_decode_rows``."""
        cfg = self.cfg
        # predictive-streamed MoE layers skip the full expert-stack
        # assembly in acquire(): the stage fetches only the experts the
        # router actually used (plus LRU hits) and prefetches the
        # predicted set for the next streamed MoE layer
        predictive = (ffn == "moe"
                      and self.store.streams_experts(li - self.n_lead))
        p = self._acquire(li, experts=not predictive)
        self._prefetch(li + 1)          # l+1's copy rides under l's stages
        if kind == "attn":
            x = x + self._attention_stage(li, p, x, pos, pos_host)
        elif kind == "mla":
            x = x + self._mla_stage(li, p, x, pos)
        else:
            y, h, conv = _ssm_decode_module(
                cfg, p, x, self.cache[li]["h"], self.cache[li]["conv"]
            )
            self.cache[li] = {"h": h, "conv": conv}
            x = x + y
        if self.pages is not None:
            self.pages.prefetch(li + 1)  # next layer's host KV frames
        if ffn == "moe":
            if predictive:
                x = x + self._expert_stage_predictive(li, x)
            else:
                x = x + self._expert_stage_grouped(li, p, x)
        elif cfg.d_ff > 0 and "ffn" in p:
            x = x + _ffn_module(cfg, p, x)
        return x

    # -- module stages ---------------------------------------------------
    def _attention_stage(self, li, p, x, pos, pos_host=None) -> jax.Array:
        """Micro-batched attention, ``plan.b_a`` rows a launch.

        The cache buffers are threaded through the donated row-block
        modules — each micro-batch's rows are updated in place; no
        whole-cache functional copy is made.
        """
        if self.pages is not None and not self.pages.fully_resident:
            return self._paged_attention_stage(li, p, x, pos, pos_host)
        bounds, mb_xs, mb_ps = self._microbatches(x, pos)
        k, v = self.cache[li]["k"], self.cache[li]["v"]
        outs = []
        for (lo, hi), mb_x, mb_pos in zip(bounds, mb_xs, mb_ps):
            y, k, v = _attn_decode_module(self.cfg, lo, p, mb_x, k, v, mb_pos)
            outs.append(y)
            self.stats.attn_microbatches += 1
            self.stats.device_attn_tokens += hi - lo
        self.cache[li]["k"], self.cache[li]["v"] = k, v
        return jnp.concatenate(outs, axis=0)

    def _mla_stage(self, li, p, x, pos) -> jax.Array:
        """Micro-batched absorbed latent-attention decode over the layer's
        latent cache, threaded through the donated row-block module."""
        bounds, mb_xs, mb_ps = self._microbatches(x, pos)
        ckv = self.cache[li]["ckv"]
        outs = []
        for (lo, hi), mb_x, mb_pos in zip(bounds, mb_xs, mb_ps):
            y, ckv = _mla_decode_module(self.cfg, lo, p, mb_x, ckv, mb_pos)
            outs.append(y)
            self.stats.attn_microbatches += 1
            self.stats.device_attn_tokens += hi - lo
        self.cache[li]["ckv"] = ckv
        return jnp.concatenate(outs, axis=0)

    def _microbatches(self, x, pos):
        """The attention micro-batches of ``plan.b_a`` rows: their
        ``[lo, hi)`` bounds, and ``x`` and ``pos`` cut at them by one split
        at static sizes — eager basic slicing would upload each
        micro-batch's start index, and on the chip that upload waits
        behind the next layer's weight copy in flight."""
        n = x.shape[0]
        b_a = max(1, min(self.plan.b_a, n))
        bounds = [(lo, min(n, lo + b_a)) for lo in range(0, n, b_a)]
        sizes = [hi - lo for lo, hi in bounds]
        mb_ps = ([pos] * len(sizes) if pos.ndim == 0
                 else jax.lax.split(pos, sizes))
        return bounds, jax.lax.split(x, sizes), mb_ps

    @hot_path
    def _paged_attention_stage(self, li, p, x, pos,
                               pos_host=None) -> jax.Array:
        """Mode B decode attention (host-tier pages present).

        Page placement only decides where the KV BYTES live: every row
        gathers its span from the device pool plus the layer's streamed
        host frames in ONE launch (the htod copy prefetched a layer ahead,
        like streamed weights), and a row whose written page lives on the
        host tier has its new slot mirrored there.  So paged decode stays
        token-identical to the contiguous engine.
        """
        cfg, pages = self.cfg, self.pages
        n = x.shape[0]
        if pos_host is None:                # direct call; planned readback
            with sanitizer.allowed("decode-pos-host-mirror"):
                pos_host = np.asarray(pos, np.int32)  # lint: allow[MG101] planned once-per-tick position readback for the page table
        pos_np = np.broadcast_to(
            np.atleast_1d(np.asarray(pos_host, np.int32)), (n,)  # lint: allow[MG101] pos_host is already a numpy mirror; host-only dtype/shape normalization
        )
        span, pt = pages.span, pages.page_tokens
        if cfg.sliding_window:
            wslot = pos_np % span
        else:
            wslot = np.minimum(pos_np, span - 1)
        wpage = wslot // pt
        woff = wslot % pt
        rows = list(range(n))
        wframe, host_writes = pages.write_targets(rows, wpage)
        with sanitizer.allowed("paged-index-upload"):
            frames = jnp.asarray(pages.gather_indices(rows))
            posd = jnp.asarray(pos_np)
            wpaged = jnp.asarray(wpage)
            wframed = jnp.asarray(wframe)
        ek, ev = pages.acquire(li)
        y, pk, pv, k_new, v_new = _paged_attn_decode_module(
            cfg, span, p, x, pages.pool_k[li], pages.pool_v[li],
            ek, ev, frames, posd, wpaged, wframed,
        )
        pages.pool_k[li], pages.pool_v[li] = pk, pv
        if host_writes:                 # a row's written page is host-side
            with sanitizer.allowed("paged-host-writeback"):
                k_np, v_np = np.asarray(k_new), np.asarray(v_new)  # lint: allow[MG101] written page lives on the host tier; planned readback
                for i, hf in host_writes:
                    pages.write_host_slot(
                        li, hf, int(woff[i]), k_np[i], v_np[i]
                    )
        self.stats.attn_microbatches += 1
        self.stats.device_attn_tokens += n
        return y

    def _mesh_moe(self, li, p, x, capacity: int, routed) -> jax.Array:
        """A prefill micro-batch's MoE stage on a mesh engine: the
        collective dispatch of the mixer launch's ``routed=(h, gates,
        idx)`` at ``capacity`` (the pow2 bucket over the measured max load,
        so nothing drops).  The expert stacks live sharded over the mesh,
        so prefill cannot take the single-device grouped launch."""
        from repro.distributed.ep_engine import ep_expert_stage

        B, S, D = x.shape
        y, _, _, _, nbytes = ep_expert_stage(
            self, li, p, x.reshape(B * S, D), capacity=capacity,
            routed=routed,
        )
        self.stats.a2a_bytes += nbytes
        self.stats.collective_dispatches += 1
        return y.reshape(B, S, D)

    def _expert_stage_grouped(self, li, p, x) -> jax.Array:
        """One grouped-dispatch launch for the whole MoE stage: routing,
        gather, expert FFNs and combine all stay on device (§4.2 realized
        as a single module launch).
        A mesh engine routes the same stage through the collective dispatch
        (``repro.distributed.ep_engine``) — counters keep one meaning."""
        if self.sctx is not None:
            from repro.distributed.ep_engine import ep_expert_stage

            y, kept, dropped, load, nbytes = ep_expert_stage(self, li, p, x)
            self.stats.a2a_bytes += nbytes
            self.stats.collective_dispatches += 1
        else:
            y, kept, dropped, load, rows = _grouped_expert_module(
                self.cfg, p, x, self._expert_capacity(x.shape[0])
            )
            self._rows_dev = self._rows_dev + rows
        self.stats.expert_launches += 1
        j = self._moe_index[li]
        self._kept_dev = self._kept_dev + kept
        self._dropped_dev_l[j] = self._dropped_dev_l[j] + dropped
        self._load_dev_l[j] = self._load_dev_l[j] + load
        return y

    def _next_streamed_moe(self, li: int) -> int:
        """The next MoE layer (wrapping) whose experts are streamed — the
        prediction target for layer ``li``'s gate tap.  Its norm2/router
        live in the store's pinned ``moe_shared`` set, so scoring it needs
        no expert bytes."""
        streamed = [l for l in self._moe_layers
                    if self.store.streams_experts(l - self.n_lead)]
        pos = streamed.index(li)
        return streamed[(pos + 1) % len(streamed)]

    @hot_path
    def _expert_stage_predictive(self, li, x) -> jax.Array:
        """Predictive-streamed MoE stage: route + predict in ONE launch,
        read the packed (used-counts ++ predicted-ids) vector back under a
        single planned transfer, assemble only the USED experts' stacks
        (LRU/prefetch hits are free; mispredictions demand-fetch), issue
        the next streamed MoE layer's predicted prefetch, then run the
        grouped FFN.  Prediction moves WHEN bytes move, never WHICH math
        runs — the dispatch consumes the true routing, so output is
        bit-identical to the whole-stack path for any predictor."""
        cfg = self.cfg
        E = cfg.num_experts
        nli = self._next_streamed_moe(li)
        si, nsi = li - self.n_lead, nli - self.n_lead   # the store's index
        shared = self.store.moe_shared(si)
        khat = self.store.predict_topk
        h, gates, idx, packed = _route_predict_module(
            cfg, khat, shared["norm2"], shared["router"],
            self.store.moe_shared(nsi)["router"], x,
        )
        with sanitizer.allowed("expert-prefetch"):
            packed_np = np.asarray(packed)  # lint: allow[MG101] ONE planned readback per predictive MoE layer: routed-copy counts + predicted ids
        used = np.nonzero(packed_np[:E])[0]
        if self.predictor is not None:      # test seam: prefetch-only
            pred = np.asarray(list(self.predictor(nli, khat)), np.int64)  # lint: allow[MG101] host-list coercion of the injected predictor's ids, no device buffer involved
        else:
            pred = packed_np[E:]
        wg, wu, wd = self.store.acquire_experts(si, used)
        self.store.prefetch_experts(nsi, pred)
        y, kept, dropped, load, rows = _grouped_ffn_module(
            cfg, self._expert_capacity(x.shape[0]), h, gates, idx,
            wg, wu, wd,
            {"shared": shared["shared"]} if "shared" in shared else None,
        )
        self.stats.expert_launches += 1
        j = self._moe_index[li]
        self._kept_dev = self._kept_dev + kept
        self._rows_dev = self._rows_dev + rows
        self._dropped_dev_l[j] = self._dropped_dev_l[j] + dropped
        self._load_dev_l[j] = self._load_dev_l[j] + load
        return y

    # -- chunked decode ---------------------------------------------------
    def decode_chunk(self, tokens, pos, sampler, T: int,
                     live=None) -> jax.Array:
        """``T`` decode ticks for the full batch, sampled per slot; returns
        the ``(B, T)`` token matrix (column *t* is tick *t*'s tokens, fed
        back as tick *t+1*'s input).

        Fused one-launch path when ``fused_eligible()``: every row rides
        ONE donated ``_fused_decode_chunk`` launch.  Otherwise every row
        takes the per-module path, one tick at a time.  Positions are
        clamped at ``max_seq - 1`` exactly like the scheduler's per-tick
        clamp.

        ``live`` (B,) bool marks rows owned by unfinished requests (None =
        all).  Dead rows re-feed their stale token/position every tick —
        matching per-tick stepping, where the scheduler never updates a
        free slot — so chunked decode is tick-identical to per-tick decode
        even when expert-capacity drops couple rows through the grouped
        dispatch.  Both paths are token-for-token identical
        (property-tested).
        """
        stale = self._stale_snapshot()
        with sanitizer.allowed("decode-inputs"):
            tokens = jnp.asarray(tokens)
            pos = jnp.asarray(pos, jnp.int32)
            live = None if live is None else jnp.asarray(live, bool)
        with sanitizer.decode_region():
            out = self._decode_chunk_guarded(tokens, pos, sampler, T, live)
        self._poison_stale(stale)
        return out

    @hot_path
    def _decode_chunk_guarded(self, tokens, pos, sampler, T: int,
                              live=None) -> jax.Array:
        if not (self.fused_eligible() and self.cache is not None):
            return self._chunk_rows_per_module(tokens, pos, sampler, T, live)
        B = tokens.shape[0]
        posv = jnp.broadcast_to(jnp.atleast_1d(pos), (B,)).astype(jnp.int32)
        with sanitizer.allowed("decode-inputs"):
            livev = jnp.ones((B,), bool) if live is None else live
        idx = np.arange(B)
        with sanitizer.allowed("sampler-state"):
            keys, steps, temps, topks = sampler.state(idx)
            keys_d, steps_d = jnp.asarray(keys), jnp.asarray(steps)
            temps_d, topks_d = jnp.asarray(temps), jnp.asarray(topks)
        use_topk = bool((topks > 0).any())
        greedy_only = not bool((temps > 0).any())
        capacity = self._expert_capacity(B)
        cap = self.max_seq - 1
        key = (B, T, capacity, cap, use_topk, greedy_only)
        if self._fused_keys.add(key):
            self.stats.decode_retraces += 1
        toks, cache, kept, dropped, load, rows = _fused_decode_chunk(
            self.cfg, tuple(self.schema), self.cfg.tie_embeddings, capacity,
            cap, use_topk, greedy_only, T,
            self.store.base, self._fused_layer_params(),
            tokens, posv, livev, tuple(self.cache),
            keys_d, steps_d, temps_d, topks_d,
        )
        self.cache = list(cache)
        self._kept_dev = self._kept_dev + kept
        self._rows_dev = self._rows_dev + rows
        self._dropped_chunk_dev = self._dropped_chunk_dev + dropped
        self._load_chunk_dev = self._load_chunk_dev + load
        sampler.advance(idx, T)
        self.stats.fused_dispatches += 1
        self.stats.fused_ticks += T
        # the fused launch bundles the per-module work units into one
        # dispatch — keep their accounting equivalent to the per-module
        # path: one grouped-dispatch evaluation per MoE layer per tick,
        # and every row is an attention token per attention layer per tick
        self.stats.expert_launches += T * sum(
            1 for _, f in self.schema if f == "moe"
        )
        self.stats.device_attn_tokens += B * T * sum(
            1 for k, _ in self.schema if k in ("attn", "mla")
        )
        return toks

    @hot_path
    def _chunk_rows_per_module(self, tokens, pos, sampler, T: int,
                               live=None) -> jax.Array:
        """Per-module chunk fallback over every batch row: ``T`` sequential
        decode ticks, each sampled through the caller's ``BatchSampler``
        (the streamed / paged / mesh execution).  Dead rows (``live``
        False) hold their stale token/position, like per-tick stepping.

        The per-tick position advance is HOST math: mixing the Python tick
        index into device arithmetic (``pos + t``) was an implicit scalar
        h2d transfer every tick — the exact pathology the sanitizer exists
        to catch.  Instead a numpy mirror advances on the host and ONE
        planned (B,)-vector upload per tick feeds the modules; the uploaded
        aval (int32, same shape) is identical, so trace keys are unchanged.
        The mirror also rides down to Mode B paging as ``pos_host``."""
        B = tokens.shape[0]
        slots = np.arange(B)
        cur, posr = tokens, pos
        if live is not None and posr.ndim == 0:
            posr = jnp.broadcast_to(posr, (B,))
        with sanitizer.allowed("decode-pos-host-mirror"):
            pos_np = np.asarray(posr, np.int32)  # lint: allow[MG101] one planned readback per chunk; host mirror drives tick advance
            adv_np = (None if live is None
                      else np.asarray(live, np.int32))  # lint: allow[MG101] live mask readback, once per chunk
        cap = self.max_seq - 1
        cols = []
        for t in range(T):
            pt_np = np.minimum(
                pos_np + (t if adv_np is None else t * adv_np), cap
            ).astype(np.int32)
            with sanitizer.allowed("decode-pos-upload"):
                pt = jnp.asarray(pt_np)
            lg = self._decode_rows(cur, pt, pt_np)
            sampled = sampler.sample(lg, slots)
            cols.append(sampled)
            cur = sampled if live is None else jnp.where(live, sampled, cur)
        return jnp.stack(cols, axis=1)

    def decode_step_sampled(self, tokens: jax.Array, pos, sampler,
                            slots=None) -> jax.Array:
        """One decode tick plus on-device per-slot sampling: one fused
        launch when eligible (``decode_chunk`` with ``T=1``), else
        ``decode_step`` + a ``serving.sampling.BatchSampler`` launch (mixed
        greedy/temperature/top-k slots, seeded per slot — see that module's
        determinism contract).  Returns the (B,) next-token array instead
        of logits."""
        if slots is None and self.fused_eligible() and self.cache is not None:
            return self.decode_chunk(tokens, pos, sampler, 1)[:, 0]
        return sampler.sample(self.decode_step(tokens, pos), slots)

    # -- generation -------------------------------------------------------
    def generate(
        self, tokens: jax.Array, decode_len: int, frontend_emb=None,
        lengths=None, sampling=None, chunk: Optional[int] = None,
    ) -> jax.Array:
        """Generation — greedy by default (the paper's decoding strategy,
        §B); pass ``sampling`` (a ``serving.sampling.SamplingParams``) for
        seeded temperature / top-k decoding, applied uniformly with each
        batch row's index folded into its key (rows draw independent
        streams from one seed).

        ``lengths`` (B,) generates from a ragged right-padded batch: each
        sequence decodes at its own positions, token-for-token identical to
        generating it alone unpadded.

        Decode runs in fused multi-token chunks of ``chunk`` ticks
        (default: the plan's ``decode_chunk``) when the fused path is
        eligible — one device dispatch per chunk; the per-module fallback
        ticks through the same chunks one launch-set at a time, with
        identical tokens either way.
        """
        from repro.serving.sampling import BatchSampler

        B, S = tokens.shape
        sampler = BatchSampler.uniform(B, sampling)
        logits = self.prefill(tokens, frontend_emb, lengths=lengths)
        cols = [sampler.sample(logits)]
        base = S if lengths is None else jnp.asarray(lengths, jnp.int32)
        step = max(1, chunk if chunk is not None
                   else getattr(self.plan, "decode_chunk", 1))
        t, total = 0, decode_len - 1
        while t < total:
            Tc = min(step, total - t)
            mat = self.decode_chunk(
                cols[-1], jnp.asarray(base + t, jnp.int32), sampler, Tc
            )
            cols.extend(mat[:, j] for j in range(Tc))
            t += Tc
        result = jnp.stack(cols, axis=1)             # (B, decode_len)
        self.sync_stats()                            # fold device counters in
        return result
