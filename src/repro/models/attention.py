"""Attention: GQA with RoPE, and multi-head latent attention (MLA);
full-sequence (train/prefill) and decode paths.

Three implementations:

* ``naive_attention``     — materialized scores, used for tiny smoke shapes
                             and as the oracle for the blocked versions.
* ``blocked_attention``   — flash-style online-softmax over KV blocks,
                             memory-bounded; causal mask applied per block.
* ``swa_attention``       — sliding-window attention that only *computes*
                             the window (sub-quadratic): scans q blocks and
                             slices a static-size KV window per block.

Decode uses a pre-allocated KV cache (full attention) or a circular window
buffer (SWA).

MLA (DeepSeek-V2, ``cfg.kv_lora_rank > 0``) caches one latent row per
token and layer instead of per-head keys and values: the RMSNorm'd
``kv_lora_rank`` latent and the one ``qk_rope_head_dim`` rope key shared by
every head, ``C = r + rope`` values, stored two positions to a row as
``{"ckv": (B, span / 2, 2C)}`` (``mla_pack``).  Prefill runs the
decompressed form (every head's nope key and value up-projected from the
latent by ``wkv_b``); decode runs the absorbed form (``W_UK`` folded into
the query, ``W_UV`` applied to the attended latent), so no cached row is
ever up-projected (``mla_decode``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import (
    apply_rope,
    apply_yarn_rope,
    dense_init,
    mla_softmax_scale,
    rms_norm,
)
from repro.sharding.specs import ShardCtx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attn_params(cfg: ModelConfig, key) -> Dict[str, jax.Array]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dt),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=dt),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=dt),
        "wo": dense_init(ks[3], (h * hd, d), in_dim=h * hd, dtype=dt),
    }
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((h * hd,), dt)
        p["wk_bias"] = jnp.zeros((kv * hd,), dt)
        p["wv_bias"] = jnp.zeros((kv * hd,), dt)
    return p


def _project_qkv(cfg: ModelConfig, p, x: jax.Array):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["wq_bias"]
        k = k + p["wk_bias"]
        v = v + p["wv_bias"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# Core attention math (all take (B, S, H, D) / (B, T, K, D))
# ---------------------------------------------------------------------------
def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, K, G, D), k: (B, Sk, K, D) -> (B, K, G, Sq, Sk) in f32."""
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    )


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference attention.  q: (B, Sq, H, D); k, v: (B, Sk, K, D).

    ``kv_mask`` (B, Sk) bool marks valid keys; padded positions of a ragged
    batch are masked out of every query's context.
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = D ** -0.5
    qg = q.reshape(B, Sq, K, G, D)
    scores = _gqa_scores(qg, k) * scale                     # (B,K,G,Sq,Sk)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    scores = jnp.where(mask, scores, NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 512,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash-style attention: online softmax over KV blocks.

    Memory is O(S * kv_block) instead of O(S^2).  All KV blocks are computed
    and masked (the Pallas kernel skips fully-masked blocks on TPU; see
    kernels/flash_attention).  ``kv_mask`` (B, Sk) masks padded keys of a
    ragged batch.
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    if S % q_block or S % kv_block:
        return naive_attention(q, k, v, causal=causal, window=window,
                               kv_mask=kv_mask)
    scale = D ** -0.5
    nq, nk = S // q_block, S // kv_block
    qb = q.reshape(B, nq, q_block, K, G, D)

    def body(carry, i):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, i * kv_block, kv_block, axis=1)
        vs = lax.dynamic_slice_in_dim(v, i * kv_block, kv_block, axis=1)
        s = (
            jnp.einsum("bnqkgd,bjkd->bnkgqj", qb, ks,
                       preferred_element_type=jnp.float32)
            * scale
        )                                                   # (B,nq,K,G,qb,kb)
        qpos = (
            jnp.arange(nq)[:, None] * q_block + jnp.arange(q_block)[None, :]
        )                                                   # (nq, qb)
        kpos = i * kv_block + jnp.arange(kv_block)          # (kb,)
        mask = jnp.ones((nq, q_block, kv_block), bool)
        if causal:
            mask &= qpos[..., None] >= kpos[None, None, :]
        if window:
            mask &= qpos[..., None] - kpos[None, None, :] < window
        s = jnp.where(mask[:, None, None, :, :][None], s, NEG_INF)
        if kv_mask is not None:
            km = lax.dynamic_slice_in_dim(kv_mask, i * kv_block, kv_block,
                                          axis=1)         # (B, kb)
            s = jnp.where(km[:, None, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bnkgqj,bjkd->bnkgqd", p.astype(vs.dtype), vs)
        acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(acc.dtype)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, nq, K, G, q_block), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, K, G, q_block), jnp.float32)
    a0 = jnp.zeros((B, nq, K, G, q_block, D), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.astype(q.dtype).transpose(0, 1, 4, 2, 3, 5)   # (B,nq,qb,K,G,D)
    return out.reshape(B, S, H, D)


def swa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    q_block: int = 512,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Sliding-window attention computing only the window (sub-quadratic).

    Scans query blocks; each block attends to a static slice of
    ``window + q_block`` keys ending at the block's last position.  Ragged
    batches (``kv_mask``) fall back to the materialized reference: padded
    keys must be masked everywhere, and the paper's workloads pad the long
    sliding-window prompts to a uniform length anyway.
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    if kv_mask is not None or S <= window + q_block or S % q_block:
        return naive_attention(q, k, v, causal=True, window=window,
                               kv_mask=kv_mask)
    scale = D ** -0.5
    nq = S // q_block
    span = window + q_block
    # pad keys/values on the left so every slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    def body(_, i):
        qs = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        qs = qs.reshape(B, q_block, K, G, D)
        # in padded coords, query block [i*qb, i*qb+qb) sees keys
        # [i*qb, i*qb + span)  (= original [i*qb - window, i*qb + qb))
        ks = lax.dynamic_slice_in_dim(kp, i * q_block, span, axis=1)
        vs = lax.dynamic_slice_in_dim(vp, i * q_block, span, axis=1)
        s = _gqa_scores(qs, ks) * scale                     # (B,K,G,qb,span)
        qpos = i * q_block + jnp.arange(q_block)
        kpos = i * q_block + jnp.arange(span) - window      # original coords
        mask = (qpos[:, None] >= kpos[None, :]) & (
            qpos[:, None] - kpos[None, :] < window
        ) & (kpos[None, :] >= 0)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vs.dtype), vs)
        return None, o.reshape(B, q_block, H, D)

    _, outs = lax.scan(body, None, jnp.arange(nq))          # (nq,B,qb,H,D)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, window: int = 0,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Dispatcher used by the model for full-sequence passes."""
    S = q.shape[1]
    if window and S > window:
        return swa_attention(q, k, v, window=window, kv_mask=kv_mask)
    if S <= 1024:
        return naive_attention(q, k, v, causal=True, window=window,
                               kv_mask=kv_mask)
    return blocked_attention(q, k, v, causal=True, window=window,
                             kv_mask=kv_mask)


# ---------------------------------------------------------------------------
# Module-level forward passes
# ---------------------------------------------------------------------------
def attn_forward(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    ctx: ShardCtx = ShardCtx(),
    positions: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention.  Returns (output, kv) so prefill can cache.

    ``lengths`` (B,) marks the true length of each right-padded sequence:
    keys at padded positions are masked out of every query's context, so a
    ragged batch attends exactly what its unpadded sequences would.
    (Outputs *at* padded query positions are garbage — callers must never
    read them; the decode path masks them by per-sequence position.)

    Sharding: heads over the model axis when the head count divides it;
    otherwise *context parallelism* — queries shard over sequence, KV
    replicate — which keeps the S^2 work partitioned instead of silently
    replicating it (found via the dry-run roofline: 20/24/12-head archs on a
    16-way axis were 16x redundant).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    # rope BEFORE the sharding constraints: its f32 intermediates otherwise
    # get collected in f32 (2x collective bytes, seen in the dry-run HLO)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    msize = ctx.model_size
    heads_ok = msize <= 1 or cfg.num_heads % msize == 0
    if heads_ok:
        q = ctx.shard(q, "batch", None, "model", None)
        k = ctx.shard(k, "batch", None, "model", None)
        v = ctx.shard(v, "batch", None, "model", None)
    else:
        q = ctx.shard(q, "batch", "model", None, None)
        k = ctx.shard(k, "batch", None, None, None)
        v = ctx.shard(v, "batch", None, None, None)
    kv_mask = None
    if lengths is not None:
        kv_mask = jnp.arange(S)[None, :] < lengths[:, None]
    out = full_attention(q, k, v, window=cfg.sliding_window, kv_mask=kv_mask)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    y = out @ p["wo"]
    return ctx.shard_residual(y), {"k": k, "v": v}


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=None
) -> Dict[str, jax.Array]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    span = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    shape = (batch, span, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, 1, D)
    cache: Dict[str, jax.Array],
    pos: jax.Array,                     # scalar or (B,) int32: current position
    ctx: ShardCtx = ShardCtx(),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step against a pre-allocated (possibly circular) cache.

    ``pos`` may be a scalar (uniform batch) or a per-sequence ``(B,)``
    vector (ragged batch / continuous scheduler): each sequence writes its
    new KV at its own position and attends only its own ``<= pos`` prefix,
    so padded or recycled cache rows beyond a sequence's length are never
    attended.
    """
    B = x.shape[0]
    K, hd = cfg.num_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(cfg, p, x)                       # (B,1,·,hd)
    posv = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (B,)
    )                                                       # (B,)
    posb = posv[:, None]
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    span = cache["k"].shape[1]
    slot = jnp.where(
        cfg.sliding_window > 0, posv % span, jnp.minimum(posv, span - 1)
    )                                                       # (B,)
    rows = jnp.arange(B)
    ck = cache["k"].at[rows, slot].set(k[:, 0])
    cv = cache["v"].at[rows, slot].set(v[:, 0])

    G = cfg.num_heads // K
    qg = q.reshape(B, 1, K, G, hd)
    s = _gqa_scores(qg, ck) * (hd ** -0.5)                  # (B,K,G,1,span)
    idx = jnp.arange(span)
    valid = idx[None, :] <= posb                            # ring holds last W
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pr.astype(cv.dtype), cv)
    o = o.reshape(B, 1, cfg.num_heads * hd)
    y = o @ p["wo"]
    return ctx.shard(y, "batch", None, None), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
def init_mla_params(cfg: ModelConfig, key) -> Dict[str, jax.Array]:
    """Projections in the published column order: ``wq`` per head nope then
    rope dims, ``wkv_a`` the latent then the shared rope key, ``wkv_b`` per
    head the nope key then the value."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * (nope + rope)), dtype=dt),
        "wkv_a": dense_init(ks[1], (d, r + rope), dtype=dt),
        "kv_norm": jnp.ones((r,), dt),
        "wkv_b": dense_init(ks[2], (r, h * (nope + vd)), dtype=dt),
        "wo": dense_init(ks[3], (h * vd, d), in_dim=h * vd, dtype=dt),
    }


def _mla_project(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array):
    """x (B, S, D) -> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope) and the
    cache row (B, S, C): the normalised latent, then the roped rope key."""
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    q_pe = apply_yarn_rope(q[..., nope:], positions, cfg)
    ckv = x @ p["wkv_a"]                                    # (B, S, r+rope)
    lat = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_yarn_rope(ckv[..., None, r:], positions, cfg)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([lat, k_pe], axis=-1)


def mla_forward(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    ctx: ShardCtx = ShardCtx(),
    positions: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence MLA in the decompressed form.  Returns (output,
    {"ckv": (B, S, C)}): the raw per-position cache rows.
    ``lengths`` masks the padded keys of a ragged batch, as in
    ``attn_forward``.  Scores are materialized per micro-batch."""
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_pe, row = _mla_project(cfg, p, x, positions)
    kv = (row[..., :r] @ p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_pe = jnp.broadcast_to(row[..., None, r:], (B, S, H, rope))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * mla_softmax_scale(cfg)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask[None, None], s, NEG_INF)
    if lengths is not None:
        kv_mask = jnp.arange(S)[None, :] < lengths[:, None]
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(kv.dtype), kv[..., nope:])
    y = o.reshape(B, S, H * vd) @ p["wo"]
    return ctx.shard_residual(y), {"ckv": row}


def mla_pack(cfg: ModelConfig, rows: jax.Array, span: int) -> jax.Array:
    """Per-position cache rows ``(..., S, C)`` -> the decode cache layout
    ``(..., span / 2, 2C)``, padded/truncated to ``span`` (even) positions:
    row ``k`` holds positions ``2k`` and ``2k+1`` as ``[lat[2k] |
    lat[2k+1] | pe[2k] | pe[2k+1]]``, so a row is a whole number of
    128-lane tiles at DeepSeek-V2's widths (2 x 576 = 1152) and every
    slice of it the decode kernel takes is lane-aligned
    (``kernels/mla_decode.py``)."""
    r = cfg.kv_lora_rank
    *lead, S, C = rows.shape
    n = min(S, span)
    rows = jnp.pad(rows[..., :n, :],
                   [(0, 0)] * len(lead) + [(0, span - n), (0, 0)])
    lat = rows[..., :r].reshape(*lead, span // 2, 2 * r)
    pe = rows[..., r:].reshape(*lead, span // 2, 2 * (C - r))
    return jnp.concatenate([lat, pe], axis=-1)


def mla_span(max_seq: int) -> int:
    """Cached positions of an MLA layer: ``max_seq`` rounded up to even."""
    return max_seq + max_seq % 2


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    return {"ckv": jnp.zeros(
        (batch, mla_span(max_seq) // 2, 2 * cfg.mla_cache_width), dtype)}


def _mla_write(cfg: ModelConfig, c: jax.Array, slot: jax.Array,
               row: jax.Array) -> jax.Array:
    """Write each batch row's new cache row ``row`` (B, C) at its position
    ``slot`` (B,) of the paired cache ``c`` (B, span/2, 2C), in place: the
    pair row holding the position is read, its half for this position
    replaced, and the whole pair row scattered back (one scatter of B
    whole rows, as the GQA cache write is)."""
    B = c.shape[0]
    r = cfg.kv_lora_rank
    rope = row.shape[-1] - r
    rows, k = jnp.arange(B), slot // 2
    odd = (slot % 2 == 1)[:, None]
    cur = c[rows, k]                                        # (B, 2C)
    lat, pe = row[:, :r].astype(c.dtype), row[:, r:].astype(c.dtype)
    pair = jnp.concatenate([
        jnp.where(odd, cur[:, :r], lat), jnp.where(odd, lat, cur[:, r:2 * r]),
        jnp.where(odd, cur[:, 2 * r:2 * r + rope], pe),
        jnp.where(odd, pe, cur[:, 2 * r + rope:])], axis=-1)
    return c.at[rows, k].set(pair)


def mla_decode(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, 1, D)
    cache: Dict[str, jax.Array],
    pos: jax.Array,                     # scalar or (B,) int32
    ctx: ShardCtx = ShardCtx(),
    use_kernel=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step in the absorbed form over the latent cache: each
    row writes its cache row at its own position, its query is folded
    through ``W_UK`` into latent space, ``kernels.ops.mla_decode`` attends
    the cached rows ``<= pos``, and ``W_UV`` then ``wo`` map the attended
    latent out."""
    from repro.kernels import ops as kernel_ops

    B = x.shape[0]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    posv = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (B,)
    )
    q_nope, q_pe, row = _mla_project(cfg, p, x, posv[:, None])
    c = cache["ckv"]
    slot = jnp.minimum(posv, 2 * c.shape[1] - 1)
    c = _mla_write(cfg, c, slot, row[:, 0])
    w_b = p["wkv_b"].reshape(r, H, nope + vd)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_b[..., :nope],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat.astype(c.dtype), q_pe[:, 0]], axis=-1)
    o_lat = kernel_ops.mla_decode(q, c, posv, rank=r,
                                  scale=mla_softmax_scale(cfg),
                                  use_kernel=use_kernel)   # (B, H, r)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_b[..., nope:],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = o.reshape(B, 1, H * vd) @ p["wo"]
    return ctx.shard(y, "batch", None, None), {"ckv": c}
