"""Composable decoder-only model covering all assigned architectures.

Layers are organized into *groups*: the smallest repeating pattern of
(sequence-mixer kind, FFN kind) pairs — a single layer for homogeneous
stacks, an 8-layer period for Jamba-style hybrids.  Parameters are stacked
over groups and the stack is traversed with ``lax.scan`` so the lowered HLO
stays one-group-sized regardless of depth (essential for the 80-layer
dry-runs).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.blocks import (
    init_layer_cache,
    init_layer_params,
    layer_decode,
    layer_forward,
)
from repro.models.layers import dense_init, rms_norm
from repro.sharding.specs import ShardCtx


def lead_pattern(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """(mixer kind, FFN kind) of each leading dense layer
    (``first_k_dense``).  They run before the periodic stack, each with its
    own parameters (``params["lead"]``, a list, not stacked)."""
    return [(cfg.layer_kind(i), cfg.ffn_kind(i))
            for i in range(cfg.first_k_dense)]


def layer_pattern(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """The smallest repeating pattern of the layers after the leading
    dense ones (``params["layers"]`` is stacked over its groups)."""
    k = cfg.first_k_dense
    n = cfg.num_layers - k
    g = cfg.attn_period if cfg.attn_period else 1
    if cfg.has_moe:
        g = math.lcm(g, cfg.moe_layer_period)
    assert n > 0 and n % g == 0, (cfg.name, cfg.num_layers, k, g)
    pattern = [(cfg.layer_kind(k + i), cfg.ffn_kind(k + i)) for i in range(g)]
    for i in range(n):
        assert (cfg.layer_kind(k + i), cfg.ffn_kind(k + i)) == pattern[i % g]
    return pattern


def num_groups(cfg: ModelConfig) -> int:
    return (cfg.num_layers - cfg.first_k_dense) // len(layer_pattern(cfg))


def _lead_keys(cfg: ModelConfig, key) -> jax.Array:
    return jax.random.split(jax.random.fold_in(key, 7), cfg.first_k_dense)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key) -> Dict:
    pattern = layer_pattern(cfg)
    G = num_groups(cfg)
    dt = jnp.dtype(cfg.dtype)
    k_layers, k_embed, k_head = jax.random.split(key, 3)

    def init_group(k):
        sk = jax.random.split(k, len(pattern))
        return [
            init_layer_params(cfg, kind, ffn, sk[j])
            for j, (kind, ffn) in enumerate(pattern)
        ]

    layers = jax.vmap(init_group)(jax.random.split(k_layers, G))
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype=dt),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.first_k_dense:
        params["lead"] = [
            init_layer_params(cfg, kind, ffn, k)
            for (kind, ffn), k in zip(lead_pattern(cfg), _lead_keys(cfg, key))
        ]
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), dtype=dt
        )
    return params


def init_params_host(cfg: ModelConfig, key) -> Dict:
    """``init_params``' weights, drawn one layer group at a time on the
    default device and kept in host memory as numpy arrays.

    The device never holds more than one group's tensors at once, so a
    model larger than device memory can be made here and then placed by
    ``serving.weights.ParamStore`` (resident modules copied to the device,
    streamed ones left on the host) or by the caller's own shardings.  The
    key schedule is ``init_params``', so the values agree with it up to the
    bf16 rounding of a few scaled draws (jit and vmap fuse the scale
    product differently)."""
    pattern = layer_pattern(cfg)
    G = num_groups(cfg)
    dt = jnp.dtype(cfg.dtype)
    k_layers, k_embed, k_head = jax.random.split(key, 3)

    @jax.jit
    def init_group(k):
        sk = jax.random.split(k, len(pattern))
        return [
            init_layer_params(cfg, kind, ffn, sk[j])
            for j, (kind, ffn) in enumerate(pattern)
        ]

    layers = None
    for g, kg in enumerate(jax.random.split(k_layers, G)):
        group = jax.tree.map(np.asarray, init_group(kg))
        if layers is None:
            layers = jax.tree.map(
                lambda a: np.empty((G,) + a.shape, a.dtype), group
            )
        for dst, src in zip(jax.tree.leaves(layers), jax.tree.leaves(group)):
            dst[g] = src
        del group
    draw = jax.jit(dense_init, static_argnums=(1,), static_argnames=("dtype",))
    params = {
        "embed": np.asarray(draw(k_embed, (cfg.vocab_size, cfg.d_model),
                                 dtype=dt)),
        "layers": layers,
        "final_norm": np.ones((cfg.d_model,), dt),
    }
    if cfg.first_k_dense:
        init_one = jax.jit(init_layer_params, static_argnums=(0, 1, 2))
        params["lead"] = [
            jax.tree.map(np.asarray, init_one(cfg, kind, ffn, k))
            for (kind, ffn), k in zip(lead_pattern(cfg), _lead_keys(cfg, key))
        ]
    if not cfg.tie_embeddings:
        params["lm_head"] = np.asarray(
            draw(k_head, (cfg.d_model, cfg.vocab_size), dtype=dt)
        )
    return params


def _embed(cfg: ModelConfig, params, tokens, frontend_emb, ctx: ShardCtx):
    x = jnp.take(params["embed"], tokens, axis=0)
    if frontend_emb is not None:
        F = frontend_emb.shape[1]
        x = jnp.concatenate([frontend_emb.astype(x.dtype), x[:, F:]], axis=1)
    return ctx.shard_residual(x)


def _logits(cfg: ModelConfig, params, x, ctx: ShardCtx):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return ctx.shard(logits, "batch", None, "model")


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------
def forward(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,                     # (B, S) int32
    frontend_emb: Optional[jax.Array] = None,
    ctx: ShardCtx = ShardCtx(),
    remat: bool = False,
    logits_mode: str = "full",             # full | last | none
    remat_policy: str = "full",            # full | dots
    lengths: Optional[jax.Array] = None,   # (B,) true lengths (ragged batch)
):
    """Returns (logits, aux_loss, caches) — logits (B,S,V), (B,1,V), or
    final hidden.  ``caches`` lists one entry per leading dense layer, then
    one per pattern slot stacked over groups.

    ``lengths`` marks the true length of each right-padded sequence.  Padded
    positions are masked out of attention and the SSM recurrence, and
    ``logits_mode='last'`` gathers each sequence's logits at its OWN last
    token instead of the batch's right edge (a pad position for every
    shorter prompt).
    """
    pattern = layer_pattern(cfg)
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, frontend_emb, ctx)
    positions = jnp.arange(S)[None, :]
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    aux0 = jnp.zeros((), jnp.float32)
    lead_caches = []
    for (kind, ffn), lp in zip(lead_pattern(cfg), params.get("lead", [])):
        x, cache, a = layer_forward(cfg, kind, ffn, lp, x, ctx, positions,
                                    lengths)
        lead_caches.append(cache)
        aux0 = aux0 + a

    def body(carry, group_p):
        x, aux = carry
        caches = []
        for j, (kind, ffn) in enumerate(pattern):
            x, cache, a = layer_forward(
                cfg, kind, ffn, group_p[j], x, ctx, positions, lengths
            )
            caches.append(cache)
            aux = aux + a
        return (x, aux), caches

    if remat and remat_policy == "dots":
        # save matmul outputs: the backward pass reuses them instead of
        # re-running the forward (and crucially, its collectives)
        scan_body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    elif remat:
        scan_body = jax.checkpoint(body)
    else:
        scan_body = body
    (x, aux), caches = lax.scan(scan_body, (x, aux0), params["layers"])
    caches = lead_caches + list(caches)
    if logits_mode == "none":
        return x, aux, caches
    if logits_mode == "last":
        if lengths is not None:
            last = x[jnp.arange(B), lengths - 1][:, None]
        else:
            last = x[:, -1:]
        return _logits(cfg, params, last, ctx), aux, caches
    return _logits(cfg, params, x, ctx), aux, caches


def loss_fn(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    labels: jax.Array,
    frontend_emb: Optional[jax.Array] = None,
    ctx: ShardCtx = ShardCtx(),
    remat: bool = True,
    aux_weight: float = 0.01,
    vocab_chunk: int = 1024,
    remat_policy: str = "full",
):
    """Mean-token NLL with *chunked* vocabulary projection.

    The logits tensor (B, S, V) is never materialized: the final hidden
    states are scanned in sequence chunks, each chunk projected and reduced
    to per-token NLL immediately — essential at 128k+ vocabularies.
    """
    x, aux, _ = forward(
        cfg, params, tokens, frontend_emb, ctx, remat=remat,
        logits_mode="none", remat_policy=remat_policy,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    B, S, D = x.shape
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    n_chunks = max(1, S // vocab_chunk) if S % vocab_chunk == 0 else 1
    c = S // n_chunks
    xc = x.reshape(B, n_chunks, c, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, c).transpose(1, 0, 2)

    def chunk_nll(carry, inp):
        xs, ls = inp
        lg = (xs @ head).astype(jnp.float32)
        lg = ctx.shard(lg, "batch", None, "model")
        lse = jax.nn.logsumexp(lg, axis=-1)
        lab = jnp.take_along_axis(lg, ls[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - lab), None

    body = jax.checkpoint(chunk_nll) if remat else chunk_nll
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    nll = total / (B * S)
    return nll + aux_weight * aux, (nll, aux)


# ---------------------------------------------------------------------------
# Prefill: forward + cache extraction
# ---------------------------------------------------------------------------
def prefill(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    frontend_emb: Optional[jax.Array] = None,
    ctx: ShardCtx = ShardCtx(),
    lengths: Optional[jax.Array] = None,
):
    """Returns (last-token logits (B,1,V), caches).

    Attention cache entries come back as the raw per-layer K/V of shape
    (G, B, S, K, hd) (rope already applied), latent attention's as the
    cache rows (G, B, S, C); a leading dense layer's entry has no G axis
    and comes first.  SSM entries come back as the final
    recurrent state.  ``serving.kvcache`` converts these into decode-ready
    buffers (padding / ring alignment).  ``lengths`` (B,) makes a ragged
    (right-padded) batch exact: pads are masked and logits come from each
    sequence's true last token.
    """
    logits, aux, caches = forward(
        cfg, params, tokens, frontend_emb, ctx, logits_mode="last",
        lengths=lengths,
    )
    return logits, caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> List:
    """One cache per leading dense layer, then one per pattern slot stacked
    over groups."""
    pattern = layer_pattern(cfg)
    G = num_groups(cfg)
    slots = [init_layer_cache(cfg, kind, batch, max_seq)
             for kind, _ in lead_pattern(cfg)]
    for kind, _ in pattern:
        c = init_layer_cache(cfg, kind, batch, max_seq)
        slots.append(
            jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, a.dtype), c)
        )
    return slots


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    cache: List,
    tokens: jax.Array,                 # (B,) int32
    pos: jax.Array,                    # scalar int32 current position
    ctx: ShardCtx = ShardCtx(),
):
    """One token for every sequence.  Returns (logits (B,V), new cache)."""
    pattern = layer_pattern(cfg)
    lead = lead_pattern(cfg)
    x = _embed(cfg, params, tokens[:, None], None, ctx)
    new_lead = []
    for (kind, ffn), lp, c in zip(lead, params.get("lead", []), cache):
        x, c = layer_decode(cfg, kind, ffn, lp, x, c, pos, ctx)
        new_lead.append(c)

    def body(x, xs):
        group_p, group_c = xs
        new_c = []
        for j, (kind, ffn) in enumerate(pattern):
            x, c = layer_decode(cfg, kind, ffn, group_p[j], x, group_c[j], pos, ctx)
            new_c.append(c)
        return x, new_c

    x, new_cache = lax.scan(body, x, (params["layers"], cache[len(lead):]))
    logits = _logits(cfg, params, x, ctx)
    return logits[:, 0], new_lead + list(new_cache)
