"""Layer blocks: (attention | latent attention | SSM) + (dense FFN | MoE)
with pre-norm residuals."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import dense_init, rms_norm
from repro.sharding.specs import ShardCtx


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------
def init_ffn_params(cfg: ModelConfig, key) -> Dict[str, jax.Array]:
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), dtype=dt),
        "w_up": dense_init(ks[1], (d, f), dtype=dt),
        "w_down": dense_init(ks[2], (f, d), dtype=dt),
    }


def ffn_apply(p, x: jax.Array, ctx: ShardCtx = ShardCtx()) -> jax.Array:
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    h = jax.nn.silu(g) * u
    h = ctx.shard(h, "batch", None, "model")
    return ctx.shard_residual(h @ p["w_down"])


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------
def init_layer_params(cfg: ModelConfig, kind: str, ffn_kind: str, key):
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.dtype)
    p: Dict = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if kind == "attn":
        p["attn"] = attn_mod.init_attn_params(cfg, ks[0])
    elif kind == "mla":
        p["mla"] = attn_mod.init_mla_params(cfg, ks[0])
    else:
        p["ssm"] = ssm_mod.init_ssm_params(cfg, ks[0])
    if ffn_kind == "moe":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = moe_mod.init_moe_params(cfg, ks[1])
    elif cfg.d_ff > 0:
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = init_ffn_params(cfg, ks[1])
    return p


def layer_forward(
    cfg: ModelConfig,
    kind: str,
    ffn_kind: str,
    p: Dict,
    x: jax.Array,
    ctx: ShardCtx = ShardCtx(),
    positions: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, jax.Array]:
    """Full-sequence layer.  Returns (x, cache_entry, aux_loss).

    ``lengths`` (B,) enables ragged (right-padded) batches: the sequence
    mixers mask padded positions so valid positions and cached state are
    exactly what the unpadded sequences would produce.
    """
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        y, cache = attn_mod.attn_forward(cfg, p["attn"], h, ctx, positions,
                                         lengths)
    elif kind == "mla":
        y, cache = attn_mod.mla_forward(cfg, p["mla"], h, ctx, positions,
                                        lengths)
    else:
        y, cache = ssm_mod.ssm_forward(cfg, p["ssm"], h, ctx, lengths)
    x = x + y
    if ffn_kind == "moe":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, aux = moe_mod.moe_apply(cfg, p["moe"], h, ctx)
        x = x + y
    elif cfg.d_ff > 0:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + ffn_apply(p["ffn"], h, ctx)
    return x, cache, aux


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int):
    if kind == "attn":
        return attn_mod.init_kv_cache(cfg, batch, max_seq)
    if kind == "mla":
        return attn_mod.init_mla_cache(cfg, batch, max_seq)
    return ssm_mod.init_ssm_state(cfg, batch)


def layer_decode(
    cfg: ModelConfig,
    kind: str,
    ffn_kind: str,
    p: Dict,
    x: jax.Array,                  # (B, 1, D)
    cache: Dict,
    pos: jax.Array,
    ctx: ShardCtx = ShardCtx(),
) -> Tuple[jax.Array, Dict]:
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        y, cache = attn_mod.attn_decode(cfg, p["attn"], h, cache, pos, ctx)
    elif kind == "mla":
        y, cache = attn_mod.mla_decode(cfg, p["mla"], h, cache, pos, ctx)
    else:
        y, cache = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache, ctx)
    x = x + y
    if ffn_kind == "moe":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, _ = moe_mod.moe_apply(cfg, p["moe"], h, ctx)
        x = x + y
    elif cfg.d_ff > 0:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + ffn_apply(p["ffn"], h, ctx)
    return x, cache
