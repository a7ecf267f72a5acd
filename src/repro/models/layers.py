"""Shared neural-net primitives: norms, RoPE, initializers, losses."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, in_dim: Optional[int] = None, dtype=jnp.bfloat16):
    in_dim = in_dim if in_dim is not None else shape[0]
    scale = (1.0 / max(in_dim, 1)) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                    # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    angles = angles[..., None, :]                                # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# yarn RoPE on the rope dims of latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies of the ``qk_rope_head_dim`` rope dims: the
    original ones above the correction range that ``yarn_beta_fast`` /
    ``yarn_beta_slow`` set at ``yarn_original_max_pos``, the ones divided
    by ``yarn_factor`` below it, a linear ramp between (plain RoPE when
    ``yarn_factor`` is 0)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.yarn_factor:
        return extra.astype(np.float32)

    def corr_dim(rotations: float) -> float:
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inter = extra / cfg.yarn_factor
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope) ** -0.5``, times ``m**2`` with
    ``m = yarn_mscale(factor, mscale_all_dim)`` under yarn."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s *= m * m
    return s


def apply_yarn_rope(x: jax.Array, positions: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """x: (..., S, H, r) rope dims; positions broadcastable to (..., S).

    Layout: the published weights keep each rope dim pair interleaved
    (``x[2i], x[2i+1]``); the pairs are de-interleaved first (evens, then
    odds) and then rotated half against half, as DeepSeek-V2's own
    ``apply_rotary_pos_emb`` does, so the output is in the de-interleaved
    order.  Queries and the shared rope key both take this path, so their
    dot products are those of the published layout.  cos and sin carry
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    r = x.shape[-1]
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xf = jnp.swapaxes(xf.reshape(x.shape[:-1] + (r // 2, 2)), -1, -2)
    xf = xf.reshape(x.shape)
    freqs = jnp.asarray(yarn_inv_freq(cfg))                      # (r/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, r/2)
    angles = angles[..., None, :]                                # (..., S, 1, r/2)
    amp = 1.0
    if cfg.yarn_factor:
        amp = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
               / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    cos, sin = jnp.cos(angles) * amp, jnp.sin(angles) * amp
    x1, x2 = xf[..., : r // 2], xf[..., r // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL.  logits: (B, S, V) possibly vocab-sharded; labels (B, S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - label_logit)
