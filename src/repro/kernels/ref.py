"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def grouped_matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """(E, C, D) @ (E, D, F) -> (E, C, F), f32 accumulation."""
    return jnp.einsum("ecd,edf->ecf", x, w, preferred_element_type=jnp.float32
                      ).astype(x.dtype)


def expert_ffn_ref(x: jax.Array, wg, wu, wd) -> jax.Array:
    """Fused gated expert FFN: silu(x@wg) * (x@wu) @ wd."""
    g = jnp.einsum("ecd,edf->ecf", x, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wd,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def ragged_expert_ffn_ref(x: jax.Array, tile_expert, n_used: int, wg, wu,
                          wd, block_m: int) -> jax.Array:
    """The fused FFN of each ``block_m``-row tile of ``x`` (R, D) with its
    tile's expert, over the first ``n_used`` tiles: (n_used*block_m, D)."""
    rows = n_used * block_m
    xt = x[:rows].reshape(n_used, block_m, x.shape[-1])
    te = jnp.asarray(tile_expert)[:n_used]
    return expert_ffn_ref(xt, wg[te], wu[te], wd[te]).reshape(rows, -1)


def decode_attention_ref(
    q: jax.Array,       # (B, H, D)
    k: jax.Array,       # (B, S, K, D)
    v: jax.Array,       # (B, S, K, D)
    pos: int,           # attend to slots <= pos
) -> jax.Array:
    B, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.reshape(B, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k.astype(jnp.float32)) * (D ** -0.5)
    valid = jnp.arange(k.shape[1]) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def flash_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """(B, S, H, D) x (B, S, K, D) -> (B, S, H, D) full-precision attention."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.reshape(B, S, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def ssd_chunk_ref(x, B_c, C_c, dt, dA, h0):
    """Single SSD chunk oracle — mirrors models.ssm._chunk_math."""
    from repro.models.ssm import _chunk_math

    return _chunk_math(x, B_c, C_c, dt, dA, h0)
