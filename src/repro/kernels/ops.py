"""Jit'd public wrappers around the Pallas kernels.

Handle padding to tile multiples, GQA head repetition, and backend
selection: ``interpret=True`` (Python interpretation, bit-exact oracle
semantics) everywhere except real TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import expert_gemm as _eg
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import mla_decode as _mla
from repro.kernels import ssd_scan as _ssd


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_use_kernel() -> bool:
    """Run the compiled Pallas kernels only on real TPU; everywhere else the
    XLA einsum fallback is both faster and bit-stable."""
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x, w, interpret=None):
    """(E, C, D) @ (E, D, F) with automatic tile padding."""
    interpret = default_interpret() if interpret is None else interpret
    E, C, D = x.shape
    F = w.shape[-1]
    bc = min(128, C) if C % 128 else 128
    xp = _pad_to(x, 1, 128)
    xp = _pad_to(xp, 2, 256)
    wp = _pad_to(_pad_to(w, 1, 256), 2, 128)
    y = _eg.grouped_matmul(xp, wp, interpret=interpret)
    return y[:, :C, :F]


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn(x, wg, wu, wd, interpret=None):
    """Fused gated expert FFN with tile padding."""
    interpret = default_interpret() if interpret is None else interpret
    E, C, D = x.shape
    xp = _pad_to(x, 1, 128)
    wgp = _pad_to(wg, 2, 128)
    wup = _pad_to(wu, 2, 128)
    wdp = _pad_to(wd, 1, 128)
    y = _eg.expert_ffn(xp, wgp, wup, wdp, interpret=interpret)
    return y[:, :C, :]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def ragged_expert_ffn(x, tile_expert, n_used, wg, wu, wd, block_m,
                      interpret=None):
    """Fused gated expert FFN over a ragged ``(R, D)`` row buffer whose
    ``block_m``-row tiles each belong to one expert (``tile_expert``); the
    first ``n_used`` tiles are computed, the rest left unwritten.  Pads
    the F dim to the tile as ``expert_ffn`` does.  The Pallas kernel alone:
    the one caller, ``models.moe.ragged_dispatch``, takes the capacity
    einsum instead where no TPU compiles it."""
    interpret = default_interpret() if interpret is None else interpret
    wgp = _pad_to(wg, 2, 128)
    wup = _pad_to(wu, 2, 128)
    wdp = _pad_to(wd, 1, 128)
    return _eg.ragged_expert_ffn(
        x, jnp.asarray(tile_expert, jnp.int32),
        jnp.asarray(n_used, jnp.int32).reshape(1), wgp, wup, wdp,
        block_m=block_m, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def grouped_expert_ffn(x, wg, wu, wd, use_kernel=None):
    """Backend-dispatched grouped expert FFN over (E, C, D) capacity buffers.

    One launch covers every expert: the fused Pallas kernel on real TPU, an
    einsum-based XLA path elsewhere.  The fallback uses the same op sequence
    as a per-expert ``x @ w`` chain (bf16 intermediates); the Pallas
    kernel keeps an f32 VMEM accumulator and agrees to bf16 rounding
    (tests/test_kernels.py).
    """
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    if use_kernel:
        # interpret=None: compiled on TPU, interpret mode if the kernel is
        # forced on a backend Pallas cannot compile for
        return expert_ffn(x, wg, wu, wd, interpret=None)
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    u = jnp.einsum("ecd,edf->ecf", x, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, pos, interpret=None):
    """(B,H,hd) x (B,S,K,hd) -> (B,H,hd), masked to slots <= pos."""
    interpret = default_interpret() if interpret is None else interpret
    S = k.shape[1]
    block_s = 256 if S % 256 == 0 else S
    return _da.decode_attention(
        q, k, v, pos, block_s=block_s, interpret=interpret
    )


def _largest_divisor(n: int, options) -> int:
    return next((o for o in options if n % o == 0), 1)


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "use_kernel"))
def mla_decode(q, c, pos, rank, scale, use_kernel=None):
    """Absorbed latent-attention decode: (B,H,rank+rope) queries over the
    paired latent cache (B,span/2,2*(rank+rope)) (``attention.mla_pack``)
    -> (B,H,rank), row ``b`` attending positions ``<= pos[b]``.  The
    Pallas kernel on real TPU; elsewhere the same math in XLA (scores and
    the latent sum accumulate in float32, the probabilities enter the sum
    in the cache's type, as in the kernel)."""
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    B = q.shape[0]
    span = 2 * c.shape[1]
    if use_kernel:
        # position blocks of an even size that divides the span (the whole
        # span where none of these does; the span is always even)
        block_t = _largest_divisor(span, (256, 128, 64, 32, 16))
        return _mla.mla_decode(
            q, c, pos, rank=rank, scale=scale,
            block_b=_largest_divisor(B, (8, 4, 2)),
            block_t=span if block_t == 1 else block_t,
            interpret=default_interpret(),
        )
    rows = _unpack_latent(c, rank)                         # (B, span, C)
    s = jnp.einsum("bhc,btc->bht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(span)[None, :] <= jnp.asarray(pos, jnp.int32)[:, None]
    s = jnp.where(valid[:, None, :], s, _mla.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,btr->bhr", p.astype(c.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _unpack_latent(c, rank: int):
    """Paired cache rows (..., span/2, 2C) -> per-position rows
    (..., span, C): the inverse of ``attention.mla_pack``."""
    *lead, half, two_c = c.shape
    rope = two_c // 2 - rank
    lat = c[..., :2 * rank].reshape(*lead, 2 * half, rank)
    pe = c[..., 2 * rank:].reshape(*lead, 2 * half, rope)
    return jnp.concatenate([lat, pe], axis=-1)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, causal=True, interpret=None):
    """(B,S,H,hd) GQA causal attention (KV heads repeated as needed)."""
    interpret = default_interpret() if interpret is None else interpret
    H, K = q.shape[2], k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    S = q.shape[1]
    block = 256 if S % 256 == 0 else S
    return _fa.flash_attention(
        q, k, v, block_q=block, block_k=block, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, B_in, C_in, dt, A, chunk, interpret=None):
    """Chunked SSD scan; returns (y, final_state)."""
    interpret = default_interpret() if interpret is None else interpret
    return _ssd.ssd_scan_pallas(
        x, B_in, C_in, dt, A, chunk, interpret=interpret
    )
