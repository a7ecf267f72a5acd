"""Pallas TPU kernel for absorbed latent-attention decode (DeepSeek-V2 MLA).

One new token per batch row attends its row's latent cache directly: with
``W_UK`` folded into the query (``q_lat = q_nope W_UK^T``) every head's
score against cached position ``t`` is ``q_lat . lat[t] + q_pe . pe[t]``,
where ``lat[t]`` is the cached normalised latent (``rank`` values) and
``pe[t]`` the shared rope key, and the head's output in latent space is the
softmax-weighted sum of the cached latents (``W_UV`` is applied after,
outside the kernel).  No cached row is ever up-projected, so the kernel
reads ``rank + rope`` values per position and layer instead of per-head
keys and values.

Layout (``models.attention.mla_pack``): positions are stored in pairs, one
pair per cache row of ``2 * (rank + rope)`` values,
``[lat[2k] | lat[2k+1] | pe[2k] | pe[2k+1]]`` (1152 values for
DeepSeek-V2-Lite, a whole number of 128-lane tiles).  A single position's
576 values would be padded to 640 lanes in HBM and make XLA relayout the
cache around each decode step's row write; paired, the cache is stored at
its own size, a step writes each new token as two contiguous slices in
place, and every slice the kernel takes is lane-aligned.

Grid ``(B / bb, span / bt)``: each step takes ``bb`` rows and ``bt``
cached positions (``bt / 2`` cache rows), with an online softmax across
the position blocks.  Blocks past a row group's last live position are
neither copied nor computed (the block index is clamped to the last live
one, which Pallas does not fetch again, and the step skips its math), so
a row costs what its context needs, not what the cache holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_decode_kernel(pos_ref, nblk_ref, q_ref, qpe_ref, c_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, bb: int, bt: int, n_t: int,
                       rank: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    nt = (((1,), (1,)), ((), ()))          # a @ b.T
    nn = (((1,), (0,)), ((), ()))          # a @ b

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nblk_ref[i])
    def _block():
        for r in range(bb):
            p_r = pos_ref[i * bb + r]

            @pl.when(j * bt <= p_r)
            def _row():
                q = q_ref[r]                          # (H, rank)
                qpe = qpe_ref[r]                      # (2, H, 2*rope)
                c = c_ref[r]                          # (bt/2, 2*(rank+rope))
                lat_e = c[:, :rank]
                lat_o = c[:, rank:2 * rank]
                pe = c[:, 2 * rank:]
                s_e = (dot(q, lat_e, nt) + dot(qpe[0], pe, nt)) * scale
                s_o = (dot(q, lat_o, nt) + dot(qpe[1], pe, nt)) * scale
                t_e = j * bt + 2 * jax.lax.broadcasted_iota(
                    jnp.int32, s_e.shape, 1)
                s_e = jnp.where(t_e <= p_r, s_e, NEG_INF)
                s_o = jnp.where(t_e + 1 <= p_r, s_o, NEG_INF)
                m_prev = m_ref[r]
                m_new = jnp.maximum(
                    m_prev, jnp.maximum(s_e.max(axis=1, keepdims=True),
                                        s_o.max(axis=1, keepdims=True)))
                p_e = jnp.exp(s_e - m_new)
                p_o = jnp.exp(s_o - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[r] = l_ref[r] * corr + (p_e.sum(axis=1, keepdims=True)
                                              + p_o.sum(axis=1, keepdims=True))
                acc_ref[r] = acc_ref[r] * corr + (
                    dot(p_e.astype(c.dtype), lat_e, nn)
                    + dot(p_o.astype(c.dtype), lat_o, nn))    # (H, rank)
                m_ref[r] = m_new

    @pl.when(j == n_t - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def mla_decode(
    q: jax.Array,        # (B, H, rank + rope): [q_lat, q_pe] per head
    c: jax.Array,        # (B, span / 2, 2 * (rank + rope)): paired cache
    pos: jax.Array,      # (B,) int32: row b attends positions <= pos[b]
    *,
    rank: int,           # latent width: the output is (B, H, rank)
    scale: float,
    block_b: int = 8,
    block_t: int = 256,
    interpret: bool = True,
) -> jax.Array:
    B, H, C = q.shape
    rope = C - rank
    span = 2 * c.shape[1]
    assert c.shape == (B, span // 2, 2 * C), (q.shape, c.shape)
    assert B % block_b == 0 and span % block_t == 0 and block_t % 2 == 0, (
        B, span, block_b, block_t)
    n_t = span // block_t
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    # the last position block each row group needs, plus one
    nblk = (jnp.max(jnp.minimum(pos, span - 1).reshape(-1, block_b), axis=1)
            // block_t + 1).astype(jnp.int32)
    # the rope query against a row's [pe[2k] | pe[2k+1]]: zeros over the
    # other position's half
    zero = jnp.zeros_like(q[..., rank:])
    qpe = jnp.stack([jnp.concatenate([q[..., rank:], zero], -1),
                     jnp.concatenate([zero, q[..., rank:]], -1)], axis=1)

    def c_map(i, j, pos_ref, nblk_ref):
        return (i, jnp.minimum(j, nblk_ref[i] - 1), 0)

    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, bb=block_b, bt=block_t,
                          n_t=n_t, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // block_b, n_t),
            in_specs=[
                pl.BlockSpec((block_b, H, rank),
                             lambda i, j, p, n: (i, 0, 0)),
                pl.BlockSpec((block_b, 2, H, 2 * rope),
                             lambda i, j, p, n: (i, 0, 0, 0)),
                pl.BlockSpec((block_b, block_t // 2, 2 * C), c_map),
            ],
            out_specs=pl.BlockSpec((block_b, H, rank),
                                   lambda i, j, p, n: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_b, H, 1), jnp.float32),
                pltpu.VMEM((block_b, H, 1), jnp.float32),
                pltpu.VMEM((block_b, H, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(pos, nblk, q[..., :rank], qpe, c)
