"""Pallas TPU kernels for the MoE expert module.

Three kernels:

* ``grouped_matmul``  — (E, C, D) @ (E, D, F) -> (E, C, F): the generic
  grouped GEMM building block, MXU-tiled.
* ``expert_ffn``      — the fused gated FFN silu(x@wg)*(x@wu) @ wd with the
  token tile and the f32 accumulator resident in VMEM across the F-tile
  loop.  This is the TPU adaptation of MoE-Gen's insight: amortize each
  expert-weight fetch (HBM->VMEM here, host->HBM at the system level) over
  the largest possible token batch.
* ``ragged_expert_ffn`` — the same fused FFN over a ragged ``(R, D)`` row
  buffer: each row tile belongs to one expert (a scalar-prefetched tile ->
  expert map), so an expert whose routed copies fit one tile has its
  weights read once a call, and tiles past the used count are neither
  fetched nor computed.  Decode's expert stage (``models.moe``
  ``ragged_dispatch``).

Both kernels are validated against kernels/ref.py in interpret mode across
shape/dtype sweeps (tests/test_kernels.py); ``kernels/ops.py`` holds the
jit'd padding wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Grouped GEMM
# ---------------------------------------------------------------------------
def _grouped_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_kd: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0],
        w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == n_kd - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,          # (E, C, D)
    w: jax.Array,          # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 256,
    interpret: bool = True,
) -> jax.Array:
    E, C, D = x.shape
    _, _, F = w.shape
    assert w.shape == (E, D, F)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0, (
        x.shape, w.shape, (block_c, block_f, block_d),
    )
    n_kd = D // block_d
    grid = (E, C // block_c, F // block_f, n_kd)
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, n_kd=n_kd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# Fused gated expert FFN
# ---------------------------------------------------------------------------
def _expert_ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, n_f: int):
    """Grid (E, C/bc, F/bf).  x tile and acc stay resident across the F loop."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bc, D)
    g = jax.lax.dot_general(
        x, wg_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (bc, bf)
    u = jax.lax.dot_general(
        x, wu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        h, wd_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_f - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def expert_ffn(
    x: jax.Array,          # (E, C, D)
    wg: jax.Array,         # (E, D, F)
    wu: jax.Array,         # (E, D, F)
    wd: jax.Array,         # (E, F, D)
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = True,
) -> jax.Array:
    E, C, D = x.shape
    F = wg.shape[-1]
    assert C % block_c == 0 and F % block_f == 0
    n_f = F // block_f
    grid = (E, C // block_c, n_f)
    return pl.pallas_call(
        functools.partial(_expert_ffn_kernel, n_f=n_f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, D), lambda e, i, j: (e, i, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, i, j: (e, 0, j)),
            pl.BlockSpec((1, D, block_f), lambda e, i, j: (e, 0, j)),
            pl.BlockSpec((1, block_f, D), lambda e, i, j: (e, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, D), lambda e, i, j: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, D), jnp.float32)],
        interpret=interpret,
    )(x, wg, wu, wd)


# ---------------------------------------------------------------------------
# Ragged fused gated expert FFN
# ---------------------------------------------------------------------------
def _ragged_expert_ffn_kernel(tile_expert_ref, n_used_ref, x_ref, wg_ref,
                              wu_ref, wd_ref, o_ref, acc_ref, *, n_f: int):
    """Grid (R/tm, F/bf): row tile ``i`` runs expert ``tile_expert[i]``'s
    FFN, with its x tile and accumulator resident across the F loop, as
    ``_expert_ffn_kernel``; tiles at or past ``n_used`` do nothing."""
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < n_used_ref[0]

    @pl.when(live & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        x = x_ref[...]                             # (tm, D)
        g = jax.lax.dot_general(
            x, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # (tm, bf)
        u = jax.lax.dot_general(
            x, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & (j == n_f - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ragged_expert_ffn(
    x: jax.Array,            # (R, D) rows, grouped into tiles by expert
    tile_expert: jax.Array,  # (R / block_m,) int32: each tile's expert
    n_used: jax.Array,       # (1,) int32: tiles [0, n_used) hold copies
    wg: jax.Array,           # (E, D, F)
    wu: jax.Array,           # (E, D, F)
    wd: jax.Array,           # (E, F, D)
    *,
    block_m: int,
    block_f: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """Rows of tiles past ``n_used`` are left unwritten.  Their steps map
    every block to the last used tile's (the last F block for the
    weights), which Pallas does not fetch again."""
    R, D = x.shape
    F = wg.shape[-1]
    assert R % block_m == 0 and F % block_f == 0, (x.shape, wg.shape)
    n_f = F // block_f

    def last(i, n):
        return jnp.minimum(i, jnp.maximum(n[0], 1) - 1)

    def f_block(i, j, n):
        return jnp.where(i < n[0], j, n_f - 1)

    def x_map(i, j, te, n):
        return (last(i, n), 0)

    def w_map(i, j, te, n):
        return (te[last(i, n)], 0, f_block(i, j, n))

    def wd_map(i, j, te, n):
        return (te[last(i, n)], f_block(i, j, n), 0)

    return pl.pallas_call(
        functools.partial(_ragged_expert_ffn_kernel, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // block_m, n_f),
            in_specs=[
                pl.BlockSpec((block_m, D), x_map),
                pl.BlockSpec((1, D, block_f), w_map),
                pl.BlockSpec((1, D, block_f), w_map),
                pl.BlockSpec((1, block_f, D), wd_map),
            ],
            out_specs=pl.BlockSpec((block_m, D), x_map),
            scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # the trace names the kernel's device op by this: the decode expert
        # kernel keeps the capacity kernel's name
        name="expert_ffn",
    )(tile_expert, n_used, x, wg, wu, wd)
