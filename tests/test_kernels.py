"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.models.ssm import ssd_scan as ssd_jnp

KEY = jax.random.PRNGKey(5)


def _rand(shape, dtype, scale=1.0, key=KEY):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 0.05}


# ---------------------------------------------------------------------------
# Grouped expert GEMM + fused FFN
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(2, 128, 256, 128), (4, 256, 512, 384),
                                     (1, 384, 256, 256)])
def test_grouped_matmul(E, C, D, F, dtype):
    x = _rand((E, C, D), dtype, 0.3)
    w = _rand((E, D, F), dtype, 0.05)
    got = ops.grouped_matmul(x, w, interpret=True)
    want = ref.grouped_matmul_ref(x, w)
    d = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert d < TOL[dtype] * D ** 0.5, d


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(2, 128, 256, 128), (3, 256, 128, 384)])
def test_expert_ffn_fused(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 4)
    x = _rand((E, C, D), dtype, 0.3, ks[0])
    wg = _rand((E, D, F), dtype, 0.05, ks[1])
    wu = _rand((E, D, F), dtype, 0.05, ks[2])
    wd = _rand((E, F, D), dtype, 0.05, ks[3])
    got = ops.expert_ffn(x, wg, wu, wd, interpret=True)
    want = ref.expert_ffn_ref(x, wg, wu, wd)
    d = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert d < TOL[dtype], d


def test_expert_ffn_padding_path():
    """C not a tile multiple exercises the ops.py padding."""
    ks = jax.random.split(KEY, 4)
    x = _rand((2, 100, 256), jnp.float32, 0.3, ks[0])
    wg = _rand((2, 256, 128), jnp.float32, 0.05, ks[1])
    wu = _rand((2, 256, 128), jnp.float32, 0.05, ks[2])
    wd = _rand((2, 128, 256), jnp.float32, 0.05, ks[3])
    got = ops.expert_ffn(x, wg, wu, wd, interpret=True)
    want = ref.expert_ffn_ref(x, wg, wu, wd)
    assert jnp.max(jnp.abs(got - want)) < 1e-4


# T rows routed over E experts: each case gives the copies of each expert
# (at most T, since a row routes to an expert once).  The row tile is
# ``moe.ragged_tile(T)`` and the buffer ``moe.ragged_buffer_rows`` rows, as
# the decode dispatch lays them out.
RAGGED_CASES = {
    "experts-with-no-copies": (24, [24, 0, 5, 0]),
    "one-expert-over-a-tile": (200, [150, 30, 0]),
    "all-copies-on-one-expert": (64, [64, 0, 0, 0]),
    "trailing-unused-tiles": (40, [3, 2, 1, 0]),
    "T-below-128": (100, [60, 100, 40]),
    "T-above-128": (300, [130, 300, 170, 0]),
}


@pytest.mark.parametrize("case", list(RAGGED_CASES))
def test_ragged_expert_ffn(case):
    """Each used tile is its expert's FFN (the oracle over the same tiles);
    experts without copies take no tile, an expert over the tile takes
    two, and tiles past the used count are not computed."""
    from repro.models import moe as moe_mod

    T, copies = RAGGED_CASES[case]
    E, D, F = len(copies), 256, 256
    k = -(-sum(copies) // T)
    tm = moe_mod.ragged_tile(T)
    R = moe_mod.ragged_buffer_rows(T, k, E, T)
    tiles = [-(-c // tm) for c in copies]
    n_used = sum(tiles)
    assert n_used * tm <= R
    if case == "trailing-unused-tiles":
        assert n_used < R // tm
    tile_expert = [e for e, n in enumerate(tiles) for _ in range(n)]
    tile_expert += [E - 1] * (R // tm - n_used)
    ks = jax.random.split(KEY, 4)
    x = _rand((R, D), jnp.bfloat16, 0.3, ks[0])
    # rows past each expert's copies are the dispatch's zeros
    live = jnp.concatenate([
        jnp.arange(t * tm) < c for c, t in zip(copies, tiles)
    ] + [jnp.zeros((R - n_used * tm,), bool)])
    x = jnp.where(live[:, None], x, 0)
    wg = _rand((E, D, F), jnp.bfloat16, 0.05, ks[1])
    wu = _rand((E, D, F), jnp.bfloat16, 0.05, ks[2])
    wd = _rand((E, F, D), jnp.bfloat16, 0.05, ks[3])
    te = jnp.asarray(tile_expert, jnp.int32)
    got = ops.ragged_expert_ffn(x, te, n_used, wg, wu, wd, block_m=tm,
                                interpret=True)
    want = ref.ragged_expert_ffn_ref(x, te, n_used, wg, wu, wd, tm)
    got = got[:n_used * tm].astype(jnp.float32)
    assert jnp.max(jnp.abs(got - want.astype(jnp.float32))) < TOL[jnp.bfloat16]
    assert not jnp.any(got[~live[:n_used * tm]])   # empty rows compute 0


# ---------------------------------------------------------------------------
# Flash decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,hd,S,pos", [
    (2, 8, 2, 64, 512, 300),
    (1, 4, 4, 128, 256, 255),
    (3, 16, 2, 64, 1024, 17),
])
def test_decode_attention(B, H, K, hd, S, pos, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand((B, H, hd), dtype, 1.0, ks[0])
    k = _rand((B, S, K, hd), dtype, 1.0, ks[1])
    v = _rand((B, S, K, hd), dtype, 1.0, ks[2])
    got = ops.decode_attention(q, k, v, jnp.int32(pos), interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos)
    d = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert d < TOL[dtype], d


def test_decode_attention_mask_boundary():
    """Slots beyond pos must not contribute: poisoning them changes nothing."""
    ks = jax.random.split(KEY, 3)
    B, H, K, hd, S, pos = 1, 4, 2, 64, 512, 100
    q = _rand((B, H, hd), jnp.float32, 1.0, ks[0])
    k = _rand((B, S, K, hd), jnp.float32, 1.0, ks[1])
    v = _rand((B, S, K, hd), jnp.float32, 1.0, ks[2])
    base = ops.decode_attention(q, k, v, jnp.int32(pos), interpret=True)
    k2 = k.at[:, pos + 1 :].set(1e4)
    v2 = v.at[:, pos + 1 :].set(-1e4)
    poisoned = ops.decode_attention(q, k2, v2, jnp.int32(pos), interpret=True)
    assert jnp.max(jnp.abs(base - poisoned)) < 1e-5


# ---------------------------------------------------------------------------
# Flash attention (prefill/train)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,K", [(512, 4, 2), (1024, 2, 2)])
def test_flash_attention(S, H, K, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand((2, S, H, 64), dtype, 1.0, ks[0])
    k = _rand((2, S, K, 64), dtype, 1.0, ks[1])
    v = _rand((2, S, K, 64), dtype, 1.0, ks[2])
    got = ops.flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention_ref(q, jnp.repeat(k, H // K, 2),
                                   jnp.repeat(v, H // K, 2))
    d = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert d < TOL[dtype], d


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,nh,hp,ns,chunk", [
    (256, 4, 32, 16, 64),
    (128, 8, 16, 32, 32),
])
def test_ssd_scan_kernel(S, nh, hp, ns, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = _rand((2, S, nh, hp), dtype, 0.5, ks[0])
    B_in = _rand((2, S, ns), dtype, 0.5, ks[1])
    C_in = _rand((2, S, ns), dtype, 0.5, ks[2])
    dt = jax.nn.softplus(jax.random.normal(ks[3], (2, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[4], (nh,)) * 0.3)
    y, h = ops.ssd_scan(x, B_in, C_in, dt, A, chunk, interpret=True)
    y_ref, h_ref = ssd_jnp(x, B_in, C_in, dt, A, chunk)
    dy = jnp.max(jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32)))
    dh = jnp.max(jnp.abs(h - h_ref))
    assert dy < TOL[dtype] * 4, dy
    assert dh < 1e-2 if dtype == jnp.float32 else dh < 0.5
