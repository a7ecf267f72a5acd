"""Compile the main path's device programs for a TPU v5e at Mixtral widths.

Nothing runs: each program is lowered against a described ``v5e:2x2``
topology (one chip of it) and compiled by the TPU compiler that ships with
jaxlib, which refuses what a chip would refuse — unaligned kernel blocks,
too much VMEM, a program that does not fit HBM.  The topology is described
inside a fixture, so collection never loads the TPU library; where it
cannot be described the tests skip.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
tests steer ``kernels.ops`` onto the compiled Pallas path themselves.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import registry
from repro.configs import get_config
from repro.core import engine  # noqa: F401  (registers the engine modules)
from repro.kernels import ops as kernel_ops

HBM_BYTES = 16 * 2**30            # one v5e chip
E, C, D, F = 8, 256, 4096, 14336  # Mixtral-8x7B expert stage


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this jaxlib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(monkeypatch):
    """Compiled Pallas kernels instead of the CPU fallback, and no
    persistent cache (a TPU executable written here cannot be read back
    without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(kernel_ops, "default_use_kernel", lambda: True)
    monkeypatch.setattr(kernel_ops, "default_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return used < HBM_BYTES


def _layer_params(cfg, sharding):
    from repro.models.blocks import init_layer_params

    shapes = jax.eval_shape(
        lambda: init_layer_params(cfg, "attn", "moe", jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), shapes)


def test_expert_ffn_kernel_compiles_at_mixtral_widths(one_chip, tpu_compile):
    x = _spec(one_chip, (E, C, D))
    wg = _spec(one_chip, (E, D, F))
    wd = _spec(one_chip, (E, F, D))
    compiled = jax.jit(
        lambda *a: kernel_ops.expert_ffn(*a, interpret=False)
    ).lower(x, wg, wg, wd).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


@pytest.mark.parametrize("T,E,k,D,F", [
    (512, 64, 6, 2048, 1408),      # DeepSeek-V2-Lite decode, batch 512
    (64, 8, 2, 4096, 14336),       # Mixtral-8x7B resident decode, batch 64
    (192, 8, 2, 4096, 14336),      # Mixtral-8x7B offload decode, batch 192
], ids=["deepseek-512", "mixtral-64", "mixtral-192"])
def test_ragged_expert_ffn_compiles_at_decode_widths(one_chip, tpu_compile,
                                                     T, E, k, D, F):
    """The ragged decode kernel over the buffer ``ragged_dispatch`` lays
    out for a dropless batch of ``T`` rows (capacity = T)."""
    from repro.models import moe as moe_mod

    tm = moe_mod.ragged_tile(T)
    R = moe_mod.ragged_buffer_rows(T, k, E, T)
    x = _spec(one_chip, (R, D))
    tiles = _spec(one_chip, (R // tm,), jnp.int32)
    used = _spec(one_chip, (1,), jnp.int32)
    wg = _spec(one_chip, (E, D, F))
    wd = _spec(one_chip, (E, F, D))
    compiled = jax.jit(
        lambda *a: kernel_ops.ragged_expert_ffn(*a, block_m=tm,
                                                interpret=False)
    ).lower(x, tiles, used, wg, wg, wd).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%expert_ffn" in text
    assert _fits(compiled)


def test_attn_decode_module_compiles_at_full_width(one_chip, tpu_compile):
    cfg = get_config("mixtral-8x7b")
    B, span = 16, 544
    p = _layer_params(cfg, one_chip)
    kv = _spec(one_chip, (B, span, cfg.num_kv_heads, cfg.head_dim))
    fn = registry.get("engine.attn_decode").fn
    compiled = fn.lower(
        cfg, 0, {"norm1": p["norm1"], "attn": p["attn"]},
        _spec(one_chip, (B, cfg.d_model)), kv, kv,
        _spec(one_chip, (B,), jnp.int32),
    ).compile()
    assert _fits(compiled)


def test_grouped_ffn_module_compiles_at_full_width(one_chip, tpu_compile):
    cfg = get_config("mixtral-8x7b")
    T, k = 16, cfg.experts_per_token
    moe = _layer_params(cfg, one_chip)["moe"]
    fn = registry.get("engine.grouped_expert_ffn").fn
    compiled = fn.lower(
        cfg, T, _spec(one_chip, (T, cfg.d_model)),
        _spec(one_chip, (T, k), jnp.float32),
        _spec(one_chip, (T, k), jnp.int32),
        moe["experts_w_gate"], moe["experts_w_up"], moe["experts_w_down"],
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_mla_decode_kernel_compiles_at_deepseek_widths(one_chip, tpu_compile):
    """The absorbed latent-attention decode of DeepSeek-V2-Lite at the
    benchmark cell's batch and span: 512 rows, 16 heads, a 512-wide
    latent and a 64-wide rope key over 1536 cached positions."""
    B, H, r, rope, span = 512, 16, 512, 64, 1536
    q = _spec(one_chip, (B, H, r + rope))
    c = _spec(one_chip, (B, span // 2, 2 * (r + rope)))
    pos = _spec(one_chip, (B,), jnp.int32)
    compiled = jax.jit(
        lambda *a: kernel_ops.mla_decode(*a, rank=r, scale=0.1)
    ).lower(q, c, pos).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_decode" in text
    assert _fits(compiled)
