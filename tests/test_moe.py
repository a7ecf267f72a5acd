"""MoE layer: routing, capacity dispatch, expert-parallel shard_map path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.configs import get_config
from repro.models.moe import (
    _dispatch_combine,
    init_moe_params,
    moe_apply_capacity_local,
    moe_apply_local,
    moe_apply_sharded,
    moe_capacity,
    route,
)
from repro.sharding.specs import ShardCtx

KEY = jax.random.PRNGKey(11)


def _cfg(cf=8.0):
    cfg = get_config("olmoe-1b-7b", smoke=True)
    return replace(cfg, capacity_factor=cf)


def test_routing_topk_normalized():
    cfg = _cfg()
    p = init_moe_params(cfg, KEY)
    x = jax.random.normal(KEY, (32, cfg.d_model))
    gates, idx, probs = route(cfg, p["router"], x)
    assert gates.shape == (32, cfg.experts_per_token)
    assert jnp.allclose(gates.sum(-1), 1.0, atol=1e-5)
    assert int(idx.max()) < cfg.num_experts
    # top-k really is top-k of probs
    ref = jnp.argsort(-probs, axis=-1)[:, : cfg.experts_per_token]
    assert jnp.array_equal(jnp.sort(idx, -1), jnp.sort(ref, -1))


def test_capacity_matches_exact_when_not_dropping():
    cfg = _cfg(cf=64.0)         # capacity >> needed: no token drops
    p = init_moe_params(cfg, KEY)
    x = (jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    y_exact, _ = moe_apply_local(cfg, p, x)
    y_cap, _ = moe_apply_capacity_local(cfg, p, x)
    diff = jnp.max(jnp.abs(y_exact.astype(jnp.float32) -
                           y_cap.astype(jnp.float32)))
    assert diff < 0.03, diff


def test_sharded_matches_local_on_1dev_mesh():
    cfg = _cfg(cf=64.0)
    p = init_moe_params(cfg, KEY)
    x = (jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
    y_sh, aux_sh = moe_apply_sharded(cfg, p, x, ctx)
    y_loc, aux_loc = moe_apply_local(cfg, p, x)
    diff = jnp.max(jnp.abs(y_sh.astype(jnp.float32) -
                           y_loc.astype(jnp.float32)))
    assert diff < 0.03, diff
    assert abs(float(aux_sh) - float(aux_loc)) < 1e-3


def test_dispatch_conservation():
    """Every kept (token, expert) slot contributes exactly once."""
    cfg = _cfg(cf=64.0)
    T, D = 64, cfg.d_model
    x = jnp.ones((T, D), jnp.float32)
    gates = jnp.full((T, cfg.experts_per_token), 1.0 / cfg.experts_per_token)
    idx = jax.random.randint(
        KEY, (T, cfg.experts_per_token), 0, cfg.num_experts
    )
    # identity experts: w_gate such that silu(g)*u @ wd == x is hard; instead
    # count via an expert that returns constant 1 rows
    wg = jnp.zeros((cfg.num_experts, D, 8)) + 10.0   # silu(10·sum x) ~ large
    wu = jnp.full((cfg.num_experts, D, 8), 1.0 / (8 * D))
    wd = jnp.ones((cfg.num_experts, 8, D))
    cap = moe_capacity(cfg, T)
    y = _dispatch_combine(cfg, x, gates, idx, wg, wu, wd, jnp.int32(0), cap)
    assert y.shape == (T, D)
    assert bool(jnp.isfinite(y).all())


def test_capacity_drops_bound_memory():
    cfg = _cfg(cf=1.0)
    assert moe_capacity(cfg, 1024) <= int(
        1024 * cfg.experts_per_token / cfg.num_experts * 1.0 + 8
    ) + 8


def test_load_balance_loss_uniform_is_one():
    from repro.models.moe import load_balance_loss

    cfg = _cfg()
    T, E = 4096, cfg.num_experts
    probs = jnp.full((T, E), 1.0 / E)
    idx = jnp.stack(
        [jnp.arange(T) % E, (jnp.arange(T) + 1) % E], axis=1
    )[:, : cfg.experts_per_token]
    lb = load_balance_loss(cfg, probs, idx)
    assert abs(float(lb) - 1.0) < 0.05
