"""Sharding rules + 1-device-mesh numerical equivalence."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.sharding.specs import ShardCtx, cache_shardings, param_shardings

KEY = jax.random.PRNGKey(0)


def _ctx_1dev():
    mesh = make_mesh((1, 1), ("data", "model"))
    return ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")


def test_param_shardings_cover_all_leaves():
    cfg = get_config("jamba-1.5-large-398b", smoke=True)
    params = jax.eval_shape(lambda: M.init_params(cfg, KEY))
    specs = param_shardings(_ctx_1dev(), params, zero1=True)
    n_params = len(jax.tree.leaves(params))
    n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: x is None))
    assert n_params == n_specs


def test_param_shardings_divisibility_respected():
    """On the production mesh every spec divides its dim."""
    cfg = get_config("mixtral-8x7b")      # 8 experts vs model=16: fallback path
    params = jax.eval_shape(lambda: M.init_params(cfg, KEY))
    mesh = make_mesh((16, 16), ("data", "model"), jax.devices() * 256)
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
    specs = param_shardings(ctx, params, zero1=True)

    def check(leaf, sharding):
        spec = sharding.spec
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            size = 1
            axes = (ax,) if isinstance(ax, str) else ax
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (leaf.shape, spec)

    jax.tree.map(check, params, specs)


def test_expert_fallback_tensor_parallel():
    """mixtral 8 experts % model=16 != 0 => F-dim sharding instead."""
    cfg = get_config("mixtral-8x7b")
    params = jax.eval_shape(lambda: M.init_params(cfg, KEY))
    mesh = make_mesh((16, 16), ("data", "model"), jax.devices() * 256)
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
    specs = param_shardings(ctx, params, zero1=False)
    flat = {
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: x is None
        )[0]
    }
    gate_spec = next(v for k, v in flat.items() if "experts_w_gate" in k)
    assert gate_spec.spec[0] is None          # experts replicated
    assert gate_spec.spec[-1] == "model"      # hidden dim sharded


def test_forward_with_mesh_matches_without():
    cfg = get_config("olmoe-1b-7b", smoke=True)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    base, _, _ = M.forward(cfg, params, toks)
    ctx = _ctx_1dev()
    sharded, _, _ = M.forward(cfg, params, toks, ctx=ctx)
    d = jnp.max(jnp.abs(base.astype(jnp.float32) -
                        sharded.astype(jnp.float32)))
    assert float(d) < 0.05, d


def test_cache_shardings_structure():
    cfg = get_config("jamba-1.5-large-398b", smoke=True)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 32))
    specs = cache_shardings(_ctx_1dev(), cache)
    assert len(jax.tree.leaves(cache)) == len(
        jax.tree.leaves(specs, is_leaf=lambda x: x is None)
    )
