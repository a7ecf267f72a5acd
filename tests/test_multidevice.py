"""Multi-device semantics on 8 fake CPU devices (subprocess: device count
locks at backend init, so these run in a child interpreter).

Checks that the expert-parallel shard_map MoE — both the E >= n_model
partitioned case and the E < n_model replica-split case — matches the
exact local reference, and that a sharded forward matches unsharded.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models.moe import (
        init_moe_params, moe_apply_a2a, moe_apply_local, moe_apply_sharded,
    )
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.sharding.specs import ShardCtx

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
    key = jax.random.PRNGKey(0)

    # Case 1: E=8 experts over 4 model ranks (2 experts/rank)
    cfg = replace(get_config("olmoe-1b-7b", smoke=True),
                  num_experts=8, experts_per_token=2, capacity_factor=32.0)
    p = init_moe_params(cfg, key)
    x = (jax.random.normal(key, (4, 16, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    y_loc, _ = moe_apply_local(cfg, p, x)
    y_sh, _ = moe_apply_sharded(cfg, p, x, ctx, small_batch_threshold=0)
    d1 = float(jnp.max(jnp.abs(y_loc.astype(jnp.float32) - y_sh.astype(jnp.float32))))
    assert d1 < 0.05, ("partitioned", d1)

    # Case 2: E=2 experts over 4 model ranks (replica split, n_rep=2)
    cfg2 = replace(cfg, num_experts=2, experts_per_token=1)
    p2 = init_moe_params(cfg2, key)
    y_loc2, _ = moe_apply_local(cfg2, p2, x)
    y_sh2, _ = moe_apply_sharded(cfg2, p2, x, ctx, small_batch_threshold=0)
    d2 = float(jnp.max(jnp.abs(y_loc2.astype(jnp.float32) - y_sh2.astype(jnp.float32))))
    assert d2 < 0.05, ("replica-split", d2)

    # Case 2b: all-to-all dispatch == local (E=8 over 4 ranks, tokens
    # sharded over the model axis as well)
    y_a2a, _ = moe_apply_a2a(cfg, p, x, ctx)
    d2b = float(jnp.max(jnp.abs(y_loc.astype(jnp.float32) - y_a2a.astype(jnp.float32))))
    assert d2b < 0.05, ("a2a", d2b)

    # Case 3: whole-model forward sharded == unsharded (capacity high enough
    # that the GShard-style dispatch drops no tokens)
    cfgm = replace(get_config("mixtral-8x7b", smoke=True), capacity_factor=32.0)
    pm = M.init_params(cfgm, key)
    toks = jax.random.randint(key, (4, 16), 0, cfgm.vocab_size)
    a, _, _ = M.forward(cfgm, pm, toks)
    b, _, _ = M.forward(cfgm, pm, toks, ctx=ctx)
    d3 = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    assert d3 < 0.08, ("forward", d3)
    print("MULTIDEVICE_OK", d1, d2, d3)
    """
)


@pytest.mark.slow
def test_sharded_moe_on_8_fake_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "MULTIDEVICE_OK" in r.stdout
