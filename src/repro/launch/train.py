"""Training launcher.

On this CPU container it runs the smoke-scale configs for real; on a TPU
slice the same entry point builds the production mesh and shards
params/optimizer per DESIGN.md §5.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 20
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.datasets import synthetic_batches
from repro.launch.mesh import make_ctx, make_debug_mesh
from repro.models import model as M
from repro.sharding.specs import ShardCtx, param_shardings
from repro.train.train_loop import train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (requires a real TPU slice)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=not args.full)
    n_dev = jax.device_count()
    if n_dev > 1:
        data = max(1, n_dev // 16)
        mesh = make_debug_mesh(data, n_dev // data)
        ctx = make_ctx(mesh, seq_shard=True)
        print(f"mesh: {dict(mesh.shape)}")
    else:
        ctx = ShardCtx()

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if ctx.mesh is not None:
        shardings = param_shardings(ctx, params, zero1=True)
        params = jax.device_put(params, shardings)  # lint: allow[MG105] init-time sharded placement, not a serving-path transfer
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params, {args.steps} steps "
          f"of {args.batch}x{args.seq} on {n_dev} device(s)")
    batches = iter(
        (jnp.asarray(t), jnp.asarray(l))
        for t, l in synthetic_batches(cfg.vocab_size, args.batch, args.seq)
    )
    train_loop(
        cfg, params, batches, steps=args.steps, ctx=ctx, lr=args.lr,
        log_every=max(1, args.steps // 10),
        checkpoint_path=args.checkpoint,
        checkpoint_every=0 if not args.checkpoint else max(10, args.steps // 2),
    )


if __name__ == "__main__":
    main()
