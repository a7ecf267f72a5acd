"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, compiles, and fits — with no real hardware.

For each combination the appropriate step function (train_step /
prefill_step / serve_step) is jit'd with production in_shardings, lowered
against ShapeDtypeStruct inputs (no allocation), compiled for the
256-chip single-pod mesh and the 512-chip 2-pod mesh, and the compiled
artifact's memory_analysis / cost_analysis / collective schedule is recorded
to reports/dryrun/*.json for the roofline analysis.

Usage:
    python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch import hlo_analysis
from repro.launch.mesh import make_ctx, make_production_mesh
from repro.models import model as model_mod
from repro.models.frontends import frontend_spec
from repro.sharding.specs import ShardCtx, cache_shardings, param_shardings
from repro.train.optimizer import adamw_init
from repro.train.train_loop import make_train_step

REPORT_DIR = os.path.join(os.path.dirname(__file__), "../../../reports/dryrun")


def skip_reason(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} is pure full-attention (see DESIGN.md §4)"
        )
    return None


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: model_mod.init_params(cfg, k), jax.random.PRNGKey(0)
    )


def _named(ctx: ShardCtx, *logical, shape):
    return NamedSharding(ctx.mesh, ctx.spec(*logical, shape=shape))


def build_case(cfg: ModelConfig, shape: ShapeSpec, ctx: ShardCtx,
               weights: str = "fsdp"):
    """Returns (fn, abstract_args, in_shardings, scan_trips)."""
    zero1 = weights == "fsdp"
    params = abstract_params(cfg)
    pspecs = param_shardings(ctx, params, zero1=zero1)
    B, S = shape.global_batch, shape.seq_len
    fe = frontend_spec(cfg, B)
    G = model_mod.num_groups(cfg)

    if shape.kind == "train":
        opt = jax.eval_shape(adamw_init, params)
        ospecs = type(opt)(
            NamedSharding(ctx.mesh, P()),
            param_shardings(ctx, opt.mu, zero1=zero1),
            param_shardings(ctx, opt.nu, zero1=zero1),
        )
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        labels = jax.ShapeDtypeStruct((B, S), jnp.int32)
        tspec = _named(ctx, "batch", None, shape=(B, S))
        step = make_train_step(cfg, ctx, remat=True)
        args = [params, opt, tokens, labels]
        shards = [pspecs, ospecs, tspec, tspec]
        if fe is not None:
            args.append(fe)
            shards.append(_named(ctx, "batch", None, None, shape=fe.shape))
        return step, args, shards, G

    if shape.kind == "prefill":
        def prefill_step(params, tokens, frontend_emb=None):
            return model_mod.prefill(cfg, params, tokens, frontend_emb, ctx)

        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        args = [params, tokens]
        shards = [pspecs, _named(ctx, "batch", None, shape=(B, S))]
        if fe is not None:
            args.append(fe)
            shards.append(_named(ctx, "batch", None, None, shape=fe.shape))
        return prefill_step, args, shards, G

    # decode: ONE new token against a cache of seq_len
    def serve_step(params, cache, tokens, pos):
        return model_mod.decode_step(cfg, params, cache, tokens, pos, ctx)

    cache = jax.eval_shape(lambda: model_mod.init_cache(cfg, B, S))
    cspecs = cache_shardings(ctx, cache)
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    args = [params, cache, tokens, pos]
    shards = [
        pspecs, cspecs,
        _named(ctx, "batch", shape=(B,)),
        NamedSharding(ctx.mesh, P()),
    ]
    return serve_step, args, shards, G


def run_case(arch: str, shape_name: str, multi_pod: bool,
             weights: str = "fsdp", save: bool = True,
             seq_shard: bool | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "weights": weights, "status": "ok",
    }
    reason = skip_reason(cfg, shape)
    if reason:
        out["status"] = "skipped"
        out["reason"] = reason
        return _save(out) if save else out

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    if seq_shard is None:
        seq_shard = shape.kind == "train"
    out["seq_shard"] = seq_shard
    ctx = make_ctx(mesh, seq_shard=seq_shard)
    try:
        fn, args, shards, trips = build_case(cfg, shape, ctx, weights)
        jitted = jax.jit(fn, in_shardings=tuple(shards))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        colls = hlo_analysis.collective_stats(hlo, default_trips=trips)
        out.update(
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_devices=mesh.devices.size,
            memory={
                k: getattr(mem, k, None)
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
            } if mem is not None else None,
            flops=float(cost.get("flops", -1.0)) if cost else None,
            bytes_accessed=float(cost.get("bytes accessed", -1.0)) if cost else None,
            collectives=colls,
            collective_bytes=hlo_analysis.total_collective_bytes(hlo, trips),
            dot_flops_per_device=hlo_analysis.dot_flops(hlo, trips),
            scan_trips=trips,
        )
    except Exception as e:  # noqa: BLE001 — a failure here is a finding
        out["status"] = "failed"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-4000:]
    return _save(out) if save else out


def _save(out: dict) -> dict:
    os.makedirs(REPORT_DIR, exist_ok=True)
    default_sp = out.get("shape") == "train_4k"
    suffix = ""
    if out.get("seq_shard") is not None and out["seq_shard"] != default_sp:
        suffix = "_sp" if out["seq_shard"] else "_nosp"
    name = f"{out['arch']}_{out['shape']}_{out['mesh']}_{out['weights']}{suffix}.json"
    with open(os.path.join(REPORT_DIR, name), "w") as f:
        json.dump(out, f, indent=2, default=str)
    return out


def main() -> None:
    # 512 virtual CPU devices for the production meshes; must precede the
    # first backend initialization (the device count locks there)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--weights", default="fsdp", choices=["fsdp", "tp"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                fname = os.path.join(
                    REPORT_DIR,
                    f"{arch}_{shape}_{mesh_name}_{args.weights}.json",
                )
                if args.skip_existing and os.path.exists(fname):
                    print(f"[skip existing] {arch} {shape} {mesh_name}")
                    continue
                r = run_case(arch, shape, multi, args.weights)
                mem = (r.get("memory") or {}).get("temp_size_in_bytes")
                print(
                    f"[{r['status']:7s}] {arch:24s} {shape:12s} {mesh_name:6s}"
                    f" compile={r.get('compile_s', '-'):>6}s"
                    f" temp={mem if mem is not None else '-'}"
                    f" {r.get('error', r.get('reason', ''))[:90]}"
                )


if __name__ == "__main__":
    main()
