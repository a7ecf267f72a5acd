"""Expert-parallel + data-parallel serving over the visible devices.

Demonstrates the ``repro.distributed`` subsystem end-to-end: a ``(1, ep)``
mesh shards every MoE layer's expert stacks across its ``model`` axis
(pipelined all-to-all dispatch), and ``ReplicaServer`` fans one arrival
queue over ``dp`` data-parallel replicas of that engine, each on its own
group of ``ep`` devices.

The run serves the same requests twice — single-device and on the mesh —
and checks the generated tokens match token-for-token (the subsystem's
standing contract: distribution changes WHERE experts run, never WHICH
tokens come out).

The backend is whatever JAX finds.  Without accelerators, split the host
CPU into 8 XLA devices before launch:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/serve_mesh.py [--dp 2] [--ep 2]
"""
import argparse

import jax

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.data.datasets import DatasetSpec, synthetic_requests
from repro.distributed import ReplicaServer
from repro.launch.mesh import make_debug_mesh
from repro.models import model as M
from repro.serving.server import ServeConfig, Server
from repro.sharding.specs import ShardCtx


def serve(cfg, params, requests, plan, serve_cfg, dp):
    if dp > 1:
        server = ReplicaServer(cfg, params, dp, plan=plan, serve=serve_cfg)
        for r in requests:
            server.submit(r)
        return server.run().merged
    server = Server(cfg, params, plan, serve_cfg)
    for r in requests:
        server.submit(r)
    return server.run()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--dp", type=int, default=2,
                    help="data-parallel Server replicas")
    ap.add_argument("--ep", type=int, default=2,
                    help="expert-parallel ranks (shards num_experts)")
    ap.add_argument("--ep-chunks", type=int, default=2,
                    help="pipelined all-to-all chunks per decode step")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-len", type=int, default=8)
    args = ap.parse_args()

    if len(jax.devices()) < args.ep:
        raise SystemExit(
            f"--ep {args.ep} needs {args.ep} devices, {len(jax.devices())} "
            "visible (on CPU: XLA_FLAGS=--xla_force_host_platform_device_"
            "count=8)")
    cfg = get_config(args.arch, smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = DatasetSpec("mesh-demo", args.requests, args.prompt_len,
                      args.decode_len)
    requests = synthetic_requests(spec, cfg.vocab_size)
    plan = Plan(B=8, b_a=8, b_e=64, decode_chunk=4)

    base = serve(cfg, params, requests, plan,
                 ServeConfig(scheduler="static",
                             decode_len=args.decode_len), dp=1)

    sctx = ShardCtx(mesh=make_debug_mesh(1, args.ep), batch_axes=("data",),
                    model_axis="model", moe_dispatch="a2a")
    mesh_cfg = ServeConfig(scheduler="static", decode_len=args.decode_len,
                           sctx=sctx, ep_chunks=args.ep_chunks)
    print(f"mesh: dp={args.dp} replicas x ep={args.ep} expert ranks "
          f"({cfg.num_experts // args.ep} experts/rank), "
          f"ep_chunks={args.ep_chunks}")
    rep = serve(cfg, params, requests, plan, mesh_cfg, dp=args.dp)

    same = all(
        (a.tokens == b.tokens).all()
        for a, b in zip(base.request_results, rep.request_results)
    )
    print(f"tokens identical to single-device serve: {same}")
    print(f"decode throughput (this host): {rep.decode_throughput:.1f} tok/s")
    print(f"a2a exchanged: {rep.a2a_gb:.4f}GB over "
          f"{rep.collective_dispatches} collective dispatches")
    assert same, "mesh serving must be token-identical"


if __name__ == "__main__":
    main()
