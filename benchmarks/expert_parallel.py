"""Expert-parallel decode: single-device vs serial a2a vs pipelined a2a.

The same workload served three ways: the single-device grouped path (the
token reference), expert-parallel dispatch with ONE all-to-all per decode
step (``ep_chunks=1`` — the exchange is fully exposed), and the pipelined
schedule (``ep_chunks=4`` — chunk k+1's exchange overlaps chunk k's expert
GEMMs, the EPS-MoE shape).  Tokens are identical across all rows — the
mesh moves WHERE experts run, never WHICH tokens come out — so
``tokens_match%`` doubles as the bit-identity check and ``a2a_gb`` is the
exchanged collective payload from the ServeReport.

CPU caveat: 8 virtual XLA devices share one physical socket, so wall-clock
tok/s mostly measures dispatch overhead at smoke scale, not real overlap;
``a2a_gb`` and the pipelined-vs-serial ORDER are the paper-relevant
signals.  Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
with fewer than 2 visible devices the mesh rows degrade to ep=1
(single-device execution, noted in the ``ep`` column).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import Table, fmt
from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.launch.mesh import make_debug_mesh
from repro.models import model as M
from repro.serving.scheduler import Request, serve_dataset
from repro.sharding.specs import ShardCtx


def expert_parallel() -> Table:
    t = Table("expert_parallel",
              ["mode", "ep", "chunks", "decode_tok_per_s", "a2a_gb",
               "collectives", "tokens_match%"])
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    DEC = 24
    prompts = [rng.integers(5, cfg.vocab_size - 5, 24).tolist()
               for _ in range(8)]
    reqs = lambda: [Request(prompt=p, decode_len=DEC) for p in prompts]
    plan = Plan(B=8, b_a=8, b_e=64, omega=0.0, decode_chunk=4)

    ep = min(4, len(jax.devices()))
    if ep < 2:
        ep = 1                      # degraded: no mesh to shard over
    sctx = None
    if ep > 1:
        sctx = ShardCtx(mesh=make_debug_mesh(1, ep),
                        batch_axes=("data",), model_axis="model",
                        moe_dispatch="a2a")
    modes = [
        ("single-device", None, 1),
        ("ep-serial", sctx, 1),
        ("ep-pipelined", sctx, 4),
    ]

    def run(ctx, chunks):
        return serve_dataset(cfg, params, reqs(), plan, DEC, max_seq=64,
                             sctx=ctx, ep_chunks=chunks)

    for _, ctx, chunks in modes:    # untimed warm-up (per-mode jit caches)
        run(ctx, chunks)
    ref = None
    for mode, ctx, chunks in modes:
        rep = run(ctx, chunks)
        toks = np.concatenate([np.asarray(r.tokens).reshape(-1)
                               for r in rep.request_results])
        if ref is None:
            ref = toks
        match = float((ref == toks).mean())
        t.add(mode, 1 if ctx is None else ep, chunks,
              fmt(rep.decode_throughput), fmt(rep.a2a_gb, 4),
              rep.collective_dispatches, fmt(100 * match))
    return t


ALL = [expert_parallel]
