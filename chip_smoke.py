"""Bring-up check: MoE-Gen serving of Mixtral-8x7B widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip serves ``mixtral-8x7b`` at its published widths (d_model 4096,
32 query / 8 KV heads of 128, 8 experts top-2 of d_ff 14336, vocab 32000)
with the depth cut to 4 layers (12.1 GB of bf16 weights), through the
same ``Server`` and helpers ``repro.launch.serve`` uses:

1. streamed: only the base weights and attention stay on the chip; every
   expert stack lives in host memory and streams through ``ParamStore``'s
   double-buffered window (the paper's regime).  The device's peak bytes
   must stay below the model's bytes.
2. resident: every weight on the chip, fused decode chunks, the grouped
   expert FFN through the compiled Pallas kernel.  Its greedy tokens must
   equal the streamed phase's, and its prefill logits must agree with
   ``models.model.forward`` on the same weights.

``--chips 4`` runs only what exists across chips: expert parallelism
(ep=4, 8 layers, 22.5 GB of experts — more than any one chip holds) against
a reference forward on the same mesh, and four one-chip replicas behind
``ReplicaServer`` against one ``Server`` that runs the same batches.

Weights are random from ``--seed``; so is the traffic.  Anywhere but on a
TPU the script exits nonzero before any work.  The last line of its output
is ``{"ok": true, "device": {...}}``; any failed check exits nonzero
without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "mixtral-8x7b"
LAYERS = 4             # one chip: 12.1 GB of weights in 16 GiB of HBM
EP_LAYERS = 8          # ep=4: 22.5 GB of experts, 5.6 GB per chip
N_REQUESTS = 16
PROMPT_LENS = (128, 512)
DECODE_LEN = 32
STREAM_DECODE_LEN = 8  # each streamed decode tick moves all 11.3 GB of
#                        experts; 8 ticks already cover every kind of step
RESIDENT_GB = 1.0      # base + attention only: every expert stack streams
# Engine against reference logits.  Both store bf16 and accumulate in f32,
# but they round at different points (grouped dispatch with the Pallas
# expert kernel and split prefill launches, against the dense-combine
# reference forward).  bf16's unit roundoff is 2^-9; the roundings along
# the residual path walk like a random walk, ~0.5% of an activation's
# size per layer in RMS, so the largest of 64000 logit differences sits
# near 2.5% x sqrt(layers) of the logits' RMS.  The bound is twice that:
# 0.05 x sqrt(layers) x RMS.  A precision below the config's bf16 (fp8,
# unit roundoff 2^-4: 32x larger) would exceed it several times over.
LOGIT_TOL_PER_SQRT_LAYER = 0.05


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}")


class CompileClock:
    """Seconds JAX spends in XLA compilation (persistent-cache reads
    included) and persistent-cache hits, from its monitoring events."""

    def __init__(self, jax) -> None:
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def __str__(self) -> str:
        return (f"{self.seconds:.1f}s compiling "
                f"({self.cache_hits} persistent-cache hits)")


def dropless(plan):
    """The plan with decode capacity b_e at the whole batch.  The search
    sizes b_e at the balanced per-expert load, which drops routed copies
    under any imbalance (ROADMAP B3); a drop couples a request's tokens to
    its batch-mates, and these checks compare requests across batches."""
    from dataclasses import replace

    return replace(plan, b_e=N_REQUESTS)


def make_requests(cfg, seed: int, decode_len: int):
    import numpy as np

    from repro.serving.server import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=int(n),
                                    dtype=np.int32),
                decode_len=decode_len)
        for n in lens
    ]


def serve(cfg, params, plan, requests, serve_cfg, stream_cfg=None):
    """Drain ``requests`` through one ``Server``; returns (server, report,
    tokens per request, wall seconds)."""
    from repro.serving.server import Server, StreamConfig

    server = Server(cfg, params, plan, serve_cfg, stream_cfg or StreamConfig())
    for r in requests:
        server.submit(r)
    t0 = time.perf_counter()
    rep = server.run()
    secs = time.perf_counter() - t0
    return server, rep, [rr.tokens.tolist() for rr in rep.request_results], secs


def engine_logits(server, requests, n: int):
    """The engine's prefill last-token logits for the first ``n`` requests
    (rows 0..n-1 of the served engine; serving is over, so their cache rows
    are free to overwrite)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.server import pad_requests

    toks, lens = pad_requests(requests[:n], 0)
    lg = server._engine.prefill_slots(jnp.asarray(toks), np.arange(n),
                                      lengths=lens)
    return np.asarray(lg, np.float32), toks, lens


def reference_logits(cfg, params, toks, lens):
    """``models.model.forward`` last-token logits on the same weights —
    the plain path: no engine, no grouped dispatch, no kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    fwd = jax.jit(
        lambda p, t, n: M.forward(cfg, p, t, logits_mode="last",
                                  lengths=n)[0][:, 0],
    )
    lg = fwd(params, jnp.asarray(toks), jnp.asarray(lens))
    return np.asarray(lg, np.float32)


def compare_logits(got, want, layers: int, what: str) -> None:
    import numpy as np

    diff = float(np.max(np.abs(got - want)))
    rms = float(np.sqrt(np.mean(want ** 2)))
    tol = LOGIT_TOL_PER_SQRT_LAYER * layers ** 0.5 * rms
    print(f"  {what}: max |engine - reference| = {diff:.5f}, "
          f"reference RMS {rms:.4f}, tolerance {tol:.5f}")
    check(bool(np.all(np.isfinite(got))), f"{what}: engine logits finite")
    check(diff <= tol, f"{what}: within tolerance")


def moe_stage_has_kernel(server, params, batch: int) -> bool:
    """Compile the decode MoE stage at the shapes the engine served
    (``_grouped_expert_math``: the per-layer launch of streamed decode, and
    what the fused chunk inlines) and look for the Pallas kernel's custom
    call."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import registry

    eng = server._engine
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                     params["layers"][0])
    x = jax.ShapeDtypeStruct((batch, eng.cfg.d_model), jnp.bfloat16)
    fn = registry.get("engine.grouped_expert").fn
    text = fn.lower(eng.cfg, p, x, eng._expert_capacity(batch)) \
        .compile().as_text()
    return "tpu_custom_call" in text


def device_bytes(dev, key: str = "bytes_in_use") -> int:
    return (dev.memory_stats() or {}).get(key, 0)


def bytes_in_use(dev) -> str:
    return (f"{dev.id}: {device_bytes(dev) / 1e9:.2f} GB in use, peak "
            f"{device_bytes(dev, 'peak_bytes_in_use') / 1e9:.2f} GB")


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------
def one_chip(seed: int, hw, clock: CompileClock) -> None:
    import jax

    from repro.core import workload as W
    from repro.launch.serve import describe_cut, executing_config, \
        plan_serving
    from repro.models import model as M
    from repro.serving.server import ServeConfig, StreamConfig

    dev = jax.devices()[0]
    cfg = executing_config(ARCH, LAYERS)
    model_bytes = W.model_bytes(cfg)
    print(f"model: {describe_cut(cfg, ARCH)} (depth cut; every width as "
          f"published)")
    t0, c0 = time.perf_counter(), clock.seconds
    params = M.init_params_host(cfg, jax.random.PRNGKey(seed))
    print(f"weights: drawn on the chip layer by layer into host memory in "
          f"{time.perf_counter() - t0:.1f}s ({clock.seconds - c0:.1f}s of it "
          f"compiling)")
    requests = make_requests(cfg, seed + 1, DECODE_LEN)
    max_seq = PROMPT_LENS[1] + DECODE_LEN
    plan = dropless(plan_serving(cfg, hw, N_REQUESTS, max_seq,
                                 DECODE_LEN)[0])
    print(f"plan ({hw.name}): {plan.describe()}; served with "
          f"omega={plan.omega}")
    serve_cfg = ServeConfig(scheduler="static", decode_len=DECODE_LEN,
                            max_seq=max_seq)

    print(f"[1/2] streamed: resident budget {RESIDENT_GB} GB, "
          f"{STREAM_DECODE_LEN} decode tokens per request")
    short = [type(r)(r.prompt, STREAM_DECODE_LEN) for r in requests]
    server, rep, streamed, secs = serve(
        cfg, params, plan, short, serve_cfg,
        StreamConfig(stream_weights=True, resident_bytes=RESIDENT_GB * 1e9,
                     predict_topk=0),
    )
    print(f"  residency: {server._store.describe()}")
    print(f"  served {len(streamed)} requests in {secs:.1f}s, "
          f"{rep.htod_gb:.1f} GB host->device, prefetch stall "
          f"{rep.prefetch_wait_s:.1f}s")
    peak = device_bytes(dev, "peak_bytes_in_use")
    print(f"  device peak {peak / 1e9:.2f} GB against {model_bytes / 1e9:.2f}"
          f" GB of weights")
    check(peak < model_bytes, "streamed: the chip never held the whole model")
    check(not server._engine.fused_eligible(),
          "streamed: per-layer decode path (weights stream)")
    check(moe_stage_has_kernel(server, params, len(short)),
          "streamed: the served MoE stage holds tpu_custom_call")
    del server
    gc.collect()

    print(f"[2/2] resident: {N_REQUESTS} requests, prompts "
          f"{PROMPT_LENS[0]}-{PROMPT_LENS[1]}, {DECODE_LEN} decode tokens")
    server, rep, resident, secs = serve(cfg, params, plan, requests,
                                        serve_cfg)
    n_tok = sum(len(t) for t in resident)
    print(f"  served {len(resident)} requests, {n_tok} tokens in {secs:.1f}s;"
          f" decode {rep.decode_throughput:.1f} tok/s (information only)")
    print(f"  {bytes_in_use(dev)}")
    check(server._engine.fused_eligible(), "resident: fused decode chunks")
    check(server._engine.stats.fused_dispatches > 0,
          "resident: decode ran through fused launches")
    check(moe_stage_has_kernel(server, params, N_REQUESTS),
          "resident: the served MoE stage holds tpu_custom_call")
    same = all(s == r[:STREAM_DECODE_LEN] for s, r in zip(streamed, resident))
    check(same, f"streamed tokens == resident tokens "
                f"({len(streamed)} x {STREAM_DECODE_LEN})")
    got, toks, lens = engine_logits(server, requests, 2)
    del server
    gc.collect()
    want = reference_logits(cfg, jax.device_put(params), toks, lens)
    compare_logits(got, want, LAYERS, f"prefill logits (2 prompts, lengths "
                              f"{[int(n) for n in lens]})")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def four_chips(seed: int, hw, clock: CompileClock) -> None:
    from dataclasses import replace

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import workload as W
    from repro.distributed import ReplicaServer
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import describe_cut, executing_config, \
        plan_serving
    from repro.models import model as M
    from repro.serving.server import ServeConfig
    from repro.sharding.specs import ShardCtx

    devs = jax.devices()[:4]
    cfg8 = executing_config(ARCH, EP_LAYERS)
    print(f"model: {describe_cut(cfg8, ARCH)}")
    t0, c0 = time.perf_counter(), clock.seconds
    params8 = M.init_params_host(cfg8, jax.random.PRNGKey(seed))
    print(f"weights: drawn layer by layer into host memory in "
          f"{time.perf_counter() - t0:.1f}s ({clock.seconds - c0:.1f}s of it "
          f"compiling)")
    max_seq = PROMPT_LENS[1] + DECODE_LEN

    print(f"[1/2] ep=4: {EP_LAYERS} layers, expert stacks sharded over 4 "
          f"chips")
    mesh = make_debug_mesh(1, 4, devs)
    sctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                    moe_dispatch="a2a")
    requests = make_requests(cfg8, seed + 1, DECODE_LEN)
    plan = dropless(plan_serving(cfg8, hw, N_REQUESTS, max_seq, DECODE_LEN,
                                 mesh_shape=(1, 4))[0])
    print(f"  plan ({hw.name}): {plan.describe()}")
    server, rep, _, secs = serve(
        cfg8, params8, plan, requests,
        ServeConfig(scheduler="static", decode_len=DECODE_LEN,
                    max_seq=max_seq, sctx=sctx, ep_chunks=plan.ep_chunks,
                    device=devs[0]),
    )
    print(f"  served {len(rep.request_results)} requests in {secs:.1f}s; "
          f"a2a {rep.a2a_gb:.3f} GB over {rep.collective_dispatches} "
          f"collective launches; decode {rep.decode_throughput:.1f} tok/s "
          f"(information only)")
    peaks = [device_bytes(d, "peak_bytes_in_use") for d in devs]
    for d in devs:
        print(f"  chip {bytes_in_use(d)}")
    check(rep.collective_dispatches > 0 and rep.a2a_bytes > 0,
          "ep=4: MoE stages ran as all-to-all dispatch")
    check(max(peaks) < W.model_bytes(cfg8),
          f"ep=4: no chip held the whole model "
          f"({W.model_bytes(cfg8) / 1e9:.1f} GB)")
    got, toks, lens = engine_logits(server, requests, 2)
    del server
    gc.collect()
    # the plain forward over the same mesh: expert stacks sharded over its
    # model axis as the engine holds them, the rest replicated, XLA
    # partitioning the dense-combine MoE by expert
    experts = NamedSharding(mesh, P(None, "model", None, None))
    placed = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.device_put(
            a, experts if "experts" in jax.tree_util.keystr(path)
            else NamedSharding(mesh, P())),
        params8)
    want = reference_logits(cfg8, placed, toks, lens)
    del placed
    compare_logits(got, want, EP_LAYERS, "ep=4 prefill logits against the "
                                         "reference forward on the mesh")

    print(f"[2/2] dp=4: four one-chip replicas at {LAYERS} layers against "
          f"one Server")
    cfg4 = executing_config(ARCH, LAYERS)
    params4 = dict(params8, layers=jax.tree.map(lambda a: a[:LAYERS],
                                                params8["layers"]))
    requests = make_requests(cfg4, seed + 2, DECODE_LEN)
    plan = dropless(plan_serving(cfg4, hw, N_REQUESTS, max_seq,
                                 DECODE_LEN)[0])
    # Prefill on the chip is not batch-invariant: a request's logits move
    # by up to 0.03 with its batch-mates and padded length (PERF.md), and
    # greedy decode turns that into different tokens at near-ties.  So the
    # one Server runs the replicas' batches: waves of 4 in the order
    # round-robin routing deals them out (replica r gets r, r+4, ...).
    per = N_REQUESTS // 4
    order = [k for r in range(4) for k in range(r, N_REQUESTS, 4)]
    serve_cfg = ServeConfig(scheduler="static", decode_len=DECODE_LEN,
                            max_seq=max_seq)
    one, _, toks, secs = serve(cfg4, params4, plan,
                               [requests[k] for k in order],
                               replace(serve_cfg, max_batch=per))
    want_toks = [None] * N_REQUESTS
    for k, t in zip(order, toks):
        want_toks[k] = t
    print(f"  one Server: {len(toks)} requests in waves of {per} in "
          f"{secs:.1f}s")
    del one
    gc.collect()
    rs = ReplicaServer(cfg4, params4, 4, plan=plan, serve=serve_cfg,
                       policy="round-robin")
    for r in requests:
        rs.submit(r)
    t0 = time.perf_counter()
    rrep = rs.run()
    secs = time.perf_counter() - t0
    got_toks = [rr.tokens.tolist() for rr in rrep.merged.request_results]
    print(f"  4 replicas: {len(got_toks)} requests in {secs:.1f}s")
    homes = []
    for i, s in enumerate(rs.servers):
        leaves = jax.tree.leaves(s._store.base) + [
            a for res in s._store._resident for m in res.values()
            for a in jax.tree.leaves(m)]
        homes.append({d.id for a in leaves for d in a.devices()})
        print(f"  replica {i}: {len(rrep.per_replica[i].request_results)} "
              f"requests, weights on chips {sorted(homes[-1])}; chip "
              f"{bytes_in_use(devs[i])}")
    in_use = [device_bytes(d) for d in devs]
    check(homes == [{d.id} for d in devs],
          "dp=4: each replica's weights sit on its own chip")
    check(min(in_use) > 0.9 * W.model_bytes(cfg4),
          "dp=4: every chip holds one replica's weights")
    check(got_toks == want_toks, "dp=4: replicas drain token-identical to "
                                 "one Server")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the expert- and "
                         "data-parallel phases, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args()
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import resolve_profile
    except ImportError as e:
        print(f"chip_smoke: cannot import JAX and the repro package from "
              f"{HERE}/src: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{devs[0].platform} device(s); this check runs only on a TPU",
              file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips; {len(devs)} visible", file=sys.stderr)
        return 3
    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    host_gb = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES") / 2**30
    print(f"device: platform {devs[0].platform}, kind {devs[0].device_kind!r},"
          f" count {len(devs)}; host RAM {host_gb:.0f} GiB; compile cache "
          f"{cache_dir}")
    hw = resolve_profile()
    print(f"hardware profile: {hw.name}")
    t0 = time.perf_counter()
    try:
        (one_chip if args.chips == 1 else four_chips)(args.seed, hw, clock)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.1f}s, of which {clock}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
