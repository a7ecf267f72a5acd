"""Serving launcher: plan with the paper's search, then serve through ``Server``.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --requests 16 --prompt-len 32 --decode-len 16 --stream-weights

Without ``--layers`` the registry's smoke preset runs (CPU-sized).  With
``--layers N`` the registry config runs at its published widths, cut to
its first N layers (a whole number of layer-pattern periods) — the size a
TPU chip serves:

    python -m repro.launch.serve --arch mixtral-8x7b --layers 4 \
        --requests 16 --prompt-len 256 --decode-len 32 --batch 16

On a TPU the hardware profile comes from the device's ``device_kind``
(``core.hardware.DEVICE_KIND_PROFILES``); elsewhere it defaults to the
paper's A5000 testbed.
"""
from __future__ import annotations

import argparse
import contextlib
from dataclasses import replace
from typing import Optional, Tuple

import jax

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import planner, workload as W
from repro.core.dag_builder import Plan
from repro.core.hardware import PROFILES, HardwareProfile, profile_for_device
from repro.data.datasets import DatasetSpec, synthetic_requests
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import arrivals
from repro.serving.sampling import SamplingParams
from repro.serving.server import ServeConfig, Server, StreamConfig
from repro.serving.weights import ParamStore


def executing_config(arch: str, layers: Optional[int] = None) -> ModelConfig:
    """The config that runs: the smoke preset, or — with ``layers`` — the
    registry config at its published widths with only its depth cut."""
    if layers is None:
        return get_config(arch, smoke=True)
    full = get_config(arch)
    cfg = replace(full, num_layers=int(layers))
    period = len(M.layer_pattern(full))
    k = full.first_k_dense
    if not k < cfg.num_layers <= full.num_layers \
            or (cfg.num_layers - k) % period:
        raise ValueError(
            f"--layers {layers}: {arch} has {full.num_layers} layers, "
            f"{k} leading dense then periods of {period}; pick {k} plus a "
            f"multiple of {period}")
    return cfg


def describe_cut(cfg: ModelConfig, arch: str) -> str:
    full = get_config(arch)
    return (f"{cfg.name}: {cfg.num_layers} of {full.num_layers} layers; "
            f"d_model {cfg.d_model}, {cfg.num_heads} heads / "
            f"{cfg.num_kv_heads} kv heads x {cfg.head_dim}, "
            f"{cfg.num_experts} experts top-{cfg.experts_per_token} "
            f"d_ff {cfg.moe_d_ff or cfg.d_ff}, vocab {cfg.vocab_size}; "
            f"{W.model_bytes(cfg) / 1e9:.2f} GB of weights")


def resolve_profile(name: Optional[str] = None) -> HardwareProfile:
    """``name`` if given; on a TPU the profile of its ``device_kind`` (an
    unknown kind is an error); elsewhere the paper's C2 A5000 testbed."""
    if name is not None:
        return PROFILES[name]
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return profile_for_device(dev)
    return PROFILES["C2-A5000-512GB"]


def plan_serving(
    cfg: ModelConfig, hw: HardwareProfile, batch: int, ctx: int,
    decode_len: int, scheduler: str = "static",
    mesh_shape: Optional[Tuple[int, int]] = None,
    ep_chunks: Optional[int] = None,
) -> Tuple[Plan, "planner.SearchResult"]:
    """The paper's search on the config that executes, at the batch it
    serves (``batch`` caps the accumulated batch B).

    The search's ω is pinned to 0: the engine attends every row on its
    device and refuses a plan with ω > 0, since the host-CPU attention the
    cost model would credit ω with is not built (ROADMAP A8).  A mesh serves
    fully resident, so it plans no predictive streaming."""
    res = planner.search_decode(
        cfg, hw, ctx=ctx, B=batch, use_cpu_attention=False,
        decode_len=decode_len, scheduler=scheduler, mesh_shape=mesh_shape,
    )
    plan = replace(
        res.plan, B=batch, b_a=max(1, min(res.plan.b_a, batch)),
        predict_topk=0 if mesh_shape else res.plan.predict_topk,
        ep_chunks=ep_chunks or res.plan.ep_chunks,
    )
    plan = replace(plan, decode_chunk=planner.select_decode_chunk(
        plan, decode_len, scheduler=scheduler,
    ))
    return plan, res


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--layers", type=int, default=None,
                    help="run the registry config at its published widths "
                         "cut to this many layers (default: the smoke "
                         "preset)")
    ap.add_argument("--profile", default=None, choices=PROFILES,
                    help="hardware profile the planner plans for (default: "
                         "the TPU's own by device_kind, else "
                         "C2-A5000-512GB)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8,
                    help="accumulated batch B the engine allocates")
    ap.add_argument("--scheduler", default="static",
                    choices=("static", "continuous"),
                    help="static accumulated batches vs continuous in-flight "
                         "batching (finished slots recycled mid-batch)")
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated prompt lengths cycled over "
                         "requests (ragged workload), e.g. 16,32,24")
    ap.add_argument("--decode-lens", default=None,
                    help="comma-separated per-request decode lengths cycled "
                         "over requests, e.g. 8,32,128")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id that finishes a sequence early")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop Poisson arrival rate (requests/s); "
                         "default is the closed-loop drain (all due at t=0)")
    ap.add_argument("--arrival-trace", default=None,
                    help="comma-separated arrival offsets in seconds, e.g. "
                         "0,0.5,1.2 (overrides --arrival-rate)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest logits (0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed (per-request streams are "
                         "deterministic in it)")
    ap.add_argument("--stream-weights", action="store_true",
                    help="execute through the streamed parameter store: "
                         "weights beyond the resident budget stay host-side "
                         "and are double-buffer prefetched per layer")
    ap.add_argument("--resident-gb", type=float, default=None,
                    help="device bytes (GB) of the greedy resident weight "
                         "set; implies --stream-weights (default when "
                         "streaming: the plan's S_Params with --layers, 0 "
                         "for the smoke preset, whose planned S_Params "
                         "would pin everything and stream nothing)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async prefetch (streamed-serial: "
                         "fetch-on-demand, copy serialized with compute)")
    ap.add_argument("--predict-topk", type=int, default=None,
                    help="predictive per-expert streaming: stream only the "
                         "k-hat experts predicted from the previous layer's "
                         "gate tap (plus demand fetches); default follows "
                         "the planned predict_topk; 0 forces whole-stack "
                         "streaming; implies --stream-weights")
    ap.add_argument("--expert-lru-gb", type=float, default=None,
                    help="hot-expert device LRU budget (GB) for predictive "
                         "streaming; default: the residency plan's spare "
                         "bytes")
    ap.add_argument("--kv-page-tokens", type=int, default=0,
                    help="page the KV cache into fixed-size blocks of this "
                         "many tokens (0 = the contiguous cache); pages "
                         "beyond the device pool budget live host-side and "
                         "stream through the prefetch window")
    ap.add_argument("--device-kv-gb", type=float, default=None,
                    help="device page-pool budget (GB); default keeps every "
                         "page frame on device (Mode A, bookkeeping only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cache shared prompt prefixes at page granularity "
                         "and admit hits by page-row copy instead of "
                         "recomputing prefill (requires --kv-page-tokens)")
    ap.add_argument("--mesh", default=None, metavar="DP,EP",
                    help="serve on a dp,ep device mesh: EP shards every "
                         "MoE layer's experts across EP devices (pipelined "
                         "all-to-all dispatch, repro.distributed) and DP "
                         "runs that engine in DP data-parallel Server "
                         "replicas behind one arrival queue, one group "
                         "of EP devices each (replicas share groups when "
                         "fewer than DP*EP devices are visible; CPU: "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8)")
    ap.add_argument("--ep-chunks", type=int, default=None,
                    help="expert-parallel pipeline chunk count (a2a of "
                         "chunk k+1 overlaps expert FFN of chunk k); "
                         "default: the planner's pick for the mesh")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm deterministic fault injection for the run, "
                         "e.g. 'seed=3,transfer=0.2,stall=0.05,oom=0.1,"
                         "preempt=7,kill=1@4' (repro.faults spec grammar); "
                         "recovery is exercised and counted — retried "
                         "transfers, preempt/resume checkpoints, replica "
                         "failover — and the served tokens stay identical "
                         "to the unarmed run")
    ap.add_argument("--sanitize", default="off",
                    choices=("off", "log", "strict"),
                    help="run serving under the analysis sanitizer: decode "
                         "regions execute with jax.transfer_guard (strict "
                         "raises on unplanned transfers, log records them) "
                         "and donation aliasing is verified; prints the "
                         "sanitizer report after serving")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the serving run into "
                         "DIR with the program's moegen.* spans on (open it "
                         "in TensorBoard's profile plugin or Perfetto)")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    hw = resolve_profile(args.profile)
    cfg = executing_config(args.arch, args.layers)
    print(f"executing {describe_cut(cfg, args.arch)}")
    print(f"device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")

    dp = ep = 1
    if args.mesh:
        try:
            dp, ep = (int(x) for x in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh wants DP,EP (got {args.mesh!r})")
        if dp < 1 or ep < 1:
            raise SystemExit(f"--mesh axes must be >= 1 (got {args.mesh!r})")
        if len(jax.devices()) < ep:
            raise SystemExit(
                f"--mesh {args.mesh} needs {ep} devices for the expert-"
                f"parallel axis but only {len(jax.devices())} are visible; "
                "on CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                "count=8 before launch")
        if args.stream_weights or args.resident_gb is not None \
                or args.predict_topk is not None:
            raise SystemExit("--mesh serves fully-resident replicas; it "
                             "composes with neither --stream-weights nor "
                             "predictive streaming")

    spec = DatasetSpec("serve", args.requests, args.prompt_len, args.decode_len)
    parse = lambda s: [int(x) for x in s.split(",")] if s else None
    times = None
    if args.arrival_trace:
        times = arrivals.trace([float(x) for x in args.arrival_trace.split(",")])
    elif args.arrival_rate is not None:
        times = arrivals.poisson(args.requests, args.arrival_rate,
                                 seed=args.seed)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              seed=args.seed)
    requests = synthetic_requests(
        spec, cfg.vocab_size,
        prompt_lens=parse(args.prompt_lens),
        decode_lens=parse(args.decode_lens),
        arrivals=times,
        sampling=sampling if not sampling.is_greedy else None,
    )
    ctx = max(len(r.prompt) + r.decode_len for r in requests)

    # 1. plan the config that executes, with the paper's search
    plan, res = plan_serving(
        cfg, hw, args.batch, ctx, args.decode_len, args.scheduler,
        mesh_shape=(dp, ep) if args.mesh else None,
        ep_chunks=args.ep_chunks,
    )
    print(f"planned ({cfg.name} on {hw.name}): {res.plan.describe()}")
    rp = W.plan_residency(cfg, res.plan.s_params)
    print(f"planned residency: {rp.resident_bytes/1e9:.1f}GB resident "
          f"of {W.model_bytes(cfg)/1e9:.1f}GB model "
          f"({rp.n_streamed()} modules streamed, stream window "
          f"{res.plan.s_expert/1e9:.1f}GB)")
    print(f"predicted decode throughput: {res.estimate.throughput:.0f} tok/s")
    print(f"fused decode chunk T={plan.decode_chunk} "
          f"({args.scheduler} cadence at B={plan.B})")

    # 2. weights on the host, placed by the store the server executes
    params = M.init_params_host(cfg, jax.random.PRNGKey(0))
    # --resident-gb implies streaming; the smoke preset is tiny, so its
    # planned S_Params would pin everything — a streamed smoke run
    # defaults to resident_bytes=0 to actually exercise the stream path
    stream = (args.stream_weights or args.resident_gb is not None
              or args.predict_topk is not None)
    if args.resident_gb is not None:
        resident_bytes = args.resident_gb * 1e9
    else:
        resident_bytes = 0.0 if args.layers is None else plan.s_params
    store = None
    if stream:
        # the ONE store the engine executes through — built here so the
        # realized split can be printed before serving
        khat = (plan.predict_topk if args.predict_topk is None
                else args.predict_topk)
        store = ParamStore(
            cfg, params, resident_bytes=resident_bytes,
            prefetch=not args.no_prefetch,
            predict_topk=khat,
            lru_bytes=(None if args.expert_lru_gb is None
                       else args.expert_lru_gb * 1e9),
        )
        print(f"realized residency: {store.describe()}")
    if args.kv_page_tokens:
        # page-pool residency at the serving shape, printed up front (the
        # table the scheduler's engines will build)
        from repro.serving.cache import CacheConfig, KVPageTable

        probe = KVPageTable(
            cfg,
            [(cfg.layer_kind(i), cfg.ffn_kind(i))
             for i in range(cfg.num_layers)],
            args.batch, ctx,
            CacheConfig(
                page_tokens=args.kv_page_tokens,
                device_pool_bytes=(None if args.device_kv_gb is None
                                   else args.device_kv_gb * 1e9),
            ),
        )
        print(f"page-pool residency: {probe.describe()}")

    from repro import analysis

    sctx = None
    if args.mesh and ep > 1:
        from repro.launch.mesh import make_debug_mesh
        from repro.sharding.specs import ShardCtx

        sctx = ShardCtx(mesh=make_debug_mesh(1, ep), batch_axes=("data",),
                        model_axis="model", moe_dispatch="a2a")
        print(f"mesh: dp={dp} replicas x ep={ep} expert-parallel ranks, "
              f"ep_chunks={plan.ep_chunks}")
    serve_cfg = ServeConfig(
        scheduler=args.scheduler, decode_len=args.decode_len,
        eos_id=args.eos_id,
        hw=hw if args.scheduler == "continuous" else None,
        kv_page_tokens=args.kv_page_tokens, device_kv_gb=args.device_kv_gb,
        prefix_cache=args.prefix_cache, sctx=sctx,
        ep_chunks=plan.ep_chunks, faults=args.faults,
    )

    san_ctx = (analysis.sanitize(strict=args.sanitize == "strict",
                                 donation=True)
               if args.sanitize != "off" else contextlib.nullcontext())
    trace_ctx = contextlib.ExitStack()
    if args.trace_dir:
        trace_ctx.enter_context(jax.profiler.trace(args.trace_dir))
        trace_ctx.enter_context(analysis.tracing())
    per_replica = None
    with san_ctx as san, trace_ctx:
        if dp > 1:
            # data-parallel fan-out: one arrival queue over dp Server
            # replicas, one device group each (shared prefix keys,
            # per-replica KV/engines)
            from repro.distributed import ReplicaServer

            server = ReplicaServer(cfg, params, dp, plan=plan,
                                   serve=serve_cfg)
        else:
            server = Server(cfg, params, plan, serve_cfg, StreamConfig(),
                            store=store)
        for r in requests:
            server.submit(r)
        rep = server.run()
        if dp > 1:
            report, per_replica = rep.merged, rep.per_replica
        else:
            report = rep
    if args.trace_dir:
        print(f"profile with moegen.* spans written under {args.trace_dir}")
    if san is not None:
        rep = san.report()
        planned = ", ".join(f"{k}={v}" for k, v in
                            sorted(rep["planned_transfers"].items())) or "none"
        bad = [d for d in rep["donation_checks"] if not d["ok"]]
        print(f"sanitizer[{rep['mode']}]: planned transfers: {planned}")
        print(f"sanitizer: donation checks "
              f"{len(rep['donation_checks']) - len(bad)}/"
              f"{len(rep['donation_checks'])} ok; "
              f"steady retraces: {sum(rep['steady_retraces'].values())}")
    print(f"served {args.requests} requests in {report.total_s:.2f}s "
          f"({report.decode_throughput:.1f} decode tok/s on this host, "
          f"{report.expert_tokens_dropped} routed copies dropped)")
    print(f"[{report.scheduler}] decode slot-steps: {report.decode_slot_steps} "
          f"(wasted {report.wasted_slot_steps}, "
          f"occupancy {report.occupancy:.0%}); "
          f"mean request latency {report.mean_latency_s:.2f}s")
    if per_replica is not None:
        for i, r in enumerate(per_replica):
            print(f"replica[{i}]: {len(r.request_results)} requests, "
                  f"{r.decode_throughput:.1f} decode tok/s, "
                  f"occupancy {r.occupancy:.0%}, "
                  f"a2a {r.a2a_gb:.4f}GB")
    if report.collective_dispatches:
        print(f"expert-parallel a2a: {report.a2a_gb:.4f}GB exchanged over "
              f"{report.collective_dispatches} collective dispatches "
              f"(ep={ep}, chunks={plan.ep_chunks})")
    print(f"TTFT p50/p95: {report.ttft_percentile(50):.3f}/"
          f"{report.ttft_percentile(95):.3f}s; "
          f"TPOT p50/p95: {report.tpot_percentile(50)*1e3:.1f}/"
          f"{report.tpot_percentile(95)*1e3:.1f}ms; "
          f"mean queue wait {report.mean_queue_wait_s:.3f}s")
    if stream:
        print(f"weight streaming: {report.htod_gb:.3f}GB htod, "
              f"prefetch stall {report.prefetch_wait_s:.3f}s")
    if report.expert_load is not None:
        per_expert = report.expert_load.sum(axis=0)
        hist = "/".join(str(int(c)) for c in per_expert)
        print(f"routing skew: {report.routing_skew:.2f}x balanced "
              f"(per-expert routed copies {hist})")
        drops = "/".join(
            str(int(d)) for d in report.expert_dropped_by_layer
        )
        print(f"per-MoE-layer drops: {drops} "
              f"({report.capacity_replans} online capacity re-plans)")
    if report.expert_pred_hits or report.expert_pred_misses \
            or report.expert_lru_hits:
        print(f"predictive expert streaming: "
              f"pred hit rate {report.pred_hit_rate:.0%} "
              f"({report.expert_pred_hits} staged / "
              f"{report.expert_pred_misses} demand), "
              f"LRU hit rate {report.lru_hit_rate:.0%} "
              f"({report.expert_lru_hits} hits)")
    if args.kv_page_tokens:
        print(f"kv paging: {report.kv_htod_bytes / 1e6:.3f}MB page htod, "
              f"{report.kv_dtoh_bytes / 1e6:.3f}MB dtoh")
        if args.prefix_cache:
            print(f"prefix cache: {report.prefix_hits} hits / "
                  f"{report.prefix_hits + report.prefix_misses} lookups "
                  f"(hit rate {report.prefix_hit_rate:.0%})")
    if report.admission_deferrals:
        print(f"admissions deferred by the Eq. 2 host KV budget: "
              f"{report.admission_deferrals}")
    if args.faults or report.transfer_retries or report.preemptions \
            or report.failovers:
        print(f"fault recovery: {report.transfer_retries} transfer retries, "
              f"{report.transfer_timeouts} watchdog timeouts; "
              f"{report.preemptions} preemptions / {report.resumes} resumes; "
              f"{report.failovers} replica failovers "
              f"({report.requeued_requests} requests requeued)")
        if report.degrade_deferrals or report.page_demotions \
                or report.chunk_shrinks:
            print(f"memory-pressure degradation: "
                  f"{report.degrade_deferrals} admission deferrals, "
                  f"{report.page_demotions} pages demoted to host, "
                  f"{report.chunk_shrinks} decode-chunk shrinks")


if __name__ == "__main__":
    main()
