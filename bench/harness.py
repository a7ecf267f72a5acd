"""One run of one cell: set-up, the measured window, the check, the line.

The program under test is driven only through its serving entry points
(``Server.submit`` and ``Server.step``), with the plan its planner makes
for the cell's batch and sequence length.  Two plan knobs are the
cell's: decode capacity ``b_e`` is the whole batch, so no routed copy is
dropped and each request's tokens are its own (the planner's ``b_e``
drops copies; ROADMAP B3), and the prefill micro-batch is the one the
configuration states, so prefill fits beside its cache.

Set-up draws the weights, builds the server, submits every request,
warms up the shapes the traffic will produce (``warm_up``) and runs the
first step (the first wave's prefill and first decode).  The window then
steps the server for ``seconds`` and ends with the step that crosses it;
its rate is taken over whole steps.  After the window, the device's peak
memory is read, the program's state is freed, and the reference judges a
sample of the served requests: the check is the mean over the judged
requests of the share of each one's served tokens that lie more than TAU
logits below the reference's first choice.

Whatever knows the architecture (its shapes, weights, reference and
work counts) comes from the cell's model module, ``cell.model``
(``bench/manifest.py``); nothing here names an architecture.
"""
from __future__ import annotations

import contextlib
import gc
import os
import time
from dataclasses import replace
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic
from bench.compile_clock import CompileClock
from bench.peaks import peaks

# The Pallas expert kernel's name in the device trace.
KERNELS = ("expert_ffn",)
SAMPLE_REQUESTS = 32       # requests the reference judges per run
MIN_REQUESTS = 8           # fewer judged requests than this is no check
MIN_JUDGED = 64            # ... nor fewer judged tokens than this
TAU = 0.1                  # a served token this many logits below the
                           # reference's best is off
TAUS = (0.0, 0.03, 0.1, 0.3)   # the readings kept beside the check's


def program_config(model, dims, config: Dict):
    """The program's own ModelConfig for a configuration file: its
    registry architecture with the file's overrides, checked by the model
    module against the published widths the reference reads."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = replace(get_config(prog["arch"]), **prog.get("overrides", {}))
    model.check_program(cfg, dims, config)
    return cfg


def static_chunks(outputs: List[int], cap: int) -> List[int]:
    """Fused decode chunk lengths a static wave with these output lengths
    steps through: the first token comes from prefill, and each chunk
    runs to the nearest finish, at most ``cap`` ticks."""
    rem = [o - 1 for o in outputs if o > 1]
    ts = []
    while rem:
        t = min(cap, min(rem))
        ts.append(t)
        rem = [r - t for r in rem if r > t]
    return ts


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def warm_up(server, waves: List[List[traffic.Req]], clock) -> Dict:
    """Make every program the window can meet ready, on the server's own
    engine, before any request is admitted (so nothing it will serve
    changes: every cache row is still empty, and prefill overwrites the
    rows it admits):

    * the eviction of a whole wave's rows, which closing a wave runs;
    * each fused decode chunk length the static waves step through, run
      with every row dead;
    * each prefill expert capacity a wave can need: the powers of two from
      the balanced load to the micro-batch's token count.

    The first wave's prefill and first decode step come after, in set-up,
    and make the remaining programs ready.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import engine as E

    eng = server._engine
    before = clock.programs
    eng.evict_slots(list(range(eng._batch)))
    cap = server.serve.decode_chunk or server.plan.decode_chunk
    chunks = sorted({t for w in waves
                     for t in static_chunks([r.output_len for r in w], cap)})
    if eng.fused_eligible():
        dead = np.zeros(eng._batch, bool)
        for t in chunks:
            out = eng.decode_chunk(jnp.asarray(server._cur),
                                   jnp.asarray(server._pos),
                                   server._sampler, t, live=dead)
            jax.block_until_ready(out)
    cfg = eng.cfg
    b_a = server.plan.b_a
    lens = sorted({max(len(r.prompt) for r in w) for w in waves})
    moe = eng.store.acquire(0)["moe"] if eng.store.fully_resident else None
    caps = []
    if moe is not None:
        for S in lens:
            n = min(b_a, server._b) * S
            lo = pow2(-(-n * cfg.experts_per_token // cfg.num_experts))
            c = lo
            while c <= pow2(n):
                x = jnp.zeros((min(b_a, server._b), S, cfg.d_model),
                              jnp.bfloat16)
                xt = x.reshape(-1, cfg.d_model)
                gates = jnp.zeros((n, cfg.experts_per_token), jnp.float32)
                idx = jnp.zeros((n, cfg.experts_per_token), jnp.int32)
                jax.block_until_ready(
                    E._prefill_moe_ffn_module(cfg, c, moe, x, xt, gates,
                                              idx))
                caps.append((S, c))
                c *= 2
    return {"chunks": chunks, "prefill_caps": caps,
            "programs": clock.programs - before}


def pick_sample(handles, served: Dict[int, List[int]], seed: int):
    """Requests the reference judges: the finished one with the most
    served tokens (a live one where none finished), then others drawn
    from the seed, finished ones before live ones, SAMPLE_REQUESTS in
    all.  Every judged request weighs the same in the check, so a fault
    in some of the batch's slots shows in proportion to them."""
    rng = np.random.default_rng([int(seed), 2])
    done = [h.index for h in handles if h.finished and served[h.index]]
    live = [h.index for h in handles if not h.finished and served[h.index]]
    if not done + live:
        return []
    first = max(done or live, key=lambda i: (len(served[i]), i))
    rest = [int(i) for part in (done, live) for i in rng.permutation(part)
            if i != first]
    return ([first] + rest)[:SAMPLE_REQUESTS]


def span(tracing: bool, name: str):
    """A host span ``bench.<name>`` on the profiler's clock when tracing."""
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def instrument(server) -> None:
    """Wrap the calls the scheduler makes into each layer below it in
    host spans, on this server's objects only."""

    def wrap(obj, attr, label):
        fn = getattr(obj, attr, None)
        if fn is None:
            return

        def inner(*a, **k):
            with span(True, label):
                return fn(*a, **k)
        setattr(obj, attr, inner)

    eng = server._engine
    wrap(server, "_prefill_wave", "scheduler.prefill_wave")
    wrap(server, "_decode_tick", "scheduler.decode_tick")
    wrap(eng, "prefill_slots", "engine.prefill")
    wrap(eng, "decode_chunk", "engine.decode_chunk")
    wrap(eng.store, "acquire", "weights.acquire")
    wrap(eng.store, "prefetch", "weights.prefetch")
    wrap(server._sampler, "sample", "sampler.sample")


def counters(server) -> Dict:
    rep = server.report
    return {"slot_steps": rep.decode_slot_steps,
            "wasted_slot_steps": rep.wasted_slot_steps,
            "htod_bytes": rep.weight_htod_bytes}


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             root: str, fault: Optional[Callable] = None,
             control: bool = False) -> Dict:
    """One run of ``cell``; returns the result line's object.  ``fault``
    (``bench/faults.py``) breaks the timed path after set-up; ``control``
    puts the control in the program's place for the check: the gaps of the
    float8 reference's choices at the same prompts and tokens go through
    the same decision (``bench/control.py``; the benchmark's own runs
    never do either)."""
    import jax

    from repro.core.hardware import PROFILES, profile_for_device
    from repro.launch.serve import plan_serving
    from repro.serving.server import Request, ServeConfig, Server, \
        StreamConfig

    clock = CompileClock(jax)
    dev = jax.devices()[0]
    tpu = dev.platform == "tpu"
    model = cell.model
    dims = model.dims(cell.config)
    cfg = program_config(model, dims, cell.config)
    serving, mix = cell.config["serving"], cell.traffic
    B = int(serving["batch"])
    reqs = traffic.generate(mix, seed, dims.vocab, B)
    waves = [reqs[i:i + B] for i in range(0, len(reqs), B)]
    offload = serving["residency"] == "offload"
    marks = {"start": time.perf_counter() - t0}
    params = (model.host_params if offload else model.resident_params)(
        seed, dims)
    marks["weights"] = time.perf_counter() - t0
    hw = profile_for_device(dev) if tpu else PROFILES["tpu-v5e"]
    median_out = int(np.median([r.output_len for r in waves[0]]))
    plan, _ = plan_serving(cfg, hw, B, int(mix["max_seq"]), median_out,
                           mix["scheduler"])
    plan = replace(plan, b_e=B,
                   b_a=int(serving.get("prefill_microbatch") or plan.b_a))
    server = Server(
        cfg, params, plan,
        ServeConfig(scheduler=mix["scheduler"], max_seq=int(mix["max_seq"]),
                    max_batch=B),
        StreamConfig(stream_weights=offload,
                     resident_bytes=(float(serving["resident_gb"]) * 1e9
                                     if offload else None)),
    )
    del params
    handles = [server.submit(Request(prompt=r.prompt, decode_len=r.output_len))
               for r in reqs]
    server._ensure_engine()
    marks["engine"] = time.perf_counter() - t0
    warm = warm_up(server, waves, clock)
    marks["warm_up"] = time.perf_counter() - t0
    server.step()
    marks["first_step"] = time.perf_counter() - t0
    compile_setup = clock.seconds
    if trace:
        instrument(server)
    if fault is not None:
        fault(server)
    gc.collect()

    # -- the window -------------------------------------------------------
    before = counters(server)
    tok0 = {h.index: len(h.tokens) for h in handles}
    programs0 = clock.programs
    cap = None
    if trace:
        from bench.trace import Capture

        cap = Capture(os.path.join(root, ".bench_trace"), KERNELS)
        cap.__enter__()
    t_start = time.perf_counter()
    setup_s = t_start - t0
    step_ends = []
    with span(trace, "window"):
        while time.perf_counter() - t_start < seconds:
            with span(trace, "step"):
                more = server.step()
            step_ends.append(time.perf_counter() - t_start)
            if not more:
                break
    window_s = time.perf_counter() - t_start
    if cap is not None:
        cap.__exit__(None, None, None)
    server.finalize()
    after = counters(server)
    in_window = clock.programs - programs0
    served = {h.index: list(h.tokens) for h in handles}
    tokens = sum(len(served[i]) - tok0[i] for i in served)
    attempted = sum(1 for i in served if len(served[i]) > tok0[i])
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    # -- what the window did, for the per-layer readers --------------------
    ctx = {
        "dims": dims, "seconds": window_s, "chips": cell.chips,
        "batch": B, "capacity": min(plan.b_e, B),
        "experts": dims.experts, "top_k": dims.top_k,
        "delta": {k: after[k] - before[k] for k in before},
        "trace": cap.result if cap is not None else None,
        "kernel": KERNELS[0], "expert_ffn_work": model.expert_ffn_work,
        "peaks": peaks(dev.device_kind) if tpu else None,
        "work_flops": window_flops(model, dims, handles, served, tok0),
    }

    # -- the check, on the program's state freed ---------------------------
    sample = pick_sample(handles, served, seed)
    judged = [(np.asarray(handles[i].prompt, np.int32), served[i])
              for i in sample]
    limit = float(cell.config["limits"]["mismatch_share"])
    del server, handles
    gc.collect()
    t_ref = time.perf_counter()
    gaps, ctl = (model.judge(dims, seed, judged, int(mix["max_seq"]),
                             control) if judged else ([], []))
    readings = {"program": gap_stats(gaps)}
    if control:
        readings["control"] = gap_stats(ctl)
    correct, checks = check(ctl if control else gaps, limit)
    marks["reference"] = time.perf_counter() - t_ref

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics[m["name"]] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] == "gen_tok_s":
                metrics[m["name"]] = {"value": tokens / window_s,
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if cap is not None and cap.result is not None:
        device["busy_s"] = cap.result.busy_s
        device["window_s"] = cap.result.window_s
        out["breakdown"] = cap.result.breakdown()
    out["info"] = {"steps": len(step_ends), "tokens": tokens,
                   "window_s": window_s, "setup_s": setup_s, "marks": marks,
                   "step_ends": [round(t, 3) for t in step_ends],
                   "programs_in_window": in_window, "warm_up": warm,
                   "compile_s_setup": compile_setup,
                   "compile_s": clock.seconds, "sample": sample,
                   "plan": str(plan), "gaps": readings}
    out["checks"] = checks
    return out


def mismatch_share(gaps: List[np.ndarray], tau: float = TAU) -> float:
    """The mean over the judged requests of the share of each one's
    served tokens whose gap exceeds ``tau``; 1 where nothing was judged."""
    shares = [float((g > tau).mean()) for g in gaps if g.size]
    return float(np.mean(shares)) if shares else 1.0


def check(gaps: List[np.ndarray], limit: float):
    """``correct`` and the numbers it was decided from, each beside its
    limit: enough requests and tokens judged, and ``mismatch_share``
    within the configuration's limit."""
    n = sum(int(g.size) for g in gaps)
    share = mismatch_share(gaps)
    checks = {"mismatch_share": {"value": share, "limit": limit},
              "judged_requests": {"value": len(gaps),
                                  "limit": MIN_REQUESTS},
              "judged_tokens": {"value": n, "limit": MIN_JUDGED}}
    ok = len(gaps) >= MIN_REQUESTS and n >= MIN_JUDGED and share <= limit
    return bool(ok), checks


def gap_stats(gaps: List[np.ndarray]) -> Dict:
    """How the judged gaps spread: requests and tokens judged, the
    check's share at each of TAUS, the widest gap."""
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    if not flat.size:
        return {}
    return {"requests": len(gaps), "tokens": int(flat.size),
            "share": {str(t): mismatch_share(gaps, t) for t in TAUS},
            "max": float(flat.max())}


def window_flops(model, dims, handles, served, tok0) -> int:
    """Model FLOPs of the work the window completed, by ``model``'s counts:
    the unpadded prompts it prefilled and every token it generated by
    decode, each at the context it attended."""
    total = 0
    for h in handles:
        n_prompt = len(h.prompt)
        a, b = tok0[h.index], len(served[h.index])
        if a == b:
            continue
        if a == 0:                 # admitted inside the window
            total += model.prefill_flops(dims, n_prompt)
            a = 1
        for i in range(a, b):      # token i was fed token i-1's position
            total += model.decode_flops(dims, n_prompt + i - 1)
    return total
