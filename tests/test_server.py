"""Request-lifecycle serving API: Server facade, sampling, online arrivals.

(The hypothesis property test for mixed greedy/sampled batches lives in
test_properties.py, the only module allowed to import hypothesis.)
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.data.datasets import DatasetSpec, synthetic_requests
from repro.models import model as M
from repro.serving import arrivals
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import serve_dataset
from repro.serving.server import Request, ServeConfig, Server, StreamConfig

KEY = jax.random.PRNGKey(0)


def _mixtral():
    cfg = get_config("mixtral-8x7b", smoke=True)
    return cfg, M.init_params(cfg, KEY)


def test_serving_package_exports_the_serving_api():
    import repro.serving as S

    for name in ("Server", "ServeConfig", "StreamConfig", "SamplingParams",
                 "Request", "RequestHandle", "ServeReport", "RequestResult",
                 "serve_dataset", "arrivals", "pad_requests",
                 "greedy_generate", "cache_from_prefill", "ParamStore"):
        assert hasattr(S, name), name
        assert name in S.__all__, name


def test_server_facade_submit_step_run_and_streaming():
    """The lifecycle surface: submit -> handles, step() drives the batch,
    per-token callbacks and handle.stream() see the same tokens the report
    records, statuses progress queued -> running -> finished."""
    cfg, params = _mixtral()
    reqs = synthetic_requests(DatasetSpec("t", 3, 8, 4), cfg.vocab_size,
                              prompt_lens=[8, 5, 7])
    server = Server(cfg, params, Plan(B=2, b_a=2, b_e=16, omega=0.0),
                    serve=ServeConfig(scheduler="continuous", decode_len=4))
    seen = []
    handles = [server.submit(r, on_token=lambda h, t: seen.append((h.index, t)))
               for r in reqs]
    assert [h.status for h in handles] == ["queued"] * 3
    assert [h.index for h in handles] == [0, 1, 2]
    # manual stepping works and terminates
    steps = 0
    while server.step():
        steps += 1
        assert steps < 100
    report = server.finalize()
    assert all(h.finished for h in handles)
    assert len(report.request_results) == 3
    for h, r in zip(handles, report.request_results):
        assert r.index == h.index
        assert np.array_equal(r.tokens, np.asarray(h.tokens))
        # callbacks fired exactly the recorded stream, in order
        assert [t for i, t in seen if i == h.index] == h.tokens
        assert r.ttft_s >= 0 and r.queue_wait_s >= 0 and r.tpot_s >= 0
    # an exhausted stream replays the recorded tokens without stepping
    assert list(handles[0].stream()) == handles[0].tokens


def test_server_handle_stream_drives_the_server():
    cfg, params = _mixtral()
    server = Server(cfg, params, Plan(B=1, b_a=1, b_e=16, omega=0.0),
                    serve=ServeConfig(decode_len=4))
    h = server.submit(Request(np.arange(6, dtype=np.int32), 4))
    toks = list(h.stream())          # pulls step() until the stream ends
    assert h.finished and len(toks) == 4
    assert toks == h.tokens


def test_server_matches_serve_dataset_wrapper():
    """The wrapper is a thin facade: a Server run with the same config
    serves identical tokens and the same report shape."""
    cfg, params = _mixtral()
    reqs = synthetic_requests(DatasetSpec("t", 5, 10, 4), cfg.vocab_size,
                              prompt_lens=[10, 6], decode_lens=[3, 5])
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    for sched in ("static", "continuous"):
        wrapped = serve_dataset(cfg, params, reqs, plan, 4, scheduler=sched)
        server = Server(cfg, params, plan,
                        serve=ServeConfig(scheduler=sched, decode_len=4))
        for r in reqs:
            server.submit(r)
        direct = server.run()
        assert len(direct.request_results) == len(wrapped.request_results)
        for a, b in zip(wrapped.request_results, direct.request_results):
            assert a.index == b.index
            assert np.array_equal(a.tokens, b.tokens), (sched, a.index)
        assert len(direct.results) == len(wrapped.results)


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------
def test_sampling_deterministic_across_runs_and_schedulers():
    """Same seed + same SamplingParams => identical tokens across runs and
    across the static/continuous schedulers (the per-request key is folded
    with the token index, not slot or global step)."""
    cfg, params = _mixtral()
    sp = SamplingParams(temperature=0.9, top_k=5, seed=7)
    reqs = synthetic_requests(DatasetSpec("s", 5, 9, 5), cfg.vocab_size,
                              prompt_lens=[9, 6, 7], decode_lens=[3, 5],
                              sampling=sp)
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    runs = [serve_dataset(cfg, params, reqs, plan, 5, scheduler=s)
            for s in ("static", "static", "continuous")]
    for rep in runs[1:]:
        for a, b in zip(runs[0].request_results, rep.request_results):
            assert a.index == b.index
            assert np.array_equal(a.tokens, b.tokens), a.index
    # sampled decode really deviates from greedy somewhere
    greedy_rep = serve_dataset(cfg, params, [
        Request(r.prompt, r.decode_len) for r in reqs
    ], plan, 5)
    assert any(
        not np.array_equal(a.tokens, b.tokens)
        for a, b in zip(runs[0].request_results, greedy_rep.request_results)
    )


def test_temperature_zero_is_greedy():
    cfg, params = _mixtral()
    reqs = synthetic_requests(DatasetSpec("g", 3, 8, 4), cfg.vocab_size)
    plan = Plan(B=3, b_a=2, b_e=16, omega=0.0)
    base = serve_dataset(cfg, params, reqs, plan, 4)
    t0 = serve_dataset(cfg, params, [
        Request(r.prompt, r.decode_len,
                sampling=SamplingParams(temperature=0.0, seed=3))
        for r in reqs
    ], plan, 4)
    for a, b in zip(base.request_results, t0.request_results):
        assert np.array_equal(a.tokens, b.tokens), a.index


def test_engine_generate_sampled_is_reproducible():
    """engine.generate(sampling=...) is bit-reproducible and rows sharing
    one seed draw distinct streams (row index folded into the key)."""
    from repro.core.engine import ModuleBatchingEngine

    import jax.numpy as jnp

    cfg = get_config("internlm2-1.8b", smoke=True)
    params = M.init_params(cfg, KEY)
    row = jax.random.randint(KEY, (1, 10), 0, cfg.vocab_size)
    toks = jnp.tile(row, (3, 1))     # identical rows: only the per-row salt
    sp = SamplingParams(temperature=1.0, seed=11)  # can decorrelate them
    outs = []
    for _ in range(2):
        eng = ModuleBatchingEngine(cfg, params,
                                   Plan(B=3, b_a=2, b_e=8, omega=0.0),
                                   max_seq=16)
        outs.append(np.asarray(eng.generate(toks, 5, sampling=sp)))
    assert np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[0][0], outs[0][1])   # decorrelated rows


# ---------------------------------------------------------------------------
# Online arrivals
# ---------------------------------------------------------------------------
def test_arrival_zero_matches_drain():
    """With every arrival_s=0 the online run is request-for-request
    identical to the drain-the-queue offline run, in both schedulers."""
    cfg, params = _mixtral()
    base_reqs = synthetic_requests(DatasetSpec("a", 5, 9, 4), cfg.vocab_size,
                                   prompt_lens=[9, 6], decode_lens=[2, 4, 6])
    online = synthetic_requests(DatasetSpec("a", 5, 9, 4), cfg.vocab_size,
                                prompt_lens=[9, 6], decode_lens=[2, 4, 6],
                                arrivals=np.zeros(5))
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    for sched in ("static", "continuous"):
        drain = serve_dataset(cfg, params, base_reqs, plan, 4, scheduler=sched)
        live = serve_dataset(cfg, params, online, plan, 4, scheduler=sched)
        assert len(drain.request_results) == len(live.request_results)
        for a, b in zip(drain.request_results, live.request_results):
            assert a.index == b.index
            assert np.array_equal(a.tokens, b.tokens), (sched, a.index)
        assert live.decode_slot_steps == drain.decode_slot_steps


def test_staggered_arrivals_gate_admission_and_populate_metrics():
    """A staggered trace: late requests cannot be admitted before their
    arrival (first token lands at/after the offset on the virtual clock),
    and a full batch makes queue-wait nonzero."""
    cfg, params = _mixtral()
    gap = 0.15
    reqs = synthetic_requests(DatasetSpec("a", 3, 8, 6), cfg.vocab_size,
                              arrivals=[0.0, 0.0, gap])
    plan = Plan(B=1, b_a=1, b_e=16, omega=0.0)
    rep = serve_dataset(cfg, params, reqs, plan, 6, scheduler="continuous")
    rr = rep.request_results
    assert len(rr) == 3
    # B=1: request 1 arrives at t=0 but must wait for request 0 to drain
    assert rr[1].queue_wait_s > 0
    # the late request's first token is at/after its arrival offset
    late = rr[2]
    assert late.arrival_s == gap
    assert late.ttft_s >= 0 and late.queue_wait_s >= 0
    assert late.ttft_s + late.arrival_s >= gap        # absolute clock time
    for r in rr:
        assert r.tpot_s > 0


def test_poisson_run_populates_ttft_tpot():
    """ISSUE acceptance: an open-loop Poisson run completes with
    per-request TTFT/TPOT populated in the report."""
    cfg, params = _mixtral()
    times = arrivals.poisson(4, rate=20.0, seed=1)
    assert (np.diff(times) > 0).all()
    reqs = synthetic_requests(DatasetSpec("p", 4, 8, 4), cfg.vocab_size,
                              arrivals=times)
    rep = serve_dataset(cfg, params, reqs,
                        Plan(B=2, b_a=2, b_e=16, omega=0.0), 4,
                        scheduler="continuous")
    assert len(rep.request_results) == 4
    for r in rep.request_results:
        assert np.isfinite(r.ttft_s) and r.ttft_s > 0
        assert np.isfinite(r.tpot_s) and r.tpot_s > 0
        assert r.queue_wait_s >= 0
    assert rep.ttft_percentile(95) >= rep.ttft_percentile(50) > 0
    assert rep.mean_tpot_s > 0


def test_arrivals_module_validation():
    with pytest.raises(ValueError, match="rate"):
        arrivals.poisson(4, rate=0.0)
    with pytest.raises(ValueError, match=">= 0"):
        arrivals.trace([0.0, -1.0])
    with pytest.raises(ValueError, match="entries"):
        arrivals.assign([Request(np.zeros(4, np.int32), 2)] * 3, [0.0, 0.1])
    assert np.allclose(arrivals.uniform(3, 0.5, start=1.0), [1.0, 1.5, 2.0])


def test_submit_rejects_oversized_and_never_fitting_requests():
    """Lifecycle-API twins of the wrapper's upfront ValueErrors."""
    from dataclasses import replace as dc_replace

    from repro.core import workload as W
    from repro.core.hardware import A5000_C2

    cfg, params = _mixtral()
    plan = Plan(B=1, b_a=1, b_e=8, omega=0.0)
    server = Server(cfg, params, plan, serve=ServeConfig(max_seq=16))
    with pytest.raises(ValueError, match="max_seq"):
        server.submit(Request(np.zeros(30, np.int32), 4))
    need = W.kv_bytes_per_seq(cfg, 40)
    hw = dc_replace(A5000_C2, host_mem_bytes=W.model_bytes(cfg) + 0.5 * need)
    gated = Server(cfg, params, plan,
                   serve=ServeConfig(scheduler="continuous", hw=hw))
    with pytest.raises(ValueError, match="Eq. 2"):
        gated.submit(Request(np.zeros(36, np.int32), 4))
    # a NaN arrival would never compare due and spin run() forever
    with pytest.raises(ValueError, match="arrival_s"):
        server.submit(Request(np.zeros(4, np.int32), 2,
                              arrival_s=float("nan")))


def test_chunked_steps_match_per_tick_both_schedulers():
    """Fused multi-token Server.step chunks are request-for-request
    token-identical to per-tick stepping in BOTH scheduler modes, with
    identical slot-step/waste accounting (chunks end exactly at finish
    boundaries, so no scheduling event ever moves)."""
    cfg, params = _mixtral()
    p_lens, d_lens = [9, 12, 5, 7, 11, 6], [6, 14, 3, 9, 22, 5]

    def requests():
        rng = np.random.default_rng(3)
        return [Request(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                        d, sampling=(SamplingParams(temperature=0.7, seed=i)
                                     if i % 2 else None))
                for i, (n, d) in enumerate(zip(p_lens, d_lens))]

    for sched in ("static", "continuous"):
        reports = {}
        for chunk in (1, 8):
            plan = Plan(B=4, b_a=4, b_e=64, omega=0.0, decode_chunk=chunk)
            srv = Server(cfg, params, plan,
                         ServeConfig(scheduler=sched, decode_len=8,
                                     max_seq=40))
            for r in requests():
                srv.submit(r)
            reports[chunk] = srv.run()
        a, b = reports[1], reports[8]
        for x, y in zip(a.request_results, b.request_results):
            assert np.array_equal(x.tokens, y.tokens), (sched, x.index)
        assert a.decode_slot_steps == b.decode_slot_steps, sched
        assert a.wasted_slot_steps == b.wasted_slot_steps, sched


def test_chunking_disabled_with_eos_and_identical_results():
    """An eos_id makes finishes unpredictable: _chunk_T degrades to
    per-tick stepping (no behavior change vs decode_chunk=1)."""
    cfg, params = _mixtral()
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(1, cfg.vocab_size, 6).astype(np.int32), 12)
            for _ in range(3)]
    outs = []
    for chunk in (1, 8):
        plan = Plan(B=3, b_a=3, b_e=64, omega=0.0, decode_chunk=chunk)
        srv = Server(cfg, params, plan,
                     ServeConfig(decode_len=12, eos_id=0, max_seq=24))
        for r in reqs:
            srv.submit(Request(r.prompt.copy(), r.decode_len))
        outs.append(srv.run())
    for x, y in zip(outs[0].request_results, outs[1].request_results):
        assert np.array_equal(x.tokens, y.tokens)


def test_chunked_steps_match_per_tick_under_capacity_drops():
    """Free slots + a capacity-starved plan (b_e=1 forces routed drops that
    couple rows through the grouped dispatch): chunked stepping must still
    match per-tick, because dead rows hold their stale token/position
    inside the chunk exactly like per-tick stepping holds a free slot."""
    cfg, params = _mixtral()
    rng = np.random.default_rng(5)
    # the shortest request finishes early and frees its slot with an empty
    # queue, so later chunks decode with a dead row in the batch
    reqs = [Request(rng.integers(0, cfg.vocab_size, n).astype(np.int32), d)
            for n, d in zip([8, 6, 10], [3, 9, 6])]
    results = {}
    for chunk in (1, 4):
        plan = Plan(B=4, b_a=4, b_e=1, omega=0.0, decode_chunk=chunk)
        srv = Server(cfg, params, plan,
                     ServeConfig(scheduler="continuous", decode_len=9,
                                 max_seq=24))
        for r in reqs:
            srv.submit(Request(r.prompt.copy(), r.decode_len))
        results[chunk] = srv.run()
    for x, y in zip(results[1].request_results, results[4].request_results):
        assert np.array_equal(x.tokens, y.tokens), x.index
    assert results[4].expert_tokens_dropped == results[1].expert_tokens_dropped


def test_from_plan_plans_no_host_attention():
    """``ServeConfig.from_plan`` plans ω = 0, like ``plan_serving``: the
    engine attends every row on its device and refuses ω > 0.  Here the
    cost model alone would credit host attention with most rows."""
    from repro.core.engine import ModuleBatchingEngine
    from repro.core.hardware import A5000_C2
    from repro.core.planner import search_decode

    cfg, params = _mixtral()
    assert search_decode(cfg, A5000_C2, 64, B=4,
                         scheduler="continuous").plan.omega > 0
    sc = ServeConfig.from_plan(cfg, A5000_C2, ctx=64, scheduler="continuous",
                               B=4, decode_len=4)
    assert sc.plan.omega == 0.0
    ModuleBatchingEngine(cfg, params, sc.plan, max_seq=64)
