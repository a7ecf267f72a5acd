"""Streamed parameter store: residency policy, prefetch, exactness.

The PR's contract (ISSUE 3): streamed-weights generation is token-for-token
identical to fully-resident generation; the greedy resident set matches the
planner's policy (base -> mixers -> dense FFNs -> expert stacks); htod
bytes and prefetch stalls are accounted; the planner only emits realizable
residency splits.  (The hypothesis-based streamed==resident property lives
in test_properties.py, the only module allowed to import hypothesis.)
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import planner, workload as W
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine
from repro.core.hardware import A5000_C2
from repro.models import model as M
from repro.serving.weights import ParamStore

KEY = jax.random.PRNGKey(0)
B, S, DEC = 4, 12, 6


def _setup(arch, **over):
    cfg = get_config(arch, smoke=True)
    if over:
        cfg = replace(cfg, **over)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


def _generate(cfg, params, toks, **engine_kw):
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=2, b_e=B, omega=0.0), max_seq=S + DEC,
        **engine_kw,
    )
    out = eng.generate(toks, DEC)
    return out, eng


# ---------------------------------------------------------------------------
# Exactness: streamed == resident, token for token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["mixtral-8x7b",          # attention + MoE
                                  "mamba2-370m",           # pure SSM
                                  "jamba-1.5-large-398b"])  # hybrid
def test_streamed_generate_matches_resident(arch):
    """resident_bytes=0 (every per-layer module streamed) produces exactly
    the resident engine's tokens, with real htod traffic and no drops."""
    cfg, params, toks = _setup(arch)
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks, stream_weights=True,
                         resident_bytes=0.0)
    assert jnp.array_equal(ref, got)
    assert eng.stats.weight_htod_bytes > 0
    assert eng.stats.expert_tokens_dropped == 0


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite"])
@pytest.mark.parametrize("prefetch", [True, False])
def test_streamed_matches_resident_both_expert_paths(arch, prefetch):
    """Streaming is orthogonal to the layer's kinds: overlapped and serial
    fetch reproduce the resident tokens for grouped-query attention and
    for latent attention with shared experts behind a leading dense
    layer."""
    cfg, params, toks = _setup(arch)
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks, stream_weights=True,
                         resident_bytes=0.0, prefetch=prefetch)
    assert jnp.array_equal(ref, got)
    assert eng.stats.weight_htod_bytes > 0


def test_streamed_partial_budget_matches_resident():
    """A budget covering only part of the model (mixers resident, experts
    streamed) is still exact."""
    cfg, params, toks = _setup("mixtral-8x7b")
    budget = W.base_weight_bytes(cfg) + sum(
        W.mixer_weight_bytes(cfg, cfg.layer_kind(i))
        for i in range(cfg.num_layers)
    )
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks, stream_weights=True,
                         resident_bytes=budget)
    assert jnp.array_equal(ref, got)
    rp = eng.store.residency
    assert all(rp.mixer_resident)            # mixers fit the budget...
    assert not any(                          # ...expert stacks do not
        rp.ffn_resident[i] for i in range(cfg.num_layers)
        if cfg.ffn_kind(i) == "moe"
    )
    assert eng.stats.weight_htod_bytes > 0


def test_streamed_everything_resident_is_noop():
    """A budget >= model bytes pins everything: no host set, no transfers."""
    cfg, params, toks = _setup("mixtral-8x7b")
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks, stream_weights=True,
                         resident_bytes=float(W.model_bytes(cfg)) + 1e9)
    assert jnp.array_equal(ref, got)
    assert eng.store.fully_resident
    assert eng.stats.weight_htod_bytes == 0
    assert eng.stats.prefetch_wait_s == 0.0


def test_streamed_single_layer_model():
    """One layer: the prefetch window wraps onto the same layer (fetch for
    the NEXT step) and generation stays exact."""
    cfg, params, toks = _setup("mixtral-8x7b", num_layers=1)
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks, stream_weights=True,
                         resident_bytes=0.0)
    assert jnp.array_equal(ref, got)
    assert eng.stats.weight_htod_bytes > 0


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite"])
def test_decode_issues_next_copy_before_attention(arch, monkeypatch):
    """Per-module streamed decode issues streamed layer l+1's copy right
    after ``acquire(l)`` returns and before l's attention stage (grouped
    query or latent); the last layer wraps to the first.  At each issue the
    window holds no other layer (only l, just landed, and l+1 are on
    device), and no acquire demand-fetches.  DeepSeek's leading dense layer
    is pinned, outside the store's index, so it acquires nothing."""
    cfg, params, toks = _setup(arch)
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=2, b_e=B, omega=0.0), max_seq=S + DEC,
        stream_weights=True, resident_bytes=0.0,
    )
    store = eng.store
    L = len(store.schema)                   # the store's streamed layers
    assert L == 2 and eng.n_lead == (arch == "deepseek-v2-lite")
    store.prefetch(0)           # else prefill's first acquire demand-fetches
    logits = eng.prefill(toks)
    assert store.demand_fetches == 0

    events, inflight_at_issue = [], {}
    acquire, prefetch = store.acquire, store.prefetch

    def rec_acquire(si, *a, **kw):
        out = acquire(si, *a, **kw)
        events.append(("acquire", si))
        return out

    def rec_prefetch(si):
        inflight_at_issue[len(events)] = sorted(store._inflight)
        events.append(("prefetch", si % L))
        prefetch(si)

    def recorder(stage):
        def rec(li, *a, **kw):
            events.append(("attention", li))
            return stage(li, *a, **kw)
        return rec

    monkeypatch.setattr(store, "acquire", rec_acquire)
    monkeypatch.setattr(store, "prefetch", rec_prefetch)
    for name in ("_attention_stage", "_mla_stage"):
        monkeypatch.setattr(eng, name, recorder(getattr(eng, name)))

    for t in range(3):
        events.clear()
        inflight_at_issue.clear()
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = eng.decode_step(tok, S + t)
        assert [e for e in events if e[0] == "acquire"] == [
            ("acquire", si) for si in range(L)]
        for si in range(L):
            a = events.index(("acquire", si))
            p = events.index(("prefetch", (si + 1) % L), a)  # wraps to 0
            assert a < p < events.index(("attention", si + eng.n_lead)), \
                events
            assert inflight_at_issue[p] == [], (p, inflight_at_issue)
        assert sorted(store._inflight) == [0]        # layer 0, next step's
        assert store.demand_fetches == 0


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite"])
@pytest.mark.parametrize("vector_pos", [False, True])
def test_attention_stage_uploads_nothing(arch, vector_pos):
    """The attention stage (grouped query, or latent for DeepSeek) cuts its
    micro-batches at static bounds, so no start index goes host-to-device:
    on the chip such an upload waits behind the next layer's weight copy,
    which is then in flight."""
    cfg, params, toks = _setup(arch)
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=3, b_e=B, omega=0.0), max_seq=S + DEC,
    )
    eng.prefill(toks)
    x = jnp.ones((B, cfg.d_model), jnp.bfloat16)
    pos = jnp.full((B,), S, jnp.int32) if vector_pos else jnp.asarray(S)
    p = eng._acquire(0)
    stage = (eng._mla_stage if eng.schema[0][0] == "mla"
             else eng._attention_stage)
    with jax.transfer_guard_host_to_device("disallow"):
        y = stage(0, p, x, pos)
    assert y.shape == x.shape
    assert eng.stats.attn_microbatches == 2 + 2   # prefill: 3 + 1 rows; decode
    assert eng.stats.device_attn_tokens == B


# ---------------------------------------------------------------------------
# ParamStore unit behavior
# ---------------------------------------------------------------------------
def test_store_greedy_fill_order_and_budget():
    """Greedy order: base always pinned; mixers before expert stacks; the
    realized resident bytes never exceed budget + base."""
    cfg, params, _ = _setup("mixtral-8x7b")
    zero = ParamStore(cfg, params, resident_bytes=0.0)
    assert not zero.fully_resident
    assert zero.residency.resident_bytes == pytest.approx(
        W.base_weight_bytes(cfg)
    )
    # enough for exactly one mixer
    one = W.base_weight_bytes(cfg) + W.mixer_weight_bytes(
        cfg, cfg.layer_kind(0)
    )
    st = ParamStore(cfg, params, resident_bytes=one)
    assert st.residency.mixer_resident[0]
    assert not any(
        st.residency.ffn_resident[i] for i in range(cfg.num_layers)
        if cfg.ffn_kind(i) == "moe"
    )
    full = ParamStore(cfg, params, resident_bytes=None)
    assert full.fully_resident
    assert full.streamed_module_bytes() == 0


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "olmoe-1b-7b",
                                  "qwen2-1.5b", "jamba-1.5-large-398b"])
def test_model_bytes_budget_realizes_fully_resident(arch):
    """The planner's fully-resident contract: a budget of exactly
    model_bytes pins EVERYTHING (the per-module policy sizes slightly
    exceed model_bytes — f32 router vs bf16 accounting — so this is a rule,
    not an emergent property of the greedy fill)."""
    for smoke in (True, False):
        cfg = get_config(arch, smoke=smoke)
        rp = W.plan_residency(cfg, W.model_bytes(cfg))
        assert rp.fully_resident, (arch, smoke)
        assert rp.n_streamed() == 0


def test_store_prefetch_window_bounded_and_counters_drain():
    cfg, params, _ = _setup("jamba-1.5-large-398b")
    st = ParamStore(cfg, params, resident_bytes=0.0, prefetch_depth=2)
    for li in range(len(st.schema)):
        st.prefetch(li)
        assert len(st._inflight) <= 2
    # acquire consumes the in-flight entry; on-demand fetch is counted
    st2 = ParamStore(cfg, params, resident_bytes=0.0)
    st2.prefetch(0)
    p = st2.acquire(0)
    assert "norm1" in p and 0 not in st2._inflight
    assert st2.demand_fetches == 0
    st2.acquire(1)                           # never prefetched
    assert st2.demand_fetches == 1
    htod, wait = st2.take_counters()
    assert htod > 0 and wait >= 0.0
    assert st2.take_counters() == (0, 0.0)   # drained


def test_store_prefetch_disabled_is_serial():
    cfg, params, _ = _setup("mixtral-8x7b")
    st = ParamStore(cfg, params, resident_bytes=0.0, prefetch=False)
    st.prefetch(0)                           # no-op
    assert not st._inflight
    st.acquire(0)
    assert st.demand_fetches == 1


# ---------------------------------------------------------------------------
# Planner emits realizable residency
# ---------------------------------------------------------------------------
def test_planned_residency_is_realizable():
    """search_decode's s_params is exactly the greedy fill's realized bytes
    and s_expert is the double-buffered stream window (or 0 when fully
    resident) — the executor can pin exactly what the planner charged."""
    cfg = get_config("mixtral-8x7b")
    res = planner.search_decode(cfg, A5000_C2, 768)
    plan = res.plan
    mb = W.model_bytes(cfg)
    if plan.s_params >= mb:
        assert plan.s_expert == 0.0
    else:
        # the window is sized for the plan's own streaming granularity:
        # whole-stack (predict_topk=0) or the predicted per-expert set
        assert plan.s_expert == pytest.approx(
            W.stream_buffer_bytes(cfg, 2, predict_topk=plan.predict_topk)
        )
        rp = W.plan_residency(cfg, plan.s_params)
        assert rp.resident_bytes == pytest.approx(plan.s_params)
        assert rp.n_streamed() > 0
    assert planner.device_memory_ok(cfg, A5000_C2, plan, 768, "decode")


def test_miss_fractions_follow_residency():
    """The DAG's htod charges follow the realized per-class residency: a
    budget that pins all mixers but no experts zeroes the attn miss and
    keeps the expert miss at 1."""
    from repro.core.dag_builder import _miss_fractions

    cfg = get_config("mixtral-8x7b")
    budget = W.base_weight_bytes(cfg) + cfg.num_layers * W.mixer_weight_bytes(
        cfg, "attn"
    )
    m = _miss_fractions(cfg, Plan(B=8, b_a=4, b_e=8, s_params=budget))
    assert m["attn"] == 0.0
    assert m["moe"] == 1.0
    m0 = _miss_fractions(cfg, Plan(B=8, b_a=4, b_e=8, s_params=0.0))
    assert m0["attn"] == 1.0 and m0["moe"] == 1.0


def test_plan_describe_is_reproducible():
    p = Plan(B=8, b_a=4, b_e=8, omega=0.3, phase="prefill", weight_reuse=3)
    d = p.describe()
    assert "phase=prefill" in d and "reuse=3" in d


# ---------------------------------------------------------------------------
# Serving surface
# ---------------------------------------------------------------------------
def test_serve_dataset_streaming_reports_htod():
    """ISSUE acceptance: ServeReport.htod_gb > 0 when s_params < model
    bytes, and streamed serving returns the resident tokens."""
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("tiny", 4, 8, 4), cfg.vocab_size)
    plan = Plan(B=4, b_a=2, b_e=8, omega=0.0)
    ref = serve_dataset(cfg, params, reqs, plan, 4)
    assert ref.htod_gb == 0.0
    for sched in ("static", "continuous"):
        rep = serve_dataset(cfg, params, reqs, plan, 4, scheduler=sched,
                            stream_weights=True, resident_bytes=0.0)
        assert rep.htod_gb > 0.0
        assert rep.prefetch_wait_s >= 0.0
        for a, b in zip(ref.request_results, rep.request_results):
            assert np.array_equal(a.tokens, b.tokens), (sched, a.index)


def test_host_params_place_resident_and_keep_streamed_on_host():
    """``init_params_host`` draws the same weights as ``init_params`` (to
    bf16 rounding of the scaled draws) into numpy; a store built from them
    copies only resident modules to the device, and generation matches the
    store built from device arrays token for token."""
    cfg, params, toks = _setup("mixtral-8x7b")
    host = M.init_params_host(cfg, KEY)
    assert jax.tree.structure(host) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(host)):
        assert isinstance(b, np.ndarray) and b.dtype == a.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   b.astype(np.float32), rtol=2 ** -7)
    budget = W.base_weight_bytes(cfg) + sum(
        W.mixer_weight_bytes(cfg, cfg.layer_kind(i))
        for i in range(cfg.num_layers))
    store = ParamStore(cfg, host, resident_bytes=budget)
    assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(store.base))
    assert all(isinstance(a, jax.Array) for r in store._resident
               for a in jax.tree.leaves(r))
    assert all(isinstance(a, np.ndarray) for h in store._host
               for a in jax.tree.leaves(h))
    assert store.streamed_module_bytes() > 0
    want, _ = _generate(cfg, host, toks)
    got, _ = _generate(cfg, host, toks, store=store)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
