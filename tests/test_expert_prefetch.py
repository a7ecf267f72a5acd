"""Predictive per-expert streaming, hot-expert LRU, capacity planning.

The ISSUE 8 contracts: predictive-streamed decode is token-identical to
resident decode with zero steady-state retraces and zero unplanned
transfers (prediction moves WHEN bytes move, never WHICH math runs); the
hot-expert LRU never exceeds its byte budget and demotes cold entries;
``capacity_for_load`` sizes ``b_e`` from the measured routing histogram;
grouped prefill dispatches ragged over real prompt tokens, with no capacity
readback;
drops and routed load are accounted per MoE layer.  (The hypothesis-based
predictor-accuracy property lives in test_properties.py, the only module
allowed to import hypothesis.)
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.configs import get_config
from repro.core import planner, workload as W
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine
from repro.models import model as M
from repro.serving.weights import ParamStore

KEY = jax.random.PRNGKey(0)
B, S, DEC = 4, 12, 6


def _setup(arch="mixtral-8x7b", **over):
    cfg = get_config(arch, smoke=True)
    if over:
        cfg = replace(cfg, **over)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    return cfg, params, toks


def _engine(cfg, params, store=None, plan=None, **kw):
    plan = plan or Plan(B=B, b_a=2, b_e=B, omega=0.0)
    return ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC,
                                store=store, **kw)


# ---------------------------------------------------------------------------
# Token identity + steady-state hygiene
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("khat", [1, 2, 4])
def test_predictive_streamed_matches_resident(khat):
    cfg, params, toks = _setup()
    ref = _engine(cfg, params).generate(toks, DEC)
    st = ParamStore(cfg, params, resident_bytes=0.0, predict_topk=khat)
    eng = _engine(cfg, params, store=st)
    got = eng.generate(toks, DEC)
    assert jnp.array_equal(ref, got)
    eng.sync_stats()
    assert eng.stats.weight_htod_bytes > 0
    # the decode stage touched the per-expert path
    assert (eng.stats.expert_pred_hits + eng.stats.expert_pred_misses
            + eng.stats.expert_lru_hits) > 0


def test_predictive_steady_state_no_retrace_no_unplanned():
    """Steady-state predictive decode: every module hits its cached trace
    and every transfer runs under a planned scope (strict guard raises
    otherwise) — the MG105/sanitizer airtightness acceptance."""
    cfg, params, toks = _setup()
    st = ParamStore(cfg, params, resident_bytes=0.0, predict_topk=2,
                    lru_bytes=1e9)
    eng = _engine(cfg, params, store=st)
    with analysis.sanitize(strict=True) as san:
        eng.prefill(toks)                       # warm: trace every module
        cur = toks[:, -1]
        for t in range(3):
            cur = jnp.argmax(eng.decode_step(cur, S + t), axis=-1)
        with san.steady():                      # steady: identical shapes
            for t in range(3, DEC):
                cur = jnp.argmax(eng.decode_step(cur, S + t), axis=-1)
    rep = san.report()
    assert rep["steady_retraces"] == {}
    assert rep["planned_transfers"].get("expert-prefetch", 0) > 0
    # single-device prefill sizes no capacity from a readback
    assert rep["planned_transfers"].get("prefill-capacity-probe", 0) == 0


def test_predictor_seam_prefetch_only():
    """An adversarial predictor (always-wrong / empty) changes WHICH bytes
    are staged, never the tokens: mispredictions demand-fetch."""
    cfg, params, toks = _setup()
    ref = _engine(cfg, params).generate(toks, DEC)
    for pred in (lambda nli, k: [], lambda nli, k: [cfg.num_experts - 1]):
        st = ParamStore(cfg, params, resident_bytes=0.0, predict_topk=2,
                        lru_bytes=0.0)
        eng = _engine(cfg, params, store=st)
        eng.predictor = pred
        assert jnp.array_equal(ref, eng.generate(toks, DEC))
        eng.sync_stats()
        assert eng.stats.expert_pred_misses > 0   # wrong on purpose


# ---------------------------------------------------------------------------
# Hot-expert LRU
# ---------------------------------------------------------------------------
def test_lru_respects_byte_budget_and_demotes_cold():
    cfg, params, _ = _setup()
    per_expert = W.expert_weight_bytes(cfg)
    st = ParamStore(cfg, params, resident_bytes=0.0, predict_topk=2,
                    lru_bytes=1.5 * per_expert)
    li = next(iter(st._experts_host))
    st.acquire_experts(li, [0])
    assert (li, 0) in st._lru
    st.acquire_experts(li, [1])                  # budget fits only one
    assert (li, 1) in st._lru and (li, 0) not in st._lru
    assert st._lru_used <= st.lru_bytes
    ec = st.take_expert_counters()
    assert ec["pred_misses"] == 2 and ec["lru_hits"] == 0
    st.acquire_experts(li, [1])                  # hot hit, no copy
    assert st.take_expert_counters()["lru_hits"] == 1


def test_lru_zero_budget_never_caches():
    cfg, params, _ = _setup()
    st = ParamStore(cfg, params, resident_bytes=0.0, predict_topk=2,
                    lru_bytes=0.0)
    li = next(iter(st._experts_host))
    st.acquire_experts(li, [0])
    st.acquire_experts(li, [0])
    assert not st._lru and st._lru_used == 0
    assert st.take_expert_counters()["lru_hits"] == 0


# ---------------------------------------------------------------------------
# Imbalance-aware capacity planning
# ---------------------------------------------------------------------------
def test_capacity_for_load_uniform_and_collapsed():
    E, Bt, k = 4, 8, 2
    uni = planner.capacity_for_load([1.0] * E, Bt, k)
    assert uni == Bt * k // E                    # balanced expected load
    hot = planner.capacity_for_load([1.0, 0.0, 0.0, 0.0], Bt, k)
    assert hot == Bt                             # fully collapsed: capped at B
    # relaxing the drop budget can only shrink the capacity
    for eps in (0.01, 0.1, 0.5):
        assert planner.capacity_for_load([3.0, 1.0, 1.0, 1.0], Bt, k, eps) \
            <= planner.capacity_for_load([3.0, 1.0, 1.0, 1.0], Bt, k, 0.0)
    # degenerate: no measurements -> balanced fallback
    assert planner.capacity_for_load([0.0] * E, Bt, k) >= 1


def test_search_decode_accepts_measured_load():
    cfg = get_config("mixtral-8x7b")
    from repro.core.hardware import A5000_C2

    res = planner.search_decode(cfg, A5000_C2, 512, B=64,
                                expert_load=[8.0, 1.0, 1.0, 1.0, 1.0, 1.0,
                                             1.0, 1.0])
    assert res.plan.b_e >= 1
    assert planner.device_memory_ok(cfg, A5000_C2, res.plan, 512, "decode")


def test_next_pow2():
    assert [W.next_pow2(n) for n in (0, 1, 2, 3, 5, 8, 9)] == \
        [1, 1, 2, 4, 8, 8, 16]


def test_engine_online_capacity_override():
    """set_expert_capacity(1) under-provisions and drops; None restores the
    plan's drop-free capacity — the Server re-plan entry point."""
    cfg, params, toks = _setup()
    eng = _engine(cfg, params)
    eng.prefill(toks)
    eng.set_expert_capacity(1)
    eng.decode_step(toks[:, -1], S)
    eng.sync_stats()
    dropped_tight = eng.stats.expert_tokens_dropped
    assert dropped_tight > 0
    eng.set_expert_capacity(None)
    eng.decode_step(toks[:, -1], S + 1)
    eng.sync_stats()
    assert eng.stats.expert_tokens_dropped == dropped_tight  # no new drops


def test_server_replan_on_skew_drift():
    """The Server's online re-plan: when the hottest expert's measured
    share drifts past replan_skew, b_e is re-derived from the measured
    histogram and pushed into the engine."""
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.server import Server, ServeConfig

    cfg, params, _ = _setup()
    reqs = synthetic_requests(DatasetSpec("t", 4, 8, 8), cfg.vocab_size)
    server = Server(cfg, params, Plan(B=4, b_a=2, b_e=4, omega=0.0),
                    serve=ServeConfig(scheduler="continuous", decode_len=8,
                                      replan_skew=0.05))
    for r in reqs:
        server.submit(r)
    server._ensure_engine()
    rep_steps = 0
    while server.step():
        rep_steps += 1
    # force a drift and drive the re-plan cadence directly
    server._replan_share = -1.0
    server._replan_ticks = 7                    # next call hits the mod-8 gate
    server._maybe_replan()
    rep = server.finalize()
    assert rep.capacity_replans == 1
    assert server._engine._b_e_override is not None
    assert rep.expert_load is not None and rep.expert_load.sum() > 0


# ---------------------------------------------------------------------------
# Per-layer accounting + pow2-bucketed grouped prefill
# ---------------------------------------------------------------------------
def test_per_layer_drop_and_load_accounting():
    cfg, params, toks = _setup()
    eng = _engine(cfg, params, plan=Plan(B=B, b_a=2, b_e=1, omega=0.0))
    eng.generate(toks, DEC)
    st = eng.sync_stats()
    n_moe = sum(1 for _, f in eng.schema if f == "moe")
    assert st.expert_tokens_dropped_by_layer.shape == (n_moe,)
    assert st.expert_load.shape == (n_moe, cfg.num_experts)
    assert int(st.expert_tokens_dropped_by_layer.sum()) == \
        st.expert_tokens_dropped
    # routed copies = kept + dropped, per the pre-capacity histogram
    assert int(st.expert_load.sum()) == \
        st.expert_tokens + st.expert_tokens_dropped


def _check_ragged_prefill(cfg, params, toks):
    lengths = np.array([S, 5, 9, 3], np.int32)
    eng = _engine(cfg, params)
    with analysis.sanitize(strict=True) as san:
        lg = eng.prefill(toks, lengths=lengths)
    probes = san.report()["planned_transfers"].get("prefill-capacity-probe", 0)
    assert probes == 0
    eng.sync_stats()
    assert eng.stats.expert_tokens_dropped == 0
    assert eng.stats.prefill_expert_rows > 0
    ref_eng = _engine(cfg, params, grouped_prefill=False)
    ref = ref_eng.prefill(toks, lengths=lengths)
    np.testing.assert_allclose(
        np.asarray(lg, np.float32), np.asarray(ref, np.float32),
        atol=0.05 * cfg.d_model ** 0.5,
    )


def test_grouped_prefill_pow2_capacity_zero_drop():
    """Single-device grouped prefill dispatches ragged over the real
    prompt tokens' routed copies at a capacity of the micro-batch's token
    count: no capacity is measured, so prefill reads nothing back between
    its launches (zero ``prefill-capacity-probe`` transfers), nothing
    drops, and on right-padded ragged lengths the logits match the
    dense-reference prefill path."""
    _check_ragged_prefill(*_setup())


def test_grouped_prefill_ragged_at_64_experts():
    """The same at DeepSeek's shape: 64 experts, top-6, shared experts and
    a leading dense layer."""
    from test_grouped_dispatch import _tiny_deepseek_64

    cfg = _tiny_deepseek_64()
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    _check_ragged_prefill(cfg, params, toks)
