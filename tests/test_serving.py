"""Serving layer: generation, KV ring conversion, scheduler, sampling.

(The tokenizer round-trip property test lives in test_properties.py, the
only module allowed to import hypothesis.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving.generate import greedy_generate
from repro.serving.kvcache import cache_from_prefill

KEY = jax.random.PRNGKey(0)


def test_greedy_generate_shape_and_determinism():
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (3, 12), 0, cfg.vocab_size)
    a = greedy_generate(cfg, params, toks, 5)
    b = greedy_generate(cfg, params, toks, 5)
    assert a.shape == (3, 5)
    assert jnp.array_equal(a, b)


def test_ring_conversion_places_positions_mod_window():
    cfg = get_config("h2o-danube-1.8b", smoke=True)  # window = 64
    W = cfg.sliding_window
    S = W + 10                                        # prompt longer than window
    G, B, K, hd = 1, 1, cfg.num_kv_heads, cfg.head_dim
    # fabricate a prefill cache where k[pos] = pos
    k = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.float32)[None, None, :, None, None],
        (G, B, S, K, hd),
    ).astype(jnp.bfloat16)
    caches = [{"k": k, "v": k}]
    out = cache_from_prefill(cfg, caches, S, max_seq=S + 8)
    ring = out[0]["k"]                                # (G, B, W, K, hd)
    assert ring.shape[2] == W
    for pos in range(S - W, S):
        slot = pos % W
        assert float(ring[0, 0, slot, 0, 0]) == float(pos)


def test_scheduler_serve_dataset():
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    spec = DatasetSpec("tiny", 6, 8, 4)
    reqs = synthetic_requests(spec, cfg.vocab_size)
    plan = Plan(B=4, b_a=2, b_e=8, omega=0.0)
    report = serve_dataset(cfg, params, reqs, plan, decode_len=4)
    assert len(report.results) == 2                   # 6 requests / B=4
    assert report.decode_tokens == 6 * 4
    assert report.decode_throughput > 0


def test_sampling_strategies():
    from repro.serving.sampling import greedy, temperature_sample, top_k_sample

    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, 0.1]])
    assert greedy(logits).tolist() == [1, 0]
    k = jax.random.PRNGKey(0)
    t = temperature_sample(k, logits, temperature=1e-4)
    assert t.tolist() == [1, 0]
    tk = top_k_sample(k, logits, k=1)
    assert tk.tolist() == [1, 0]


def test_pad_requests_truncates_and_reports_lengths():
    from repro.serving.scheduler import Request, pad_requests

    reqs = [Request(np.arange(12, dtype=np.int32), 4),
            Request(np.arange(5, dtype=np.int32), 4)]
    toks, lens = pad_requests(reqs, pad_id=9, max_prompt_len=8)
    assert toks.shape == (2, 8)                   # truncation is real now
    assert lens.tolist() == [8, 5]
    assert toks[1, 5:].tolist() == [9, 9, 9]      # right-padded with pad_id
    assert toks[0].tolist() == list(range(8))


def test_serve_dataset_rejects_oversized_prompt():
    from repro.core.dag_builder import Plan
    from repro.serving.scheduler import Request, serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = [Request(np.zeros(30, np.int32), 4)]
    with np.testing.assert_raises_regex(ValueError, "max_seq"):
        serve_dataset(cfg, params, reqs, Plan(B=1, b_a=1, b_e=4, omega=0.0),
                      4, max_seq=16)
    # truncation makes the same request servable
    rep = serve_dataset(cfg, params, reqs, Plan(B=1, b_a=1, b_e=4, omega=0.0),
                        4, max_seq=16, max_prompt_len=12)
    assert rep.request_results[0].tokens.size == 4


def test_serve_dataset_ragged_prompts_match_per_sequence():
    """Mixed prompt lengths in one static batch serve the same tokens as
    each request served alone (the seed's ragged-prompt bug: logits taken
    at a pad position for every shorter prompt)."""
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("rag", 3, 12, 4), cfg.vocab_size,
                              prompt_lens=[12, 7, 9])
    plan = Plan(B=3, b_a=2, b_e=8, omega=0.0)
    rep = serve_dataset(cfg, params, reqs, plan, 4)
    for i, r in enumerate(reqs):
        solo = serve_dataset(cfg, params, [r],
                             Plan(B=1, b_a=1, b_e=8, omega=0.0), 4)
        assert np.array_equal(rep.request_results[i].tokens,
                              solo.request_results[0].tokens), i


def test_serve_dataset_honors_per_request_decode_len():
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("mix", 4, 8, 4), cfg.vocab_size,
                              decode_lens=[2, 6])
    plan = Plan(B=4, b_a=2, b_e=8, omega=0.0)
    rep = serve_dataset(cfg, params, reqs, plan, 4)
    assert [r.tokens.size for r in rep.request_results] == [2, 6, 2, 6]
    assert rep.decode_tokens == 16                # not 4 * max(decode_len)
    assert rep.wasted_slot_steps == 4 * 5 - (1 + 5 + 1 + 5)


def test_continuous_scheduler_equivalent_and_fewer_slot_steps():
    """Continuous in-flight batching: identical tokens per request, strictly
    fewer decode-step.slot units than the static scheduler on a
    mixed-decode_len workload."""
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("mix", 7, 12, 4), cfg.vocab_size,
                              prompt_lens=[12, 7, 9], decode_lens=[3, 8, 5])
    plan = Plan(B=3, b_a=2, b_e=16, omega=0.0)
    rs = serve_dataset(cfg, params, reqs, plan, 4, scheduler="static")
    rc = serve_dataset(cfg, params, reqs, plan, 4, scheduler="continuous")
    assert rc.scheduler == "continuous"
    assert len(rc.request_results) == len(reqs)
    for a, b in zip(rs.request_results, rc.request_results):
        assert a.index == b.index
        assert np.array_equal(a.tokens, b.tokens), a.index
    assert rc.decode_slot_steps < rs.decode_slot_steps
    assert rc.wasted_slot_steps < rs.wasted_slot_steps
    assert rc.occupancy > rs.occupancy
    assert rc.decode_tokens == rs.decode_tokens == sum(
        r.decode_len for r in reqs
    )
    assert all(r.latency_s >= 0 for r in rc.request_results)


def test_continuous_scheduler_eos_frees_slots_early():
    """EOS finishes a sequence before its decode_len; both schedulers trim
    the stream at EOS and the freed slot is recycled."""
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("eos", 4, 8, 6), cfg.vocab_size)
    plan = Plan(B=2, b_a=2, b_e=8, omega=0.0)
    base = serve_dataset(cfg, params, reqs, plan, 6, scheduler="continuous")
    # pick an eos that actually occurs mid-stream for at least one request
    eos = next(
        int(t) for r in base.request_results for t in r.tokens[:-1]
    )
    rep = serve_dataset(cfg, params, reqs, plan, 6, scheduler="continuous",
                        eos_id=eos)
    assert any(r.tokens.size < 6 for r in rep.request_results)
    for r in rep.request_results:
        if r.tokens.size < 6:
            assert r.tokens[-1] == eos
            assert eos not in r.tokens[:-1]
    assert rep.decode_slot_steps <= base.decode_slot_steps


@pytest.mark.slow
def test_continuous_scheduler_mixed_8_32_128():
    """Acceptance-scale workload: decode lengths drawn from {8, 32, 128} —
    continuous executes strictly fewer decode-step.slot units than static
    with identical tokens per request."""
    from repro.core.dag_builder import Plan
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("mix", 6, 16, 32), cfg.vocab_size,
                              decode_lens=[8, 32, 128])
    plan = Plan(B=3, b_a=3, b_e=16, omega=0.0)
    rs = serve_dataset(cfg, params, reqs, plan, 32, scheduler="static")
    rc = serve_dataset(cfg, params, reqs, plan, 32, scheduler="continuous")
    for a, b in zip(rs.request_results, rc.request_results):
        assert a.index == b.index
        assert np.array_equal(a.tokens, b.tokens), a.index
    assert rc.decode_slot_steps < rs.decode_slot_steps
    assert rc.decode_tokens == rs.decode_tokens == 2 * (8 + 32 + 128)


def test_serving_kvcache_slot_insert_evict():
    """scatter_prefill_rows overwrites exactly the target rows; evict_rows
    zeroes them."""
    from repro.serving.kvcache import evict_rows, scatter_prefill_rows

    cfg = get_config("jamba-1.5-large-398b", smoke=True)
    params = M.init_params(cfg, KEY)
    from repro.core.dag_builder import Plan
    from repro.core.engine import ModuleBatchingEngine

    eng = ModuleBatchingEngine(cfg, params,
                               Plan(B=4, b_a=4, b_e=8, omega=0.0), max_seq=16)
    toks = jax.random.randint(KEY, (4, 8), 0, cfg.vocab_size)
    eng.prefill(toks)
    before = [jax.tree.map(lambda a: np.asarray(a), layer)
              for layer in eng.cache]
    newcomer = jax.random.randint(jax.random.PRNGKey(5), (1, 6),
                                  0, cfg.vocab_size)
    eng.prefill_slots(newcomer, [2], lengths=np.asarray([6]))
    for layer_b, layer_a in zip(before, eng.cache):
        for key in layer_b:
            a, b = np.asarray(layer_a[key]), layer_b[key]
            assert np.array_equal(a[[0, 1, 3]], b[[0, 1, 3]]), key  # untouched
            assert not np.array_equal(a[2], b[2]), key              # replaced
    eng.cache = evict_rows(eng.cache, [2])
    for layer in eng.cache:
        for key in layer:
            assert not np.asarray(layer[key])[2].any(), key


def test_continuous_admission_gated_by_host_kv_budget():
    """Eq. 2 memory admission: with a host budget that fits only two
    in-flight sequences, the continuous scheduler defers the queue head
    until eviction frees KV bytes — same tokens, non-zero deferrals — and
    a request that can NEVER fit raises instead of deadlocking."""
    from dataclasses import replace as dc_replace

    from repro.core import workload as W
    from repro.core.dag_builder import Plan
    from repro.core.hardware import A5000_C2
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("mem", 5, 8, 4), cfg.vocab_size)
    plan = Plan(B=3, b_a=2, b_e=8, omega=0.0)
    need = W.kv_bytes_per_seq(cfg, 8 + 4)
    hw = dc_replace(A5000_C2,
                    host_mem_bytes=W.model_bytes(cfg) + 2.5 * need)
    free_run = serve_dataset(cfg, params, reqs, plan, 4,
                             scheduler="continuous")
    gated = serve_dataset(cfg, params, reqs, plan, 4, scheduler="continuous",
                          hw=hw)
    assert gated.admission_deferrals > 0
    assert free_run.admission_deferrals == 0
    assert len(gated.request_results) == len(reqs)
    for a, b in zip(free_run.request_results, gated.request_results):
        assert a.index == b.index
        assert np.array_equal(a.tokens, b.tokens), a.index
    # a request whose KV can never fit must raise, not wait forever
    hw_tiny = dc_replace(A5000_C2,
                         host_mem_bytes=W.model_bytes(cfg) + 0.5 * need)
    with pytest.raises(ValueError, match="Eq. 2"):
        serve_dataset(cfg, params, reqs, plan, 4, scheduler="continuous",
                      hw=hw_tiny)
