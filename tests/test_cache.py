"""Paged tiered KV cache: page table, host tier, prefix reuse (ISSUE 6).

The PR's contract: paged generation — fully device-resident (Mode A),
host-tier streamed (Mode B), and prefix-cache-admitted — is token-for-token
identical to the contiguous baseline; a config whose KV exceeds the device
pool budget but fits the host still serves; prefix hits skip the shared
span's prefill launches entirely.  (The hypothesis paged==contiguous
property lives in test_properties.py, the only module allowed to import
hypothesis.)
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine, dispatch_count
from repro.core.hardware import A5000_C2
from repro.models import model as M
from repro.serving.cache import CacheConfig, KVPageTable, PrefixStore
from repro.serving.kvcache import evict_retraces, evict_rows
from repro.serving.scheduler import Request, ServeConfig, Server, serve_dataset

KEY = jax.random.PRNGKey(0)
B, S, DEC = 4, 12, 6


def _setup(arch="mixtral-8x7b", **over):
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
    return np.asarray(out), eng


def _schema(cfg):
    return [(cfg.layer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.num_layers)]


# ---------------------------------------------------------------------------
# CacheConfig / KVPageTable unit behavior
# ---------------------------------------------------------------------------
def test_cache_config_validation():
    assert not CacheConfig().enabled
    assert CacheConfig(page_tokens=8).enabled
    with pytest.raises(AssertionError):
        CacheConfig(page_tokens=-1)
    with pytest.raises(AssertionError):
        CacheConfig(page_tokens=0, prefix_cache=True)


def test_page_table_alloc_free_and_frame_encoding():
    """ensure_rows/free_rows recycle frames, device tier first;
    gather_indices remaps device frame f -> f, host frame h -> P+1+h,
    unallocated -> the null sink P."""
    cfg, _, _ = _setup()
    # budget for exactly half the frames -> Mode B with a real device pool
    probe = KVPageTable(cfg, _schema(cfg), B, S + DEC, CacheConfig(page_tokens=4))
    half = probe.total_frames // 2
    pt = KVPageTable(cfg, _schema(cfg), B, S + DEC,
                     CacheConfig(page_tokens=4,
                                 device_pool_bytes=half * probe.frame_bytes))
    assert pt.device_frames == half and pt.host_frames == probe.total_frames - half
    assert not pt.fully_resident
    P = pt.device_frames
    # unallocated rows gather the null frame
    assert (pt.gather_indices([0, 1]) == P).all()
    pt.ensure_rows([0, 1])                        # fills the device half
    assert (pt.gather_indices([0, 1]) < P).all()
    pt.ensure_rows([2, 3])                        # spills to the host tier
    assert (pt.gather_indices([2, 3]) > P).all()  # host rows remap past null
    # re-ensuring a live row keeps its placement
    before = pt.page_map[2].copy()
    pt.ensure_rows([2])
    assert np.array_equal(pt.page_map[2], before)
    # free returns every frame; the map is clean and realloc succeeds,
    # device frames first again
    pt.free_rows([0, 1])
    assert (pt.page_map[:2] == -1).all()
    pt.ensure_rows([1])
    assert (pt.gather_indices([1]) < P).all()
    pt.ensure_rows(list(range(B)))
    assert (pt.page_map >= 0).all()
    assert "frames device" in pt.describe()


def test_page_table_spills_across_tiers():
    """When the device tier runs dry, allocation spills into the host tier
    instead of failing."""
    cfg, _, _ = _setup()
    pt = KVPageTable(cfg, _schema(cfg), B, S + DEC,
                     CacheConfig(page_tokens=4, device_pool_bytes=1.0))
    assert pt.device_frames == 0                  # everything is host-tier
    pt.ensure_rows(list(range(B)))                # all must spill
    assert (pt.page_map >= 0).all()


def test_mode_a_table_is_bookkeeping_only():
    cfg, _, _ = _setup()
    pt = KVPageTable(cfg, _schema(cfg), B, S + DEC, CacheConfig(page_tokens=8))
    assert pt.fully_resident
    assert not pt.pool_k and not pt.host_k        # no pools materialized
    assert pt.take_counters() == (0, 0)


# ---------------------------------------------------------------------------
# Exactness: paged == contiguous, token for token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["mixtral-8x7b",           # attention + MoE
                                  "h2o-danube-1.8b"])       # sliding window
def test_paged_resident_generate_matches_contiguous(arch):
    """Mode A: the engine keeps its contiguous buffers and the fused decode
    path — paging is free when every frame fits the device pool."""
    cfg, params, toks = _setup(arch)
    ref, ref_eng = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks,
                         cache_config=CacheConfig(page_tokens=8))
    assert np.array_equal(ref, got)
    assert eng.pages is not None and eng.pages.fully_resident
    assert eng.fused_eligible() == ref_eng.fused_eligible()
    assert eng.stats.kv_htod_bytes == 0


@pytest.mark.parametrize("page_tokens", [8, 4])
@pytest.mark.parametrize("arch", ["mixtral-8x7b", "h2o-danube-1.8b"])
def test_paged_host_tier_generate_matches_contiguous(arch, page_tokens):
    """Mode B: every page frame host-side, streamed through the prefetch
    window — still bit-identical, with real page traffic, whether the span
    fills its last page or not (18 slots: 3 pages of 8, 5 of 4)."""
    cfg, params, toks = _setup(arch)
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks,
                         cache_config=CacheConfig(page_tokens=page_tokens,
                                                  device_pool_bytes=1.0))
    assert np.array_equal(ref, got), (arch, page_tokens)
    assert not eng.pages.fully_resident
    assert eng.stats.kv_htod_bytes > 0
    assert eng.stats.kv_dtoh_bytes > 0


def test_paged_mixed_tier_generate_matches_contiguous():
    """A device pool covering only half the frames: rows straddle tiers and
    decode writes spill both ways."""
    cfg, params, toks = _setup()
    probe = KVPageTable(cfg, _schema(cfg), B, S + DEC, CacheConfig(page_tokens=4))
    budget = (probe.total_frames // 2) * probe.frame_bytes
    ref, _ = _generate(cfg, params, toks)
    got, eng = _generate(cfg, params, toks,
                         cache_config=CacheConfig(page_tokens=4,
                                                  device_pool_bytes=budget))
    assert np.array_equal(ref, got)
    assert 0 < eng.pages.device_frames < eng.pages.total_frames


def test_paged_host_tier_disables_fused_path():
    """The path-selection contract: host-tier pages force the per-layer
    loop (the page stream needs a layer boundary to hide behind), exactly
    like streamed weights."""
    cfg, params, toks = _setup()
    _, eng = _generate(cfg, params, toks,
                       cache_config=CacheConfig(page_tokens=8,
                                                device_pool_bytes=1.0))
    assert not eng.fused_eligible()
    assert eng.stats.fused_dispatches == 0


# ---------------------------------------------------------------------------
# Serving: device budget gating + the host-tier acceptance case
# ---------------------------------------------------------------------------
def _requests(cfg, lens, dec=DEC, seed=3, shared=0):
    rng = np.random.default_rng(seed)
    pre = [int(t) for t in rng.integers(5, cfg.vocab_size - 5, size=shared)]
    return [
        Request(prompt=pre + [int(t) for t in
                              rng.integers(5, cfg.vocab_size - 5, size=n)],
                decode_len=dec)
        for n in lens
    ]


@pytest.mark.parametrize("scheduler", ["static", "continuous"])
def test_kv_exceeding_device_budget_serves_from_host(scheduler):
    """ISSUE acceptance: a config whose KV cannot fit the device pool
    budget (device_kv_gb ~ 0) but fits host memory serves successfully and
    returns the contiguous baseline's tokens."""
    cfg, params, _ = _setup()
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    lens = [8, 6, 9, 7]
    ref = serve_dataset(cfg, params, _requests(cfg, lens), plan, DEC,
                        scheduler=scheduler, max_seq=S + DEC)
    rep = serve_dataset(cfg, params, _requests(cfg, lens), plan, DEC,
                        scheduler=scheduler, max_seq=S + DEC,
                        kv_page_tokens=8, device_kv_gb=1e-9)
    for a, b in zip(ref.request_results, rep.request_results):
        assert np.array_equal(a.tokens, b.tokens), a.index
    assert rep.kv_htod_gb > 0.0


def test_serve_config_from_plan_sizes_server_up_front():
    """from_plan: the planner fixes max_seq/max_batch before the first
    submit instead of sizing from the first-step queue."""
    cfg, params, _ = _setup()
    sc = ServeConfig.from_plan(cfg, A5000_C2, ctx=64, scheduler="continuous",
                               B=4, decode_len=4, kv_page_tokens=8)
    assert sc.plan is not None
    assert sc.max_seq == 64 and sc.max_batch == sc.plan.B
    assert 1 <= sc.max_batch <= 4
    srv = Server(cfg, params, serve=sc)
    for r in _requests(cfg, [6, 8]):
        r.decode_len = 4
        srv.submit(r)
    rep = srv.run()
    assert len(rep.request_results) == 2


def test_serve_config_prefix_cache_requires_paging():
    with pytest.raises(AssertionError):
        ServeConfig(prefix_cache=True)


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------
def test_prefix_store_keys_lru_and_support():
    ps = PrefixStore(page_tokens=4, entries=2)
    assert ps.key(np.arange(4)) is None           # no page strictly inside
    key, pspan = ps.key(np.arange(9))
    assert pspan == 8 and key == np.arange(8, dtype=np.int32).tobytes()
    assert ps.get(key) is None and ps.misses == 1
    ps.put(key, ["a"])
    assert ps.get(key) == ["a"] and ps.hits == 1
    ps.put(b"k2", ["b"])
    ps.put(b"k3", ["c"])                          # evicts the LRU entry
    assert len(ps._store) == 2 and ps.hit_rate == 0.5
    assert PrefixStore.supported(get_config("mixtral-8x7b", smoke=True))
    assert not PrefixStore.supported(get_config("h2o-danube-1.8b", smoke=True))


@pytest.mark.parametrize("device_pool", [None, 1.0])
def test_prefix_hit_admission_is_exact_and_skips_prefill(device_pool):
    """A prefix hit replays stored page rows and runs ONLY the suffix
    prefill: L+2 module launches (embed + one per layer + head), whatever
    the prefix length — and the admitted sequence decodes bit-identically
    to a cold prefill."""
    cfg, params, _ = _setup()
    cc = CacheConfig(page_tokens=4, device_pool_bytes=device_pool,
                     prefix_cache=True)
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    rng = np.random.default_rng(11)
    for npre in (8, 12):                          # two prefix lengths
        pre = [int(t) for t in rng.integers(5, cfg.vocab_size - 5, size=npre)]
        pa = pre + [int(t) for t in rng.integers(5, cfg.vocab_size - 5, size=2)]
        pb = pre + [int(t) for t in rng.integers(5, cfg.vocab_size - 5, size=3)]
        ref = serve_dataset(cfg, params, [Request(prompt=list(p), decode_len=4)
                                          for p in (pa, pb)],
                            plan, 4, max_seq=npre + 8, kv_page_tokens=4)
        eng = ModuleBatchingEngine(cfg, params, plan, max_seq=npre + 8,
                                   cache_config=cc)
        eng.init_cache(2)
        eng.prefill_slots(jnp.asarray(pa)[None, :], [0])
        kvs = eng.read_prefix_rows(0, npre)
        d0 = dispatch_count()
        logits = eng.prefill_prefix_hit(1, pb, kvs, npre)
        assert dispatch_count() - d0 == cfg.num_layers + 2, npre
        tok = int(np.argmax(np.asarray(logits[0])))
        assert tok == int(ref.request_results[1].tokens[..., 0].reshape(-1)[0])


@pytest.mark.parametrize("scheduler", ["static", "continuous"])
def test_prefix_cache_serving_matches_cold_and_counts_hits(scheduler):
    cfg, params, _ = _setup()
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    reqs = lambda: _requests(cfg, [3, 2, 4], shared=9, seed=5)
    ref = serve_dataset(cfg, params, reqs(), plan, DEC, scheduler=scheduler,
                        max_seq=24)
    # page 8: every prompt (lengths 12, 11, 13) keys at pspan=8, inside
    # the 9-token shared span — one stored prefix serves them all
    rep = serve_dataset(cfg, params, reqs(), plan, DEC, scheduler=scheduler,
                        max_seq=24, kv_page_tokens=8, prefix_cache=True)
    for a, b in zip(ref.request_results, rep.request_results):
        assert np.array_equal(a.tokens, b.tokens), (scheduler, a.index)
    assert rep.prefix_hits >= 1
    assert 0.0 < rep.prefix_hit_rate <= 1.0


def test_prefix_cache_silently_disabled_when_unsupported():
    """SWA models cannot transplant prefixes: the server drops the store
    rather than corrupting the ring alignment."""
    cfg, params, _ = _setup("h2o-danube-1.8b")
    plan = Plan(B=2, b_a=2, b_e=16, omega=0.0)
    rep = serve_dataset(cfg, params, _requests(cfg, [3, 2], shared=9),
                        plan, 4, max_seq=S + DEC, kv_page_tokens=4,
                        prefix_cache=True)
    assert rep.prefix_hits == 0 and rep.prefix_misses == 0
    assert len(rep.request_results) == 2


# ---------------------------------------------------------------------------
# Eviction retrace fix
# ---------------------------------------------------------------------------
def test_evict_rows_padded_width_shares_one_trace():
    """Eviction sets of size 1..8 pad to one width (8): slot recycling must
    not retrace per distinct set size (the bugfix this PR asserts)."""
    cfg, params, toks = _setup()
    eng = ModuleBatchingEngine(cfg, params, Plan(B=8, b_a=4, b_e=8, omega=0.0),
                               max_seq=S)
    eng.prefill(jnp.tile(toks, (2, 1)))
    r0 = evict_retraces()
    for n in range(1, 8):
        eng.cache = evict_rows(eng.cache, list(range(n)))
    assert evict_retraces() - r0 <= 1             # width 8, possibly cached
    eng.cache = evict_rows(eng.cache, list(range(8)))
    assert evict_retraces() - r0 <= 1             # still width 8
    for li in range(cfg.num_layers):
        assert not np.asarray(eng.cache[li]["k"][:8]).any()


# ---------------------------------------------------------------------------
# API surface
# ---------------------------------------------------------------------------
def test_serving_package_exports_the_cache_api():
    import repro.serving as S

    for name in ("CacheConfig", "KVPageTable", "PrefixStore"):
        assert hasattr(S, name), name
        assert name in S.__all__, name
