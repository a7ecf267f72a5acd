"""Fused donated decode: one launch per chunk, token-identical to per-module.

The fused macro-step (``engine._fused_decode_chunk``) runs embed -> the
whole layer schema -> head -> per-slot sampling as ONE jitted, donated
device dispatch, scanned over T decode ticks.  These tests pin the
contract: tokens are bit-identical to the per-module path (the oracle,
``fused_decode=False``) across archs, sampling modes and ragged lengths;
the chunk really is one dispatch; retraces are counted; streamed residency
falls back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine, dispatch_count
from repro.models import model as M
from repro.serving.sampling import BatchSampler, SamplingParams

KEY = jax.random.PRNGKey(0)
B, S, DEC = 4, 12, 8


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


def _generate(cfg, params, toks, fused, chunk, plan=None, **kw):
    plan = plan or Plan(B=B, b_a=2, b_e=B, omega=0.0, decode_chunk=chunk)
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC,
                               fused_decode=fused)
    out = np.asarray(eng.generate(toks, DEC, **kw))
    return out, eng


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "mamba2-370m",
                                  "jamba-1.5-large-398b"])
def test_fused_chunk_matches_per_module_greedy(arch):
    """Attn / SSM / hybrid archs: fused multi-token chunks produce the
    exact per-module greedy token streams, in ONE dispatch per chunk."""
    cfg, params, toks = _setup(arch)
    ref, _ = _generate(cfg, params, toks, fused=False, chunk=1)
    got, eng = _generate(cfg, params, toks, fused=True, chunk=4)
    assert np.array_equal(ref, got)
    assert eng.stats.fused_dispatches == 2            # ceil((DEC-1)/4)
    assert eng.stats.fused_ticks == DEC - 1


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "jamba-1.5-large-398b"])
def test_fused_chunk_matches_per_module_sampled(arch):
    """Seeded temperature/top-k streams are bit-identical fused vs
    per-module (the shared ``sample_tokens`` + in-carry token indices)."""
    cfg, params, toks = _setup(arch)
    sp = SamplingParams(temperature=0.8, top_k=5, seed=13)
    ref, _ = _generate(cfg, params, toks, fused=False, chunk=1, sampling=sp)
    got, _ = _generate(cfg, params, toks, fused=True, chunk=4, sampling=sp)
    assert np.array_equal(ref, got)


def test_fused_chunk_matches_per_module_ragged():
    """Ragged right-padded batches decode at per-sequence positions inside
    the fused chunk — token-identical to the per-module path."""
    cfg, params, _ = _setup("mixtral-8x7b")
    lens = np.asarray([12, 7, 4])
    rng = np.random.default_rng(0)
    padded = np.zeros((3, 12), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = rng.integers(0, cfg.vocab_size, n)
    plan = Plan(B=3, b_a=2, b_e=16, omega=0.0, decode_chunk=4)
    ref = np.asarray(ModuleBatchingEngine(
        cfg, params, plan, max_seq=12 + DEC, fused_decode=False
    ).generate(jnp.asarray(padded), DEC, lengths=lens, chunk=1))
    got = np.asarray(ModuleBatchingEngine(
        cfg, params, plan, max_seq=12 + DEC
    ).generate(jnp.asarray(padded), DEC, lengths=lens))
    assert np.array_equal(ref, got)


def test_fused_chunk_is_one_dispatch():
    """Regression: a fused T-token chunk is exactly ONE device dispatch;
    the per-module path costs O(layers * modules) per tick."""
    cfg, params, toks = _setup("mixtral-8x7b")
    plan = Plan(B=B, b_a=B, b_e=B, omega=0.0, decode_chunk=4)
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    cur = jnp.argmax(eng.prefill(toks), -1)
    sampler = BatchSampler.uniform(B, None)
    eng.decode_chunk(cur, jnp.int32(S), sampler, 4)   # compile once
    d0 = dispatch_count()
    eng.decode_chunk(cur, jnp.int32(S), sampler, 4)
    assert dispatch_count() - d0 == 1
    # per-module oracle: > 1 dispatch for a single tick
    ref = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC,
                               fused_decode=False)
    ref.prefill(toks)
    d0 = dispatch_count()
    ref.decode_step(cur, S)
    assert dispatch_count() - d0 > 1


def test_fused_retrace_counter():
    """Repeated same-shape chunks reuse the cached callable (retraces
    stays put); a new (B, path, chunk) key is counted as a retrace."""
    cfg, params, toks = _setup("mixtral-8x7b")
    plan = Plan(B=B, b_a=B, b_e=B, omega=0.0, decode_chunk=4)
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    cur = jnp.argmax(eng.prefill(toks), -1)
    sampler = BatchSampler.uniform(B, None)
    eng.decode_chunk(cur, jnp.int32(S), sampler, 4)
    eng.decode_chunk(cur, jnp.int32(S), sampler, 4)
    assert eng.stats.decode_retraces == 1
    eng.decode_chunk(cur, jnp.int32(S), sampler, 2)   # new chunk length
    assert eng.stats.decode_retraces == 2


def test_streamed_residency_falls_back_to_per_module():
    """Streamed weights keep the per-layer dispatch loop (the prefetch
    needs the layer boundary) — no fused dispatch is issued, tokens still
    identical to the fused resident run."""
    cfg, params, toks = _setup("mixtral-8x7b")
    plan = Plan(B=B, b_a=2, b_e=B, omega=0.0, decode_chunk=4)
    fused, _ = _generate(cfg, params, toks, fused=True, chunk=4, plan=plan)
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC,
                               stream_weights=True, resident_bytes=0.0)
    assert not eng.fused_eligible()
    got = np.asarray(eng.generate(toks, DEC))
    assert eng.stats.fused_dispatches == 0
    assert eng.stats.weight_htod_bytes > 0
    assert np.array_equal(fused, got)


def test_decode_step_sampled_takes_fused_path():
    """The single-tick sampled entry point rides the fused launch when
    eligible and matches the per-module tick exactly."""
    cfg, params, toks = _setup("mixtral-8x7b")
    plan = Plan(B=B, b_a=B, b_e=B, omega=0.0, decode_chunk=4)
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    ref = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC,
                               fused_decode=False)
    cur = jnp.argmax(eng.prefill(toks), -1)
    ref.prefill(toks)
    t_f = np.asarray(eng.decode_step_sampled(
        cur, jnp.int32(S), BatchSampler.uniform(B, None)))
    t_r = np.asarray(ref.decode_step_sampled(
        cur, jnp.int32(S), BatchSampler.uniform(B, None)))
    assert np.array_equal(t_f, t_r)
    assert eng.stats.fused_dispatches == 1


def test_select_decode_chunk_cadence():
    """Planner T: static waves chunk up to the wave length; continuous
    chunks below the eviction cadence mean_decode_len / B; an arrival
    stream tightens it further; always a power of two in [1, cap]."""
    from repro.core.planner import select_decode_chunk

    p_small = Plan(B=4, b_a=4, b_e=8, omega=0.0)
    p_big = Plan(B=512, b_a=32, b_e=8, omega=0.0)
    assert select_decode_chunk(p_small, 64, scheduler="static") == 64
    assert select_decode_chunk(p_small, 64) == 16         # 64/4 ticks/evict
    assert select_decode_chunk(p_big, 64) == 1            # evicts every tick
    assert select_decode_chunk(p_small, 64, arrival_rate=10.0,
                               step_time_s=0.05) == 2     # 2 ticks/arrival
    assert select_decode_chunk(p_small, 10 ** 9, scheduler="static") == 64
