"""Module-based batching engine == model-based reference, plus engine stats."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine, unstack_layers
from repro.models import model as M
from repro.serving.generate import greedy_generate
from repro.serving.kvcache import cache_from_prefill

KEY = jax.random.PRNGKey(0)
B, S, DEC = 6, 16, 6


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "olmoe-1b-7b",
                                  "mamba2-370m", "jamba-1.5-large-398b"])
def test_engine_logits_match_reference(arch):
    """Per-step logits equal the model-based reference.

    Runs in float32: the engine's per-layer module launches and the
    reference's fused ``lax.scan`` reassociate bf16 reductions differently,
    and in deep random-weight smoke models (jamba: 8 layers) that eps-level
    noise is chaotically amplified through top-k routing flips.  f32 makes
    the comparison tight (~1e-6), i.e. a STRICTER structural-equivalence
    check; bf16 behavior is covered by the engine-vs-engine token-exactness
    tests (ragged generate, fused-vs-per-module, streamed-vs-resident)."""
    from dataclasses import replace

    cfg = replace(get_config(arch, smoke=True), dtype="float32")
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    lg_ref, caches = M.prefill(cfg, params, toks)
    cache = cache_from_prefill(cfg, caches, S, max_seq=S + DEC)
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=2, b_e=B, omega=0.0), max_seq=S + DEC
    )
    lg_eng = eng.prefill(toks)
    scale = float(jnp.max(jnp.abs(lg_ref))) + 1e-6
    d0 = jnp.max(jnp.abs(lg_ref[:, 0] - lg_eng))
    assert float(d0) / scale < 1e-4, d0
    nxt = jnp.argmax(lg_ref[:, 0], -1)
    lg2_ref, _ = M.decode_step(cfg, params, cache, nxt, jnp.int32(S))
    lg2_eng = eng.decode_step(nxt, S)
    d1 = jnp.max(jnp.abs(lg2_ref - lg2_eng))
    assert float(d1) / scale < 1e-4, d1


@pytest.mark.parametrize("b_a", [2, 4, 6])
def test_engine_microbatch_counts(b_a):
    cfg, params, toks = _setup("mixtral-8x7b")
    plan = Plan(B=B, b_a=b_a, b_e=B, omega=0.0)   # capacity B: no drops
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    eng.prefill(toks)
    eng.stats.attn_microbatches = 0
    eng.decode_step(toks[:, 0], S)
    n_attn_layers = sum(1 for k, _, _ in eng.layers if k == "attn")
    # every row attends on the device, in micro-batches of b_a rows
    assert eng.stats.attn_microbatches == n_attn_layers * -(-B // b_a)
    assert eng.stats.device_attn_tokens == n_attn_layers * B
    # grouped dispatch: exactly ONE expert launch per MoE layer per step,
    # and every routed token-copy was processed (no capacity drops)
    n_moe_layers = sum(1 for _, f, _ in eng.layers if f == "moe")
    assert eng.stats.expert_launches == n_moe_layers
    eng.sync_stats()
    assert eng.stats.expert_tokens == n_moe_layers * B * cfg.experts_per_token
    assert eng.stats.expert_tokens_dropped == 0


def test_engine_generation_runs_all_archs():
    for arch in ["qwen2-1.5b", "h2o-danube-1.8b", "phi3.5-moe-42b-a6.6b"]:
        cfg, params, toks = _setup(arch)
        eng = ModuleBatchingEngine(
            cfg, params, Plan(B=B, b_a=3, b_e=8, omega=0.0), max_seq=S + DEC
        )
        out = eng.generate(toks, DEC)
        assert out.shape == (B, DEC)
        assert int(out.max()) < cfg.vocab_size


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "jamba-1.5-large-398b"])
def test_engine_ragged_generate_matches_per_sequence(arch):
    """Padded ragged batch generate == each sequence generated alone,
    token-for-token (prompt-length mask + per-sequence decode positions)."""
    import numpy as np

    cfg = get_config(arch, smoke=True)
    params = M.init_params(cfg, KEY)
    lens = [16, 11, 7]
    S, DEC = max(lens), 5
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    padded = np.zeros((len(lens), S), np.int32)
    for i, p in enumerate(prompts):
        padded[i, : len(p)] = p
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=3, b_a=2, b_e=16, omega=0.0), max_seq=S + DEC
    )
    got = np.asarray(eng.generate(jnp.asarray(padded), DEC,
                                  lengths=np.asarray(lens)))
    for i, p in enumerate(prompts):
        solo = ModuleBatchingEngine(
            cfg, params, Plan(B=1, b_a=1, b_e=16, omega=0.0), max_seq=S + DEC
        )
        ref = np.asarray(solo.generate(jnp.asarray(p)[None], DEC))
        assert np.array_equal(got[i], ref[0]), (i, got[i], ref[0])


def test_engine_ragged_prefill_unpadded_logits_gather():
    """Prefill logits of a padded shorter prompt equal the unpadded run's
    (the seed emitted logits at the PAD position for every shorter prompt)."""
    cfg, params, toks = _setup("mixtral-8x7b")
    n = 9                                         # a prompt shorter than S
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=2, b_e=B, omega=0.0), max_seq=S + DEC
    )
    lengths = jnp.asarray([S] * (B - 1) + [n])
    lg = eng.prefill(toks.at[B - 1, n:].set(0), lengths=lengths)
    solo = ModuleBatchingEngine(
        cfg, params, Plan(B=1, b_a=1, b_e=B, omega=0.0), max_seq=S + DEC
    )
    lg_solo = solo.prefill(toks[B - 1 :, :n])
    assert jnp.array_equal(lg[B - 1], lg_solo[0])


@pytest.mark.parametrize("omega", [0.5, 1.0])
def test_engine_refuses_host_attention_share(omega):
    """The host-CPU attention the planner models as ω is not built, so an
    engine refuses a plan with ω > 0 instead of serving it some other way."""
    cfg, params, _ = _setup("mixtral-8x7b")
    with pytest.raises(ValueError, match="host-side attention is not built"):
        ModuleBatchingEngine(cfg, params, Plan(B=B, b_a=2, b_e=B, omega=omega))


def test_unstack_layers_roundtrip():
    cfg, params, _ = _setup("jamba-1.5-large-398b")
    layers = unstack_layers(cfg, params)
    assert len(layers) == cfg.num_layers
    kinds = [k for k, _, _ in layers]
    assert kinds.count("attn") == 1          # 8-layer smoke: one attn layer
