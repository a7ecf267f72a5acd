"""DeepSeek-V2-Lite on the normal serving path: latent attention (MLA) with
its latent cache, yarn RoPE, un-renormalised top-k gates, shared experts
and a leading dense layer; and pins that Mixtral's routing, layer pattern
and cache shapes did not move with them.

The tiny DeepSeek (d 64, 4 heads, kv_lora 32, nope/rope/v 32/16/32, 8
experts top-3, 2 shared, 1 dense + 2 MoE layers, yarn on) runs every
mechanism of the published configuration at CPU size."""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine
from repro.kernels import ops as kernel_ops
from repro.models import attention as A
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.layers import mla_softmax_scale, yarn_inv_freq
from repro.serving.kvcache import cache_from_prefill
from repro.serving.server import Request, ServeConfig, Server, StreamConfig
from repro.serving.weights import ParamStore

KEY = jax.random.PRNGKey(0)


def tiny(dtype="bfloat16"):
    return replace(
        get_config("deepseek-v2-lite"), name="deepseek-v2-lite-tiny",
        num_layers=3, d_model=64, vocab_size=256, num_heads=4,
        num_kv_heads=4, head_dim=48, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, d_ff=128, num_experts=8,
        experts_per_token=3, moe_d_ff=32, num_shared_experts=2,
        dtype=dtype)


def test_registry_config_has_the_published_widths():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size) == (
        27, 2048, 16, 102400)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mla_cache_width) == (512, 128, 64, 128, 576)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.moe_d_ff,
            cfg.num_shared_experts, cfg.d_ff, cfg.first_k_dense) == (
        64, 6, 1408, 2, 10944, 1)
    assert not cfg.norm_topk_prob
    assert (cfg.yarn_factor, cfg.yarn_mscale, cfg.yarn_mscale_all_dim,
            cfg.yarn_original_max_pos) == (40.0, 0.707, 0.707, 4096)
    # 1 dense + 26 MoE layers of the published 27: about 15.7B parameters
    assert 14e9 < cfg.param_counts()["total"] < 17e9


def test_yarn_frequencies_and_softmax_scale_pinned():
    cfg = get_config("deepseek-v2-lite")
    f = yarn_inv_freq(cfg)
    assert f.shape == (32,)
    # the correction range at beta_fast 32 / beta_slow 1 over 4096
    # original positions is dims [10, 23] of 32: below it the original
    # frequency, above it the frequency over the factor 40, a ramp between
    orig = [10000.0 ** (-2 * i / 64) for i in range(32)]
    assert f[0] == pytest.approx(1.0)
    assert f[10] == pytest.approx(orig[10], rel=1e-6)
    assert f[16] == pytest.approx(orig[16] * (7 / 13 + 6 / 13 / 40),
                                  rel=1e-6)
    assert f[23] == pytest.approx(orig[23] / 40, rel=1e-6)
    assert f[31] == pytest.approx(orig[31] / 40, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert mla_softmax_scale(cfg) == pytest.approx(0.1147214, rel=1e-6)


def test_absorbed_decode_equals_decompressed_attention():
    """One MLA layer in float32: the absorbed decode over the latent cache
    gives the decompressed form's output at the last position."""
    cfg = tiny("float32")
    p = A.init_mla_params(cfg, KEY)
    p["kv_norm"] = jax.random.uniform(KEY, (32,), minval=0.8, maxval=1.2)
    B, S = 2, 9
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        full, entry = A.mla_forward(cfg, p, x)
        cache = A.init_mla_cache(cfg, B, 16)
        cache["ckv"] = A.mla_pack(cfg, entry["ckv"][:, :S - 1], 16)
        pos = jnp.full((B,), S - 1, jnp.int32)
        got, new = A.mla_decode(cfg, p, x[:, S - 1:], cache, pos)
    np.testing.assert_allclose(got[:, 0], full[:, -1], atol=1e-5, rtol=1e-5)
    # the step wrote its own cache row: the decompressed form's row S-1
    np.testing.assert_allclose(new["ckv"], A.mla_pack(cfg, entry["ckv"], 16),
                               atol=1e-6)
    assert new["ckv"].shape == (B, 8, 2 * cfg.mla_cache_width)


@pytest.mark.parametrize("span", [64, 256])
def test_mla_decode_kernel_matches_xla(span):
    B, H, r, rope = 16, 4, 128, 64
    k = jax.random.split(KEY, 2)
    q = jax.random.normal(k[0], (B, H, r + rope)).astype(jnp.bfloat16)
    c = jax.random.normal(k[1], (B, span // 2, 2 * (r + rope))
                          ).astype(jnp.bfloat16)
    pos = jnp.asarray(np.random.default_rng(0).integers(0, span + 8, B),
                      jnp.int32)
    got = kernel_ops.mla_decode(q, c, pos, rank=r, scale=0.05,
                                use_kernel=True)
    want = kernel_ops.mla_decode(q, c, pos, rank=r, scale=0.05,
                                 use_kernel=False)
    # bf16 outputs; the kernel's probabilities are unnormalised when they
    # are rounded to bf16, the reference's normalised: a few bf16 ulps
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_prefill_then_decode_through_the_latent_cache_equals_forward():
    cfg = tiny("float32")
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    S = 11
    with jax.default_matmul_precision("highest"):
        _, caches = M.prefill(cfg, params, toks[:, :S])
        cache = cache_from_prefill(cfg, caches, S, max_seq=S + 4)
        got, _ = M.decode_step(cfg, params, cache, toks[:, S], jnp.int32(S))
        full, _, _ = M.forward(cfg, params, toks[:, :S + 1])
    np.testing.assert_allclose(got, full[:, S], atol=1e-4, rtol=1e-4)
    # the cache holds 576-style rows: latent + rope key, two to a row
    assert cache[0]["ckv"].shape == (2, 8, 2 * (32 + 16))


def test_gates_not_renormalised_and_mixtral_still_renormalises():
    x = jax.random.normal(KEY, (5, 64))
    ds = tiny()
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    gates, idx, probs = moe_mod.route(ds, w, x)
    np.testing.assert_allclose(
        gates, jnp.take_along_axis(probs, idx, axis=-1), rtol=1e-6)
    assert float(gates.sum(-1).max()) < 1.0
    mix = get_config("mixtral-8x7b", smoke=True)
    w4 = w[:, :4]
    g, i, p = moe_mod.route(mix, w4, x)
    top = jnp.take_along_axis(p, i, axis=-1)
    np.testing.assert_array_equal(
        g, top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9))
    np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-6)


def test_shared_experts_run_on_every_row():
    cfg = tiny("float32")
    p = moe_mod.init_moe_params(cfg, KEY)
    assert p["shared"]["w_gate"].shape == (64, 2 * 32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64))
    with jax.default_matmul_precision("highest"):
        y, _ = moe_mod.moe_apply(cfg, p, x)
        routed, _ = moe_mod.moe_apply(
            cfg, {k: v for k, v in p.items() if k != "shared"}, x)
        w = p["shared"]
        shared = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    np.testing.assert_allclose(y, routed + shared, atol=1e-5)


def test_dense_prefix_runs_before_the_periodic_stack():
    full = get_config("deepseek-v2-lite")
    assert M.lead_pattern(full) == [("mla", "dense")]
    assert M.layer_pattern(full) == [("mla", "moe")]
    assert M.num_groups(full) == 26
    cfg = tiny()
    params = M.init_params(cfg, KEY)
    assert len(params["lead"]) == 1 and "ffn" in params["lead"][0]
    assert params["layers"][0]["moe"]["router"].shape == (2, 64, 8)
    store = ParamStore(cfg, params)
    # the store's index starts after the dense prefix, which it pins
    assert "moe" in store.acquire(0) and "ffn" in store.lead[0]
    assert store.schema == [("mla", "moe")] * 2
    eng = ModuleBatchingEngine(cfg, params, Plan(B=2, b_a=1, b_e=2),
                               max_seq=16, store=store)
    assert eng.schema == [("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    eng.init_cache(2)
    assert [c["ckv"].shape for c in eng.cache] == [(2, 8, 96)] * 3


def test_mixtral_layer_pattern_and_cache_shapes_unchanged():
    full = get_config("mixtral-8x7b")
    assert M.lead_pattern(full) == []
    assert M.layer_pattern(full) == [("attn", "moe")]
    assert M.num_groups(full) == 32
    cfg = get_config("mixtral-8x7b", smoke=True)
    cache = M.init_cache(cfg, 2, 16)
    assert [jax.tree.map(lambda a: a.shape, c) for c in cache] == [
        {"k": (2, 2, 16, cfg.num_kv_heads, 32),
         "v": (2, 2, 16, cfg.num_kv_heads, 32)}]
    params = M.init_params(cfg, KEY)
    assert "lead" not in params
    eng = ModuleBatchingEngine(cfg, params, Plan(B=2, b_a=1, b_e=2),
                               max_seq=16)
    eng.init_cache(2)
    assert [jax.tree.map(lambda a: a.shape, c) for c in eng.cache] == [
        {"k": (2, 16, cfg.num_kv_heads, 32),
         "v": (2, 16, cfg.num_kv_heads, 32)}] * 2


def _serve(cfg, params, prompts, stream=None, **serve):
    server = Server(cfg, params,
                    Plan(B=4, b_a=2, b_e=4, omega=0.0, decode_chunk=4),
                    ServeConfig(max_seq=32, max_batch=4, **serve),
                    stream or StreamConfig())
    handles = [server.submit(Request(prompt=p, decode_len=6))
               for p in prompts]
    while server.step():
        pass
    return [list(h.tokens) for h in handles], server.finalize()


PROMPTS = [np.arange(n, dtype=np.int32) * 7 % 256 for n in (7, 12, 5, 9)]


def test_every_serving_path_serves_the_same_tokens():
    """Fused decode, per-expert predictive streaming (per-module decode,
    the shared experts pinned beside the router) and the continuous
    scheduler agree token for token, and greedy decoding equals the
    model's own forward in float32."""
    cfg = tiny("float32")
    params = M.init_params(cfg, KEY)
    with jax.default_matmul_precision("highest"):
        fused, _ = _serve(cfg, params, PROMPTS)
        cont, _ = _serve(cfg, params, PROMPTS, scheduler="continuous")
        streamed, rep = _serve(cfg, params, PROMPTS, StreamConfig(
            stream_weights=True, resident_bytes=0, predict_topk=3))
        # greedy: each served token is the forward pass's argmax over the
        # prompt and the tokens served before it (causal, so one padded
        # pass scores every position)
        seqs = np.zeros((4, 24), np.int32)
        for i, (p, t) in enumerate(zip(PROMPTS, fused)):
            seqs[i, :len(p) + 6] = np.concatenate([p, t])
        lg, _, _ = M.forward(cfg, params, jnp.asarray(seqs))
        best = np.asarray(jnp.argmax(lg, axis=-1))
    assert fused == cont == streamed
    assert rep.weight_htod_bytes > 0
    for i, p in enumerate(PROMPTS):
        assert list(best[i, len(p) - 1:len(p) + 5]) == fused[i]


def test_decode_ctx_tokens_counts_the_attended_context():
    cfg = tiny()
    params = M.init_params(cfg, KEY)
    _, rep = _serve(cfg, params, PROMPTS)
    # 5 decode ticks per request fed at positions len .. len+4, each
    # attending pos + 1 cached rows, in each of the 3 MLA layers
    want = 3 * sum(sum(n + i + 1 for i in range(5)) for n in (7, 12, 5, 9))
    assert rep.decode_ctx_tokens == want
    mix = get_config("mixtral-8x7b", smoke=True)
    _, rep = _serve(mix, M.init_params(mix, KEY), PROMPTS)
    assert rep.decode_ctx_tokens == 0


def test_layer_spans_carry_the_layer_kind(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name, **stats):
            made.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    cfg = tiny()
    eng = ModuleBatchingEngine(cfg, M.init_params(cfg, KEY),
                               Plan(B=2, b_a=2, b_e=2), max_seq=16,
                               fused_decode=False)
    toks = jnp.asarray(PROMPTS[0][None].repeat(2, 0))
    with analysis.tracing():
        eng.prefill(toks)
        eng.decode_step(toks[:, -1], 7)
    layers = [s for n, s in made if n == "moegen.engine.layer"]
    kinds = [(s["phase"], s["layer"], s["mixer"], s["ffn"]) for s in layers]
    assert kinds == [
        ("prefill", 0, "mla", "dense"), ("prefill", 1, "mla", "moe"),
        ("prefill", 2, "mla", "moe"), ("decode", 0, "mla", "dense"),
        ("decode", 1, "mla", "moe"), ("decode", 2, "mla", "moe")]


def test_latent_prefill_uploads_nothing_per_micro_batch():
    """With its rows and prompt lengths on the device, a ragged MLA
    prefill of several micro-batches uploads nothing from the host: the
    per-micro-batch inputs are cut at static bounds and the latent cache
    is packed inside its insert launch, so no eager slice or pad uploads
    an index between launches (each such upload is a point where the host
    can hold the chip idle)."""
    cfg = tiny()
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (8, 12), 0, cfg.vocab_size)
    lengths = jnp.asarray([12, 5, 9, 3, 7, 12, 2, 8], jnp.int32)
    eng = ModuleBatchingEngine(cfg, params, Plan(B=8, b_a=2, b_e=8,
                                                 omega=0.0), max_seq=32)
    want = eng.prefill(toks, lengths=lengths)           # traces every module
    with jax.transfer_guard_host_to_device("disallow"):
        got = eng.prefill_slots(toks, jnp.arange(8), lengths=lengths)
    assert jnp.array_equal(got, want)
