"""Grouped-expert dispatch: the engine's vectorized MoE stage.

Covers the PR's contract:
* the grouped engine generates the greedy tokens of the model's own
  forward when the per-expert capacity ``b_e`` admits every routed token;
* capacity overflow drops are counted in ``EngineStats`` and never crash;
* the XLA einsum fallback of ``kernels.ops.grouped_expert_ffn`` agrees with
  the Pallas kernel oracle (kernels/ref.py) and with the interpret-mode
  Pallas kernel itself;
* a decode step issues exactly one grouped launch per MoE layer;
* prefill can share the same grouped implementation via
  ``ShardCtx(moe_dispatch='grouped')``;
* the ragged dispatch of decode and prefill (``moe.ragged_dispatch``, its
  kernel in interpret mode) equals the capacity dispatch, with and without
  a mask of real tokens, keeps the fused chunk token-identical to
  per-module decode at 64 experts, and counts the rows its kernel
  computes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine
from repro.kernels import ops, ref
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.sharding.specs import ShardCtx

KEY = jax.random.PRNGKey(0)
B, S, DEC = 6, 16, 8


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "mixtral-8x7b",
                                  "deepseek-v2-lite"])
def test_grouped_matches_loop_token_for_token(arch):
    """The acceptance bar: grouped generate == the greedy argmax of the
    model's own forward (``M.forward``, dense-combine MoE), token for token,
    in float32.  The dense-combine reference does not go through the
    grouped dispatch under test.  (The name is kept from when the oracle
    was the engine's per-expert loop.)"""
    cfg = replace(get_config(arch, smoke=True), dtype="float32")
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    plan = Plan(B=B, b_a=2, b_e=B, omega=0.0)     # capacity B: no drops
    eng = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(eng.generate(toks, DEC))
        # causal, so one pass over prompt + served tokens scores each one
        seqs = jnp.concatenate([toks, jnp.asarray(out)], axis=1)
        lg, _, _ = M.forward(cfg, params, seqs)
    best = np.asarray(jnp.argmax(lg, axis=-1))[:, S - 1:S + DEC - 1]
    assert np.array_equal(best, out), float(np.mean(best == out))
    assert eng.stats.expert_tokens_dropped == 0
    # one grouped launch per MoE layer per decode step
    n_moe = sum(1 for _, f, _ in eng.layers if f == "moe")
    assert eng.stats.expert_launches == n_moe * (DEC - 1)


def test_capacity_overflow_is_counted():
    """b_e below the routed load drops token-copies, visibly in stats."""
    cfg, params, toks = _setup("olmoe-1b-7b")
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=B, b_e=1, omega=0.0), max_seq=S + DEC
    )
    out = eng.generate(toks, DEC)                  # also syncs stats
    assert out.shape == (B, DEC)
    n_moe = sum(1 for _, f, _ in eng.layers if f == "moe")
    routed = n_moe * (DEC - 1) * B * cfg.experts_per_token
    assert eng.stats.expert_tokens_dropped > 0
    assert eng.stats.expert_tokens + eng.stats.expert_tokens_dropped == routed
    # capacity 1 x E experts bounds what can be kept per layer-step
    assert eng.stats.expert_tokens <= n_moe * (DEC - 1) * cfg.num_experts


def test_decode_step_no_host_routing_sync(monkeypatch):
    """The grouped decode step never materializes routing on the host: the
    engine module's numpy binding is replaced by a tripwire for one step."""
    from repro.core import engine as engine_mod

    cfg, params, toks = _setup("mixtral-8x7b")
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=B, b_a=B, b_e=B, omega=0.0), max_seq=S + DEC
    )
    eng.prefill(toks)

    class _NoHostNumpy:
        def __getattr__(self, name):
            raise AssertionError(f"host numpy used in decode_step: np.{name}")

    monkeypatch.setattr(engine_mod, "np", _NoHostNumpy())
    eng.decode_step(toks[:, 0], S)                 # must not touch numpy


def test_xla_fallback_matches_ref_and_pallas():
    """ops.grouped_expert_ffn: einsum fallback vs kernels/ref.py oracle and
    vs the Pallas kernel in interpret mode."""
    E, C, D, F = 4, 128, 256, 128
    ks = jax.random.split(KEY, 4)
    x = (jax.random.normal(ks[0], (E, C, D)) * 0.3).astype(jnp.bfloat16)
    wg = (jax.random.normal(ks[1], (E, D, F)) * 0.05).astype(jnp.bfloat16)
    wu = (jax.random.normal(ks[2], (E, D, F)) * 0.05).astype(jnp.bfloat16)
    wd = (jax.random.normal(ks[3], (E, F, D)) * 0.05).astype(jnp.bfloat16)
    fallback = ops.grouped_expert_ffn(x, wg, wu, wd, use_kernel=False)
    oracle = ref.expert_ffn_ref(x, wg, wu, wd)
    pallas = ops.expert_ffn(x, wg, wu, wd, interpret=True)
    d_ref = jnp.max(jnp.abs(fallback.astype(jnp.float32) -
                            oracle.astype(jnp.float32)))
    d_pal = jnp.max(jnp.abs(fallback.astype(jnp.float32) -
                            pallas.astype(jnp.float32)))
    assert float(d_ref) < 0.05 * D ** 0.5, d_ref
    assert float(d_pal) < 0.05 * D ** 0.5, d_pal
    # on CPU the dispatch wrapper must select the fallback
    auto = ops.grouped_expert_ffn(x, wg, wu, wd)
    assert jnp.array_equal(auto, fallback)


def test_grouped_dispatch_drop_accounting_exact():
    cfg = replace(get_config("olmoe-1b-7b", smoke=True))
    p = moe_mod.init_moe_params(cfg, KEY)
    xt = (jax.random.normal(KEY, (32, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    gates, idx, _ = moe_mod.route(cfg, p["router"], xt)
    for cap in (1, 4, 32):
        y, kept, dropped, load = moe_mod.grouped_dispatch(
            cfg, xt, gates, idx,
            p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"], cap,
        )
        assert y.shape == xt.shape
        assert int(kept) + int(dropped) == 32 * cfg.experts_per_token
        # per-expert kept count can never exceed the capacity
        assert int(kept) <= cap * cfg.num_experts
        # the routed-load histogram counts every copy, PRE-capacity
        assert load.shape == (cfg.num_experts,)
        assert int(load.sum()) == 32 * cfg.experts_per_token


def test_grouped_dispatch_rejected_on_mesh():
    """moe_dispatch='grouped' is a single-device path: on a mesh with a
    model axis it must error, not silently fall back to psum."""
    cfg = get_config("olmoe-1b-7b", smoke=True)
    p = moe_mod.init_moe_params(cfg, KEY)
    x = jnp.zeros((2, 8, cfg.d_model), jnp.bfloat16)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                   moe_dispatch="grouped")
    with pytest.raises(ValueError, match="grouped"):
        moe_mod.moe_apply(cfg, p, x, ctx)


def test_serve_report_surfaces_drops():
    """serve_dataset folds the device-side drop counters into the report."""
    from repro.data.datasets import DatasetSpec, synthetic_requests
    from repro.serving.scheduler import serve_dataset

    cfg = get_config("olmoe-1b-7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = synthetic_requests(DatasetSpec("tiny", 4, 8, 4), cfg.vocab_size)
    rep = serve_dataset(cfg, params, reqs,
                        Plan(B=4, b_a=2, b_e=1, omega=0.0), 4)
    assert rep.expert_tokens_dropped > 0
    rep_ok = serve_dataset(cfg, params, reqs,
                           Plan(B=4, b_a=2, b_e=4, omega=0.0), 4)
    assert rep_ok.expert_tokens_dropped == 0


def test_grouped_prefill_shares_decode_path():
    """moe_apply with ShardCtx(moe_dispatch='grouped') routes the reference
    forward through the engine's grouped implementation."""
    cfg = replace(get_config("olmoe-1b-7b", smoke=True), capacity_factor=64.0)
    p = moe_mod.init_moe_params(cfg, KEY)
    x = (jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    y_grp, _ = moe_mod.moe_apply(cfg, p, x, ShardCtx(moe_dispatch="grouped"))
    y_loc, _ = moe_mod.moe_apply_local(cfg, p, x)
    d = jnp.max(jnp.abs(y_grp.astype(jnp.float32) - y_loc.astype(jnp.float32)))
    assert float(d) < 0.03, d
    # and the engine flag exercises it end-to-end at prefill
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (4, 8), 0, cfg.vocab_size)
    eng = ModuleBatchingEngine(
        cfg, params, Plan(B=4, b_a=2, b_e=4, omega=0.0), max_seq=16,
        grouped_prefill=True,
    )
    ref_eng = ModuleBatchingEngine(
        cfg, params, Plan(B=4, b_a=2, b_e=4, omega=0.0), max_seq=16,
    )
    lg = eng.prefill(toks)
    lr = ref_eng.prefill(toks)
    scale = float(jnp.max(jnp.abs(lr.astype(jnp.float32)))) + 1e-6
    d = float(jnp.max(jnp.abs(lg.astype(jnp.float32) -
                              lr.astype(jnp.float32)))) / scale
    assert d < 0.05, d


def _valid_mask(kind, T, idx):
    """(T,) real-token mask of a right-padded batch of 20-token rows:
    every token, the second half of each row padded, or the second half
    padded plus a whole row of pads and every token routed to expert 0
    (so that expert's load of real copies is zero)."""
    if kind is None:
        return None
    pos = jnp.arange(T) % 20
    if kind == "all":
        return jnp.ones((T,), bool)
    valid = pos < 10
    if kind == "halves":
        return valid
    return valid & (jnp.arange(T) >= 20) & ~jnp.any(idx == 0, axis=-1)


_RAGGED_CASES = [
    (40, 40, None), (40, 3, None), (200, 200, None), (200, 30, None),
    (40, 40, "all"), (200, 200, "halves"), (200, 30, "halves"),
    (200, 200, "block"), (512, 512, "halves"),
]


@pytest.mark.parametrize(
    "T,cap,mask", _RAGGED_CASES,
    ids=["-".join(str(v) for v in c if v is not None) for c in _RAGGED_CASES])
def test_ragged_dispatch_equals_grouped(T, cap, mask):
    """Same y, kept, dropped and load as the capacity dispatch, dropless
    (capacity = T) and where copies drop, with and without a mask of real
    tokens (pads take no row and read zero); both kernels in interpret
    mode.  At T 512 the dispatch takes 256-row tiles, as prefill does."""
    cfg = get_config("olmoe-1b-7b", smoke=True)
    p = moe_mod.init_moe_params(cfg, KEY)
    xt = (jax.random.normal(KEY, (T, cfg.d_model)) * 0.3).astype(jnp.bfloat16)
    gates, idx, _ = moe_mod.route(cfg, p["router"], xt)
    valid = _valid_mask(mask, T, idx)
    args = (cfg, xt, gates, idx, p["experts_w_gate"], p["experts_w_up"],
            p["experts_w_down"], cap)
    got = moe_mod.ragged_dispatch(*args, use_kernel=True, valid=valid)
    want = moe_mod.grouped_dispatch(*args, use_kernel=True, valid=valid)
    for g, w in zip(got, want):
        assert jnp.array_equal(g, w)
    y, kept, dropped, load = got
    real = (jnp.ones((T,), bool) if valid is None else valid)
    n_real = int(real.sum())
    assert int(load.sum()) == int(kept) + int(dropped) == \
        cfg.experts_per_token * n_real
    assert (int(dropped) > 0) == (cap < n_real)
    assert not jnp.any(y[~real])
    if mask == "block":
        assert int(load[0]) == 0
    if T == 512:
        assert moe_mod.ragged_tile(T, cfg.experts_per_token,
                                   cfg.num_experts) == 256
    # off the TPU the ragged dispatch is the capacity einsum itself
    auto = moe_mod.ragged_dispatch(*args, valid=valid)
    fallback = moe_mod.grouped_dispatch(*args, use_kernel=False, valid=valid)
    for g, w in zip(auto, fallback):
        assert jnp.array_equal(g, w)


def _tiny_deepseek_64():
    """The tiny DeepSeek of tests/test_deepseek.py (1 dense + 2 MoE
    layers, shared experts, un-renormalised gates) at DeepSeek-V2-Lite's
    64 routed experts, top-6."""
    return replace(
        get_config("deepseek-v2-lite"), name="deepseek-v2-lite-tiny",
        num_layers=3, d_model=64, vocab_size=256, num_heads=4,
        num_kv_heads=4, head_dim=48, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, d_ff=128, num_experts=64,
        experts_per_token=6, moe_d_ff=32, num_shared_experts=2)


def test_ragged_fused_chunk_matches_per_module_at_64_experts(monkeypatch):
    """The fused chunk inlines the per-module decode's ragged MoE stage:
    token for token the same, with the ragged kernel (interpret mode)
    running in both, and the same kernel rows counted."""
    traced = []

    def with_kernel(*a, **kw):
        traced.append(1)
        return ragged(*a, **kw, use_kernel=True)

    ragged = moe_mod.ragged_dispatch
    monkeypatch.setattr(moe_mod, "ragged_dispatch", with_kernel)
    # a name of its own: no jitted module traced without the kernel is reused
    cfg = replace(_tiny_deepseek_64(), name="deepseek-v2-lite-tiny-ragged")
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                              cfg.vocab_size)
    plan = Plan(B=4, b_a=2, b_e=4, omega=0.0, decode_chunk=4)
    out, rows = [], []
    for fused in (False, True):
        eng = ModuleBatchingEngine(cfg, params, plan, max_seq=16,
                                   fused_decode=fused)
        out.append(np.asarray(eng.generate(toks, 6)))
        rows.append(eng.stats.decode_expert_rows)
        assert eng.stats.fused_dispatches == (2 if fused else 0)
    assert traced
    assert np.array_equal(out[0], out[1])
    assert rows[0] == rows[1] > 0


@pytest.mark.parametrize("streamed", [False, True])
def test_serve_report_counts_decode_expert_rows(streamed):
    """With every router zeroed each row routes to experts 0..k-1, so a
    tick's ragged kernel computes k tiles of ragged_tile(4) = 16 rows in
    each MoE layer; the report sums that over layers and ticks, from the
    fused chunk and from the predictive-streamed per-module stage."""
    from repro.serving.server import (Request, ServeConfig, Server,
                                      StreamConfig)

    cfg = _tiny_deepseek_64()
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: (jnp.zeros_like(a)
                         if "router" in jax.tree_util.keystr(path) else a),
        M.init_params(cfg, KEY))
    server = Server(cfg, params,
                    Plan(B=4, b_a=2, b_e=4, omega=0.0, decode_chunk=4),
                    ServeConfig(max_seq=32, max_batch=4),
                    StreamConfig(stream_weights=True, resident_bytes=0,
                                 predict_topk=6) if streamed
                    else StreamConfig())
    for n in (7, 12, 5, 9):
        server.submit(Request(prompt=np.arange(n, dtype=np.int32),
                              decode_len=6))
    while server.step():
        pass
    rep = server.finalize()
    assert (rep.weight_htod_bytes > 0) == streamed
    ticks = rep.decode_slot_steps // 4
    assert ticks > 0
    assert moe_mod.ragged_tile(4, cfg.experts_per_token,
                               cfg.num_experts) == 16
    assert rep.decode_expert_rows == 2 * ticks * cfg.experts_per_token * 16
