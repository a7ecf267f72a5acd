"""The DeepSeek-V2 model module (``bench/models/DeepseekV2ForCausalLM.py``)
against the program on the CPU at a tiny size: the harness serves the
tiny cell through ``Server`` and the module's float32 reference judges it;
the module refuses a program whose widths differ from the file's; the
committed cell resolves; the work counts at the published widths."""
import copy
import os
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, manifest
from tiny_bench import ROOT, TINY_MIX

DS = manifest.load_model(os.path.join(ROOT, "bench"), "DeepseekV2ForCausalLM")
SEED = 2**33 + 5
CELL = "dsv2-lite-5l.offline"

# DeepSeek-V2-Lite's mechanisms at CPU size: MLA without q-LoRA, yarn
# RoPE, 8 routed experts top-3 with un-renormalised gates, 2 shared
# experts, 1 dense + 2 MoE layers, an untied head.
TINY = {
    "architectures": ["DeepseekV2ForCausalLM"],
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "topk_method": "greedy",
    "num_hidden_layers": 3, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False,
    "program": {"arch": "deepseek-v2-lite", "overrides": {
        "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "v_head_dim": 32, "d_ff": 128,
        "moe_d_ff": 32, "num_experts": 8, "experts_per_token": 3,
        "vocab_size": 256}},
    "serving": {"residency": "resident", "batch": 4,
                "prefill_microbatch": 2},
    "limits": {"mismatch_share": 0.05},
}


def test_tiny_cell_serves_what_the_reference_judges_correct():
    """Prefill, then decode through the latent cache, on the harness's own
    path (``Server``, the planner's plan, warm-up, the static scheduler,
    fused decode, ``ParamStore``), judged by the module's float32
    reference.  The program computes in bf16: a served token may lie a
    rounding below the reference's best, never more than the check's
    TAU = 0.1 logits, so no judged token counts as a mismatch."""
    cell = manifest.Cell(
        "tiny.waves", 1, TINY, TINY_MIX,
        [{"name": "gen_tok_s", "unit": "tokens/s"},
         {"name": "setup_s", "unit": "s"}], [], {}, DS)
    out = harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                           "/nonexistent")
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] == 0.0
    assert out["info"]["gaps"]["program"]["max"] <= harness.TAU
    assert out["info"]["warm_up"]["prefill_caps"]


def test_float32_program_equals_the_reference():
    """The program in float32 at HIGHEST matmul precision against the
    reference: the same equations (the absorbed decode against the
    reference's decompressed attention, yarn, un-renormalised gates, the
    shared experts, the dense prefix), so every served token is the
    reference's first choice up to float32 summation order (1e-4 logits)."""
    from repro.core.dag_builder import Plan
    from repro.serving.server import Request, ServeConfig, Server

    dims = DS.dims(TINY)
    cfg = replace(harness.program_config(DS, dims, TINY), dtype="float32")
    drawn = DS.resident_params(SEED, dims)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          {k: v for k, v in drawn.items() if k != "layers"})
    params["layers"] = [jax.tree.map(
        lambda s: jnp.stack(s.arrays).astype(jnp.float32),
        drawn["layers"][0], is_leaf=lambda a: isinstance(a, DS.LayerStack))]
    prompts = [np.arange(n, dtype=np.int32) * 11 % 256 for n in (20, 33, 9)]
    with jax.default_matmul_precision("highest"):
        server = Server(cfg, params, Plan(B=4, b_a=2, b_e=4, decode_chunk=4),
                        ServeConfig(max_seq=64, max_batch=4))
        hs = [server.submit(Request(prompt=p, decode_len=8)) for p in prompts]
        while server.step():
            pass
    served = [(p, list(h.tokens)) for p, h in zip(prompts, hs)]
    gaps, _ = DS.judge(dims, SEED, served, 64)
    assert max(float(g.max()) for g in gaps) < 1e-4


@pytest.mark.parametrize("override", [
    {"kv_lora_rank": 16}, {"qk_rope_head_dim": 8}, {"moe_d_ff": 64},
    {"num_shared_experts": 1}, {"first_k_dense": 0, "num_layers": 2},
    {"norm_topk_prob": True}, {"yarn_factor": 0.0}])
def test_check_program_rejects_a_changed_width(override):
    config = copy.deepcopy(TINY)
    config["program"]["overrides"].update(override)
    with pytest.raises(ValueError, match="differs from the published"):
        harness.program_config(DS, DS.dims(config), config)


def test_dims_refuse_what_the_reference_does_not_serve():
    with pytest.raises(ValueError, match="q-LoRA"):
        DS.dims({**TINY, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="yarn"):
        DS.dims({**TINY, "rope_scaling": {"type": "linear", "factor": 2}})


def test_the_committed_cell_resolves():
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert manifest.problems(m, ROOT) == []
    cell = manifest.resolve(m, CELL, ROOT)
    assert cell.model.__name__ == "bench_model_DeepseekV2ForCausalLM"
    assert cell.chips == 1 and cell.traffic["max_seq"] == 1536
    assert {x["name"] for x in cell.per_layer} == {
        "occupancy_pct", "prefill_pct", "decode_expert_pad_pct",
        "expert_ffn_roofline", "idle_pct", "mfu_pct", "mla_decode_pct"}
    c = cell.config
    # the published DeepSeek-V2-Lite widths, only the depth cut
    assert (c["hidden_size"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"],
            c["moe_intermediate_size"], c["intermediate_size"],
            c["vocab_size"]) == (2048, 512, 128, 64, 128, 64, 6, 2, 1408,
                                 10944, 102400)
    assert c["reduced"] == ["num_hidden_layers"]
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) \
        == (5, 27)
    dims = cell.model.dims(c)
    cfg = harness.program_config(cell.model, dims, c)
    assert cfg.num_layers == 5 and cfg.first_k_dense == 1
    assert dims.cache_width == 576


def test_work_counts_at_the_published_widths():
    dims = DS.dims(manifest.load_json(os.path.join(
        ROOT, "bench", "configs", "deepseek-v2-lite-5l.json")))
    d, h, r = 2048, 16, 512
    proj_decode = 2 * (d * h * 192 + d * 576 + h * 128 * r + h * r * 128
                       + h * 128 * d)
    proj_prefill = 2 * (d * h * 192 + d * 576 + r * h * 256 + h * 128 * d)
    moe = 2 * (d * 64 + 8 * 3 * d * 1408)
    dense = 2 * 3 * d * 10944
    ffn = dense + 4 * moe
    head = 2 * d * 102400
    pos = 700
    assert DS.decode_flops(dims, pos) == (
        5 * (proj_decode + 2 * (pos + 1) * h * (576 + 512)) + ffn + head)
    n = 300
    assert DS.prefill_flops(dims, n) == (
        n * (5 * proj_prefill + ffn)
        + 5 * 2 * h * (192 + 128) * n * (n + 1) // 2 + head)
    flops, nbytes = DS.expert_ffn_work(dims, 3072, 64)
    assert flops == 3072 * 6 * d * 1408
    assert nbytes == 64 * 3 * d * 1408 * 2 + 3072 * 2 * d * 2
