"""A tiny cell laid out as the benchmark lays out its cells."""
import json
import os

from bench.manifest import load_model, model_file

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIXTRAL_FILE = model_file(os.path.join(ROOT, "bench"), "MixtralForCausalLM")
MIXTRAL = load_model(os.path.join(ROOT, "bench"), "MixtralForCausalLM")

# A Mixtral-shaped model small enough for the CPU: every mechanism of the
# benchmark's configurations (GQA, RoPE, top-2 of 4 experts, untied head).
TINY_CONFIG = {
    "architectures": ["MixtralForCausalLM"],
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2,
    "num_local_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "program": {"arch": "mixtral-8x7b", "overrides": {
        "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "moe_d_ff": 128, "num_experts": 4,
        "vocab_size": 256, "rope_theta": 10000.0}},
    "serving": {"residency": "resident", "batch": 4,
                "prefill_microbatch": 2, "resident_gb": 0.0001},
    "limits": {"mismatch_share": 0.05},
}
TINY_MIX = {
    "loop": "closed", "scheduler": "static", "max_seq": 48, "blocks": 3,
    "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 32,
               "quantum": 8},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16,
               "quantum": 4},
}


def write_bench(tmp, config=TINY_CONFIG, mix=TINY_MIX, readers=None,
                models=None):
    """A checkout holding one tiny cell, laid out as the benchmark is:
    a manifest, a configuration file, a traffic mix, metric readers and
    model modules (``models``: architecture -> source; by default the
    configuration's architecture as a copy of the Mixtral module), each
    found by its name."""
    if models is None:
        with open(MIXTRAL_FILE) as f:
            models = {config["architectures"][0]: f.read()}
    readers = readers or {
        "occupancy_pct": "def read(ctx):\n"
                         "    d = ctx['delta']\n"
                         "    return 100.0 * (1 - d['wasted_slot_steps']"
                         " / d['slot_steps'])\n"}
    os.makedirs(tmp / "bench" / "configs")
    os.makedirs(tmp / "bench" / "traffic")
    os.makedirs(tmp / "bench" / "metrics")
    os.makedirs(tmp / "bench" / "models")
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp / "bench" / "traffic" / "waves.json").write_text(json.dumps(mix))
    for name, src in readers.items():
        (tmp / "bench" / "metrics" / f"{name}.py").write_text(src)
    for arch, src in models.items():
        (tmp / "bench" / "models" / f"{arch}.py").write_text(src)
    manifest = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "tiny", "source": "https://example.org/tiny",
                     "file": "bench/configs/tiny.json",
                     "reduced": ["num_hidden_layers"], "why": "tiny"}],
        "workloads": [{"name": "tiny.waves", "config": "tiny",
                       "traffic": "waves", "chips": 1, "why": "tiny"}],
        "end_to_end": [
            {"name": "gen_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": name, "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "gen_tok_s"} for name in readers],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return manifest
