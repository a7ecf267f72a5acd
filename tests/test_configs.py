"""Config registry: every assigned architecture with its exact dimensions."""
import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, list_archs
from repro.models.model import layer_pattern, num_groups

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
    "olmoe-1b-7b": (16, 2048, 16, 16, 0, 50304),
    "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
    "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
    "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
    "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
    "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
    "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
    "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 0, 32064),
}


def test_all_assigned_archs_registered():
    archs = list_archs()
    for a in EXPECTED:
        assert a in archs


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_exact_dimensions(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = EXPECTED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v


def test_moe_configs():
    olmoe = get_config("olmoe-1b-7b")
    assert (olmoe.num_experts, olmoe.experts_per_token) == (64, 8)
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert (phi.num_experts, phi.experts_per_token) == (16, 2)
    jamba = get_config("jamba-1.5-large-398b")
    assert (jamba.num_experts, jamba.experts_per_token) == (16, 2)


def test_param_counts_plausible():
    # headline sizes should be within ~15% of the names
    approx = {
        "mamba2-370m": 0.37e9,
        "olmoe-1b-7b": 7e9,
        "internvl2-76b": 70e9,      # language backbone of the 76B VLM
        "qwen2-1.5b": 1.5e9,
        "phi3.5-moe-42b-a6.6b": 42e9,
        "jamba-1.5-large-398b": 398e9,
        "mixtral-8x7b": 46.7e9,
    }
    for arch, n in approx.items():
        total = get_config(arch).param_counts()["total"]
        assert 0.7 * n < total < 1.35 * n, (arch, total, n)


def test_active_params_moe():
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    c = cfg.param_counts()
    assert c["active"] < 0.3 * c["total"]          # 6.6B of 42B


def test_jamba_interleave_pattern():
    cfg = get_config("jamba-1.5-large-398b")
    pattern = layer_pattern(cfg)
    assert len(pattern) == 8
    kinds = [k for k, _ in pattern]
    assert kinds.count("attn") == 1 and kinds[4] == "attn"   # 1:7 interleave
    ffns = [f for _, f in pattern]
    assert ffns.count("moe") == 4                            # MoE every other
    assert num_groups(cfg) == 9


def test_shapes_table():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288


def test_smoke_configs_reduced():
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        assert cfg.d_model <= 512
        assert cfg.num_layers <= 8
        assert cfg.num_experts <= 4


def test_sub_quadratic_census():
    subq = {a for a in ARCH_IDS if get_config(a).sub_quadratic}
    assert subq == {"mamba2-370m", "jamba-1.5-large-398b", "h2o-danube-1.8b"}


def test_depth_cut_keeps_published_widths():
    """``launch.serve.executing_config``: the smoke preset without a layer
    count; otherwise the registry config at its published widths with only
    the depth cut, in whole layer-pattern periods."""
    from dataclasses import replace

    from repro.launch.serve import executing_config

    assert executing_config("mixtral-8x7b") == get_config("mixtral-8x7b",
                                                          smoke=True)
    full = get_config("mixtral-8x7b")
    assert executing_config("mixtral-8x7b", 4) == replace(full, num_layers=4)
    with pytest.raises(ValueError, match="multiple of 8"):
        executing_config("jamba-1.5-large-398b", 4)
    with pytest.raises(ValueError):
        executing_config("mixtral-8x7b", 33)
