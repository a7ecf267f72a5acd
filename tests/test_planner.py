"""Batching planner + DAG cost model: constraints and paper-claim directions.

(Property-based variants live in test_properties.py, the only module allowed
to import hypothesis.)
"""
import pytest

from repro.configs import get_config
from repro.core import baselines, planner, workload as W
from repro.core.dag import JobDag
from repro.core.dag_builder import Plan, estimate_decode, estimate_prefill
from repro.core.hardware import A5000_C2, A6000_C3

CTX = 768


def test_host_memory_limit_eq2():
    cfg = get_config("mixtral-8x7b")
    B_max = planner.host_batch_limit(cfg, A5000_C2, CTX)
    used = B_max * W.kv_bytes_per_seq(cfg, CTX) + W.model_bytes(cfg)
    assert used <= A5000_C2.host_mem_bytes
    # one more sequence would overflow
    over = (B_max + 2) * W.kv_bytes_per_seq(cfg, CTX) + W.model_bytes(cfg)
    assert over > A5000_C2.host_mem_bytes


def test_device_memory_constraint_eq3():
    cfg = get_config("mixtral-8x7b")
    res = planner.search_decode(cfg, A5000_C2, CTX)
    assert planner.device_memory_ok(cfg, A5000_C2, res.plan, CTX, "decode")


def test_module_batching_beats_model_based_decode():
    """The paper's headline: 8-31x decode throughput over model-based."""
    cfg = get_config("mixtral-8x7b")
    ours = planner.search_decode(cfg, A5000_C2, CTX).estimate.throughput
    for system in ("deepspeed", "flexgen", "moe-lightning", "vllm"):
        base = baselines.estimate_baseline_decode(
            cfg, A5000_C2, CTX, system
        ).throughput
        assert ours > 3 * base, (system, ours, base)
    ds = baselines.estimate_baseline_decode(cfg, A5000_C2, CTX, "deepspeed")
    assert ours / ds.throughput > 5     # paper Table 6: 17x for Mixtral-8x22B-class


def test_prefill_gain_grows_with_sparsity():
    """Paper Table 7: gains are larger for sparser MoE (olmoe 64e-top8 vs
    mixtral 8e-top2)."""
    gain = {}
    for arch in ("mixtral-8x7b", "olmoe-1b-7b"):
        cfg = get_config(arch)
        ours = planner.search_prefill(cfg, A5000_C2, 512).estimate.throughput
        base = baselines.estimate_baseline_prefill(
            cfg, A5000_C2, 512, "deepspeed"
        ).throughput
        gain[arch] = ours / base
    assert gain["olmoe-1b-7b"] >= gain["mixtral-8x7b"]


def test_weak_cpu_lowers_omega():
    """Paper Table 10: C3's weak host drives the split toward the GPU."""
    cfg = get_config("mixtral-8x7b")
    w_c2 = planner.search_decode(cfg, A5000_C2, CTX).plan.omega
    w_c3 = planner.search_decode(cfg, A6000_C3, CTX).plan.omega
    assert w_c3 <= w_c2


def test_decode_B_set_to_host_max():
    cfg = get_config("mixtral-8x7b")
    res = planner.search_decode(cfg, A5000_C2, CTX)
    assert res.plan.B == planner.host_batch_limit(cfg, A5000_C2, CTX)


def test_full_kv_offload_reduces_fetch_traffic():
    """Paper Fig. 4: offloading KV enables batches that amortize weights."""
    cfg = get_config("mixtral-8x7b")
    ours = planner.search_decode(cfg, A5000_C2, CTX)
    base = baselines.estimate_baseline_decode(cfg, A5000_C2, CTX, "deepspeed")
    ours_per_tok = ours.estimate.htod_bytes / ours.estimate.tokens
    base_per_tok = base.htod_bytes / base.tokens
    assert ours_per_tok < base_per_tok / 4


# ---------------------------------------------------------------------------
# DAG properties
# ---------------------------------------------------------------------------
def test_dag_critical_path_simple():
    dag = JobDag()
    a = dag.add("copy", "htod", 2.0)
    b = dag.add("compute", "gpu", 1.0, deps=[a])
    dag.add("copy2", "htod", 0.5)          # overlaps with compute
    assert dag.earliest_finish() == pytest.approx(3.0)
    assert dag.critical_path()[-1] == "compute"


def test_dag_channel_serialization():
    dag = JobDag()
    dag.add("c1", "htod", 1.0)
    dag.add("c2", "htod", 1.0)             # same channel: serializes
    assert dag.earliest_finish() == pytest.approx(2.0)


def test_decode_capacity_never_below_balanced_load():
    """b_e is a per-expert capacity: the search never under-provisions it
    below the balanced per-expert token load (drops would be invisible to
    the throughput objective)."""
    cfg = get_config("mixtral-8x7b")
    res = planner.search_decode(cfg, A5000_C2, CTX)
    per_e = -(-res.plan.B * cfg.experts_per_token // cfg.num_experts)
    assert res.plan.b_e >= per_e
    assert res.plan.b_e <= res.plan.B


def test_expert_buffer_term_in_eq3():
    """The grouped (E, C, D) dispatch buffer is charged against Eq. 3:
    larger capacities consume strictly more device memory."""
    cfg = get_config("mixtral-8x7b")
    lo = Plan(B=4096, b_a=32, b_e=512, omega=0.0)
    hi = Plan(B=4096, b_a=32, b_e=4096, omega=0.0)
    used_lo = planner.device_memory_used(cfg, lo, CTX, "decode")
    used_hi = planner.device_memory_used(cfg, hi, CTX, "decode")
    assert used_hi - used_lo == pytest.approx(
        W.expert_buffer_bytes(cfg, 4096) - W.expert_buffer_bytes(cfg, 512)
    )


def test_profile_for_device_by_kind():
    """On a TPU the planner's profile is looked up by ``device_kind``; an
    unknown kind is an error, not a default."""
    from types import SimpleNamespace

    from repro.core.hardware import TPU_V5E, profile_for_device

    assert profile_for_device(SimpleNamespace(device_kind="TPU v5 lite")) \
        is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        profile_for_device(SimpleNamespace(device_kind="TPU v9"))
