import pytest

from bench import trace
from bench.trace import Ev, Trace

MS = 1_000_000  # ns


def _synth():
    """One device, a 100 ms window: a decode program 0-40 ms holding a
    kernel 10-30 ms and an all-to-all 30-45 ms (5 ms of it exposed), a
    prefill program 60-80 ms; the host waits on weights 45-60 ms and
    prepares inputs 80-100 ms."""
    dev = "/device:TPU:0"
    tr = Trace()
    tr.ops[dev] = [
        Ev("fusion.1", 0 * MS, 10 * MS, "jit__fused_decode_chunk"),
        Ev("expert_ffn.28", 10 * MS, 30 * MS, "jit__fused_decode_chunk"),
        Ev("fusion.2", 30 * MS, 40 * MS, "jit__fused_decode_chunk"),
        Ev("all-to-all.3", 30 * MS, 45 * MS, "jit__fused_decode_chunk"),
        Ev("expert_ffn.1", 60 * MS, 70 * MS, "jit__prefill_moe_ffn_module"),
        Ev("fusion.7", 70 * MS, 80 * MS, ""),
    ]
    tr.modules[dev] = [
        Ev("jit__fused_decode_chunk", 0, 45 * MS),
        Ev("jit__prefill_moe_ffn_module", 60 * MS, 80 * MS),
    ]
    tr.spans = [
        Ev("bench.window", 0, 100 * MS),
        Ev("bench.step", 0, 100 * MS),
        Ev("bench.weights.acquire", 44 * MS, 61 * MS),
        Ev("bench.sampler.sample", 80 * MS, 100 * MS),
    ]
    return tr


def test_busy_is_the_union_of_operations():
    r = trace.reduce(_synth(), kernels=("expert_ffn",))
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.065)       # 0-45 and 60-80 ms
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.35)


def test_kernel_time_by_program():
    r = trace.reduce(_synth(), kernels=("expert_ffn",))
    assert r.kernel_s["expert_ffn"] == pytest.approx(0.030)
    assert r.kernel_calls["expert_ffn"] == 2
    assert r.kernel_s_by_module[("expert_ffn", "jit__fused_decode_chunk")] \
        == pytest.approx(0.020)
    # an operation with no module stat takes the program around it
    assert r.module_s["jit__prefill_moe_ffn_module"] == pytest.approx(0.020)


def test_exposed_collective_is_what_no_compute_covers():
    r = trace.reduce(_synth())
    assert r.exposed_collective_s == pytest.approx(0.005)


def test_idle_gaps_go_to_the_innermost_host_span():
    r = trace.reduce(_synth())
    assert r.idle_by_host["bench.weights.acquire"] == pytest.approx(0.015)
    assert r.idle_by_host["bench.sampler.sample"] == pytest.approx(0.020)
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["bench.sampler.sample", pytest.approx(0.02)]
    assert len(b["device_ops"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert dict(b["device_ops"])["jit__fused_decode_chunk:expert_ffn"] == \
        pytest.approx(0.020)


def test_window_defaults_to_the_device_span_without_host_spans():
    tr = _synth()
    tr.spans = []
    r = trace.reduce(tr)
    assert r.window_s == pytest.approx(0.080)
    assert r.idle_by_host == {"host:none": pytest.approx(0.015)}


def test_two_devices_average():
    tr = _synth()
    tr.ops["/device:TPU:1"] = [Ev("fusion.1", 0, 100 * MS, "m")]
    r = trace.reduce(tr)
    assert r.devices == 2
    assert r.busy_s == pytest.approx((0.065 + 0.1) / 2)


def test_op_and_program_names_from_the_profiler():
    text = ("%expert_ffn.28 = bf16[8,128,4096]{2,1,0} custom-call("
            "bf16[8,128,4096] %pad.202), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "expert_ffn.28"
    assert trace.op_name("%fusion.3 = bf16[8] fusion(%expert_ffn.1)") \
        == "fusion.3"
    assert trace._program("jit__fused_decode_chunk(2888199309506651467)") \
        == "jit__fused_decode_chunk"


def test_union_and_minus():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
