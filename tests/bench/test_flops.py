import importlib.util
import os

import pytest

from tiny_bench import MIXTRAL, ROOT

MIXTRAL_4L = MIXTRAL.Dims(layers=4, d=4096, heads=32, kv_heads=8,
                          head_dim=128, d_ff=14336, experts=8, top_k=2,
                          vocab=32000, eps=1e-5, theta=1e6)


def reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_mixtral_token_is_twice_its_active_parameters():
    # 1.708 G active parameters at 4 layers (routed experts, attention,
    # router, head; not the embedding lookup), 2 FLOPs each
    per_layer = (4096 * 4096 * 2 + 2 * 4096 * 1024 + 4096 * 8
                 + 2 * 3 * 4096 * 14336)
    active = 4 * per_layer + 4096 * 32000
    assert active == 1_708_261_376
    no_context = (MIXTRAL.decode_flops(MIXTRAL_4L, 0)
                  - 4 * MIXTRAL.attention_flops(MIXTRAL_4L, 1))
    assert no_context == 2 * active == 3_416_522_752


def test_attention_grows_with_the_context():
    d0 = MIXTRAL.decode_flops(MIXTRAL_4L, 0)
    d9 = MIXTRAL.decode_flops(MIXTRAL_4L, 1023)
    assert d9 - d0 == 4 * 4 * 1023 * 32 * 128


def test_prefill_is_its_tokens_and_one_head():
    n = 300
    per = [MIXTRAL.token_flops(MIXTRAL_4L, p + 1) for p in range(n)]
    assert MIXTRAL.prefill_flops(MIXTRAL_4L, n) == (
        sum(per) + MIXTRAL.head_flops(MIXTRAL_4L))


def test_expert_ffn_work_counts_routed_copies_only():
    fl, by = MIXTRAL.expert_ffn_work(MIXTRAL_4L, copies=128, experts_hit=8)
    assert fl == 128 * 6 * 4096 * 14336
    assert by == 8 * 3 * 4096 * 14336 * 2 + 128 * 2 * 4096 * 2


def _trace(kernel_s, calls, module="jit__fused_decode_chunk"):
    class T:
        pass

    t = T()
    t.kernel_s_by_module = {("expert_ffn", module): kernel_s}
    t.kernel_calls_by_module = {("expert_ffn", module): calls}
    return t


def _ctx(batch, capacity, trace=None, **kw):
    ctx = {"dims": MIXTRAL_4L, "batch": batch, "capacity": capacity,
           "experts": 8, "top_k": 2, "kernel": "expert_ffn",
           "expert_ffn_work": MIXTRAL.expert_ffn_work,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": trace, "chips": 1}
    ctx.update(kw)
    return ctx


def test_roofline_ignores_capacity_and_padding():
    """The kernel is judged on the copies routed to it, so a buffer
    padded to any capacity reads the same share for the same time."""
    read = reader("expert_ffn_roofline")
    tr = _trace(kernel_s=0.01, calls=2)
    a = read(_ctx(64, 64, tr))
    b = read(_ctx(64, 4096, tr))
    assert a == b
    fl, by = MIXTRAL.expert_ffn_work(MIXTRAL_4L, 128, 8)
    t_min = max(fl / 197e12, by / 819e9)
    assert a == pytest.approx(100 * 2 * t_min / 0.01)
    assert by / 819e9 > fl / 197e12          # decode is bound by bytes


def test_roofline_leaves_prefill_calls_out_and_reads_nothing_without():
    read = reader("expert_ffn_roofline")
    assert read(_ctx(64, 64, _trace(0.01, 2, "jit__prefill_moe_ffn"))) is None
    assert read(_ctx(64, 64, None)) is None


def test_mfu_counts_the_work_not_the_padding():
    """Window FLOPs come from each request's own prompt and positions:
    the batch's padded prompt length and dead slots never enter."""
    from bench.harness import window_flops

    class H:
        def __init__(self, i, n):
            self.index, self.prompt = i, [0] * n

    hs = [H(0, 100), H(1, 1000)]
    served = {0: [1] * 5, 1: [1] * 3}
    tok0 = {0: 0, 1: 1}
    got = window_flops(MIXTRAL, MIXTRAL_4L, hs, served, tok0)
    want = (MIXTRAL.prefill_flops(MIXTRAL_4L, 100)
            + sum(MIXTRAL.decode_flops(MIXTRAL_4L, 100 + i - 1)
                  for i in range(1, 5))
            + sum(MIXTRAL.decode_flops(MIXTRAL_4L, 1000 + i - 1)
                  for i in range(1, 3)))
    assert got == want
    read = reader("mfu_pct")
    assert read(_ctx(64, 64, work_flops=got, seconds=2.0)) == pytest.approx(
        100 * got / 2.0 / 197e12)
    assert read(_ctx(64, 64, work_flops=got, seconds=2.0, peaks=None)) is None


def test_counter_readers():
    delta = {"slot_steps": 640, "wasted_slot_steps": 160, "htod_bytes": 0}
    ctx = _ctx(64, 64, delta=delta, seconds=4.0)
    assert reader("occupancy_pct")(ctx) == pytest.approx(75.0)
    # 10 ticks x 8 experts x 64 rows; 480 live slot-steps x 2 copies
    assert reader("decode_expert_pad_pct")(ctx) == pytest.approx(
        100 * (1 - 960 / 5120))
    assert reader("htod_gbs")(ctx) is None
    ctx["delta"]["htod_bytes"] = 8e9
    assert reader("htod_gbs")(ctx) == pytest.approx(2.0)
