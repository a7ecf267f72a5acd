"""Program spans (``repro.analysis.spans``): nothing is built with tracing
off; with it on, a served run's profile holds the scheduler, engine,
transfer and stream-wait spans with their stats; the prefill expert
counters; the KV-page window's own tag; the launcher's ``--trace-dir``."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import analysis
from repro.analysis import runtime, spans
from repro.configs import get_config
from repro.core.dag_builder import Plan
from repro.models import model as M
from repro.serving.scheduler import serve_dataset
from repro.serving.server import Request
from repro.serving.weights import StreamWindow

KEY = jax.random.PRNGKey(0)


class _Counting:
    """Stands in for ``jax.profiler.TraceAnnotation``; records each one
    built."""

    made = []

    def __init__(self, name, **stats):
        _Counting.made.append((name, stats))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counting(monkeypatch):
    _Counting.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    return _Counting.made


def test_span_off_builds_no_annotation(counting):
    a = spans.span("step", step=3)
    b = spans.span("xfer", tag="token-readback", key=(1, 2))
    assert a is b                     # the one shared null context
    with a, b, runtime.allowed("token-readback"):
        pass
    assert counting == []


def test_span_on_names_and_stats(counting):
    with analysis.tracing():
        with spans.span("engine.layer", layer=2, phase="decode"):
            pass
        with spans.span("xfer", tag="expert-prefetch", key=(1, 2),
                        bytes=5):
            pass
        with analysis.tracing(False):
            spans.span("step")
    assert spans.span("step") is spans.span("admit")   # off again
    assert counting == [
        ("moegen.engine.layer", {"layer": 2, "phase": "decode"}),
        ("moegen.xfer", {"tag": "expert-prefetch", "key": "(1, 2)",
                         "bytes": 5}),
    ]


def test_allowed_is_an_xfer_span_with_or_without_sanitizer(counting):
    with analysis.tracing():
        with runtime.allowed("token-readback"):
            pass
        with analysis.sanitize(strict=True) as san:
            with runtime.allowed("stream-window", key=4):
                pass
    assert counting == [("moegen.xfer", {"tag": "token-readback"}),
                        ("moegen.xfer", {"tag": "stream-window", "key": 4})]
    assert san.report()["planned_transfers"] == {"stream-window": 1}


def test_stream_window_waits_carry_bytes_and_demand(counting):
    win = StreamWindow(lambda k: (jax.numpy.full((4,), k), 16 * k),
                       tag="stream-window")
    with analysis.tracing():
        win.prefetch(1)
        win.acquire(1)
        win.acquire(2)                # never staged: fetched on demand
    waits = [s for n, s in counting if n == "moegen.stream.wait"]
    issues = [s for n, s in counting if n == "moegen.xfer"]
    assert waits == [
        {"tag": "stream-window", "key": 1, "bytes": 16, "demand": 0},
        {"tag": "stream-window", "key": 2, "bytes": 32, "demand": 1}]
    assert issues == [{"tag": "stream-window", "key": 1},
                      {"tag": "stream-window", "key": 2}]
    assert win.htod_bytes == 48


def _events(root):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith(spans.PREFIX)]
    return out


def test_traced_served_run_holds_program_spans(tmp_path):
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    prompts = [np.arange(1, 9 + i) % cfg.vocab_size for i in range(4)]
    reqs = [Request(p, 4) for p in prompts]
    plan = Plan(B=4, b_a=2, b_e=8, omega=0.0)
    with jax.profiler.trace(str(tmp_path)), analysis.tracing():
        serve_dataset(cfg, params, reqs, plan, 4, stream_weights=True,
                      resident_bytes=0.0)
    ev = _events(str(tmp_path))
    names = {n for n, _ in ev}
    for want in ("step", "admit", "prefill", "decode", "emit",
                 "engine.layer", "sample", "xfer", "stream.wait"):
        assert spans.PREFIX + want in names, want
    tags = {s["tag"] for n, s in ev if n == "moegen.xfer"}
    assert {"token-readback", "stream-window"} <= tags
    # single-device prefill reads no capacity back (a mesh engine does)
    assert "prefill-capacity-probe" not in tags
    waits = [s for n, s in ev if n == "moegen.stream.wait"]
    assert waits and all(s["bytes"] > 0 and s["tag"] == "stream-window"
                         for s in waits)
    phases = {s["phase"] for n, s in ev if n == "moegen.engine.layer"}
    assert phases == {"prefill", "decode"}
    (pf,) = [s for n, s in ev if n == "moegen.prefill"]
    assert pf == {"rows": 4, "tokens": sum(len(p) for p in prompts)}


def test_untraced_run_records_no_program_spans(tmp_path):
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = [Request(np.arange(1, 9), 3) for _ in range(2)]
    with jax.profiler.trace(str(tmp_path)):
        serve_dataset(cfg, params, reqs, Plan(B=2, b_a=2, b_e=8, omega=0.0),
                      3)
    assert _events(str(tmp_path)) == []


def test_prefill_expert_counters_count_real_prompt_tokens():
    """Expert rows are what the ragged prefill kernel computed, whole row
    tiles per MoE layer per micro-batch; routed copies are top-k x the
    real (unpadded) prompt tokens, so the rows' rounding to tiles shows
    as prefill's expert padding."""
    from repro.models import moe as moe_mod

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    lens = [5, 9, 12, 7]
    reqs = [Request(np.arange(1, n + 1), 3) for n in lens]
    rep = serve_dataset(cfg, params, reqs, Plan(B=4, b_a=2, b_e=8, omega=0.0),
                        3)
    n_moe = sum(1 for _, ffn in M.layer_pattern(cfg) if ffn == "moe") \
        * M.num_groups(cfg)
    assert rep.prefill_routed_copies == \
        cfg.experts_per_token * sum(lens) * n_moe
    # micro-batches of 2 prompts padded to the longest, 12 tokens
    tm = moe_mod.ragged_tile(2 * 12, cfg.experts_per_token, cfg.num_experts)
    assert rep.prefill_expert_rows >= rep.prefill_routed_copies
    assert rep.prefill_expert_rows % tm == 0


def test_kv_page_window_has_its_own_tag():
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, KEY)
    reqs = [Request(np.arange(1, 9), 5) for _ in range(4)]
    with analysis.sanitize(strict=True) as san:
        rep = serve_dataset(cfg, params, reqs,
                            Plan(B=4, b_a=2, b_e=8, omega=0.0), 5,
                            scheduler="continuous", kv_page_tokens=4,
                            device_kv_gb=1e-9)
    planned = san.report()["planned_transfers"]
    assert planned.get("kv-pages", 0) > 0 and rep.kv_htod_bytes > 0
    assert "stream-window" not in planned     # no weight is streamed


def test_launcher_trace_dir_writes_program_spans(tmp_path, capsys):
    from repro.launch import serve

    serve.main(["--arch", "mixtral-8x7b", "--requests", "2",
                "--prompt-len", "8", "--decode-len", "3", "--batch", "2",
                "--trace-dir", str(tmp_path)])
    assert "moegen.* spans written" in capsys.readouterr().out
    names = {n for n, _ in _events(str(tmp_path))}
    assert {"moegen.step", "moegen.decode", "moegen.xfer"} <= names
    assert spans.span("step") is spans.span("decode")   # off after the run
