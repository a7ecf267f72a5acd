import json

import pytest

from bench import program_spans as ps
from bench import trace
from bench.program_spans import Span
from bench.trace import Ev, Trace

MS = 1_000_000  # ns


def _synth():
    """One device, a 100 ms window, busy 0-20, 50-60 and 90-100 ms.  The
    program's step covers 0-100 ms; a decode layer 10-65 ms holds the
    weight copy of key 1 (issued 22-30 ms) and the wait for it (35-55 ms);
    the token readback runs 70-85 ms and the emit 85-95 ms."""
    dev = "/device:TPU:0"
    tr = Trace()
    tr.ops[dev] = [Ev("fusion.1", 0, 20 * MS, "jit_a"),
                   Ev("expert_ffn.2", 50 * MS, 60 * MS, "jit_a"),
                   Ev("fusion.3", 90 * MS, 100 * MS, "jit_b")]
    tr.modules[dev] = [Ev("jit_a", 0, 60 * MS), Ev("jit_b", 90 * MS, 100 * MS)]
    tr.spans = [Ev("bench.window", 0, 100 * MS),
                Ev("bench.step", 0, 100 * MS),
                Ev("bench.weights.acquire", 34 * MS, 56 * MS)]
    spans = [
        Span("step", 0, 100 * MS, {"step": 0}),
        Span("engine.layer", 10 * MS, 65 * MS, {"layer": 0}),
        Span("xfer", 22 * MS, 30 * MS, {"tag": "stream-window", "key": 1}),
        Span("stream.wait", 35 * MS, 55 * MS,
             {"tag": "stream-window", "key": 1, "bytes": 4_000_000,
              "demand": 0}),
        Span("xfer", 70 * MS, 85 * MS, {"tag": "token-readback"}),
        Span("emit", 85 * MS, 95 * MS, {"T": 1}),
    ]
    return tr, spans


def test_bench_reduction_is_untouched_by_program_spans(tmp_path):
    """``bench.trace`` reads only ``bench.*`` host spans: a profile that
    also holds the program's spans loads and reduces as one without."""
    import jax
    import jax.numpy as jnp

    from repro import analysis
    from repro.analysis import spans

    def profile(d, on):
        with jax.profiler.trace(str(d)):
            with jax.profiler.TraceAnnotation("bench.window"), \
                    analysis.tracing(on):
                with spans.span("step", step=0):
                    with analysis.allowed("token-readback"):
                        jnp.ones(8).block_until_ready()
        (path,) = list(d.glob("plugins/profile/*/*.xplane.pb"))
        return str(path)

    with_spans = profile(tmp_path / "on", True)
    without = profile(tmp_path / "off", False)
    assert [s.name for s in trace.load(with_spans).spans] == ["bench.window"]
    assert [s.name for s in trace.load(without).spans] == ["bench.window"]
    assert {s.key for s in ps.load_spans(with_spans)} == \
        {"step", "xfer:token-readback"}
    assert ps.load_spans(without) == []


def test_bench_idle_gaps_give_each_gap_whole_to_the_outer_span():
    """The bias the program spans' reduction avoids: ``bench.trace`` gives
    each gap to the ``bench.*`` span overlapping it most, so the 15 ms
    spent waiting for the copy inside ``bench.weights.acquire`` goes to
    ``bench.step``."""
    tr, spans = _synth()
    assert trace.reduce(tr).idle_by_host == \
        {"bench.step": pytest.approx(0.060)}
    assert ps.reduce(tr, spans).idle_by_span["stream.wait:stream-window"] \
        == pytest.approx(0.015)


def test_gap_crossing_an_inner_edge_is_split():
    tr, spans = _synth()
    r = ps.reduce(tr, spans)
    by = r.idle_by_span
    # idle 20-50: layer 20-22 and 30-35, copy issue 22-30, wait 35-50
    assert by["xfer:stream-window"] == pytest.approx(0.008)
    assert by["stream.wait:stream-window"] == pytest.approx(0.015)
    # idle 60-90: layer 60-65, step 65-70, readback 70-85, emit 85-90
    assert by["engine.layer"] == pytest.approx(0.002 + 0.005 + 0.005)
    assert by["step"] == pytest.approx(0.005)
    assert by["xfer:token-readback"] == pytest.approx(0.015)
    assert by["emit"] == pytest.approx(0.005)
    assert "none" not in by
    assert sum(by.values()) == pytest.approx(r.idle_s)
    assert r.idle_s == pytest.approx(0.060)


def test_idle_under_no_program_span_is_none():
    tr, spans = _synth()
    spans = [s for s in spans if s.name != "step"]
    r = ps.reduce(tr, spans)
    assert r.idle_by_span["none"] == pytest.approx(0.005)    # 65-70 ms
    secs = [v for _, v in r.top()]
    assert secs == sorted(secs, reverse=True) and len(secs) == 6


def test_copies_pair_issue_to_wait_and_the_parts_sum_to_idle():
    tr, spans = _synth()
    spans += [   # key 2: issued 40-45, waited 60-65; key 3 never waited
        Span("xfer", 40 * MS, 45 * MS, {"tag": "expert-prefetch",
                                         "key": "(0, 2)"}),
        Span("stream.wait", 60 * MS, 65 * MS,
             {"tag": "expert-prefetch", "key": "(0, 2)", "bytes": 1_000_000,
              "demand": 0}),
        Span("xfer", 66 * MS, 67 * MS, {"tag": "stream-window", "key": 3}),
    ]
    r = ps.reduce(tr, spans)
    assert r.stream_copies == 2
    assert r.stream_bytes == 5_000_000
    assert r.stream_link_s == pytest.approx(0.043)    # 22-55 and 40-65 ms
    sh = ps.shares(r)
    parts = ("stream_issue_idle_pct", "stream_wait_idle_pct",
             "host_idle_pct", "none_idle_pct")
    assert sum(sh[k] for k in parts) == pytest.approx(sh["idle_pct"])
    assert sh["idle_pct"] == pytest.approx(60.0)
    assert sh["htod_link_gbs"] == pytest.approx(5e6 / 0.043 / 1e9)
    assert sh["stream_span_gbs"] == pytest.approx(5e6 / 0.1 / 1e9)


def test_a_copy_outside_the_window_is_not_counted():
    tr, spans = _synth()
    r = ps.reduce(tr, spans, window=(25 * MS, 100 * MS))
    assert r.stream_bytes == 0 and r.stream_link_s == 0
    assert ps.shares(r)["htod_link_gbs"] is None


def test_nothing_to_read_gives_nothing():
    r = ps.Reduced(window_s=0.0, idle_s=0.0, idle_by_span={},
                   stream_bytes=0, stream_link_s=0.0, stream_copies=0,
                   devices=1)
    assert ps.shares(r) == {}
    tr, spans = _synth()
    tr.ops = {}
    r = ps.reduce(tr, spans)
    assert r.idle_by_span == {} and ps.shares(r)["idle_pct"] == 0.0


def test_command_line_reads_a_launcher_profile(tmp_path, capsys):
    from repro.launch import serve

    serve.main(["--arch", "mixtral-8x7b", "--requests", "2",
                "--prompt-len", "8", "--decode-len", "3", "--batch", "2",
                "--stream-weights",
                "--trace-dir", str(tmp_path / "p")])
    capsys.readouterr()
    assert ps.main([str(tmp_path / "p")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["stream_copies"] > 0 and out["stream_bytes"] > 0
    assert ps.main([str(tmp_path / "empty")]) == 1
