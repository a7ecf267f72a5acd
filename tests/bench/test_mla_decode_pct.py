"""The ``mla_decode_pct`` reader on a synthetic reduced trace: the share
of busy time the absorbed latent-attention decode kernel takes outside
the prefill programs, and None where there is nothing to read."""
import os

import pytest

from bench import trace
from bench.manifest import load_reader
from bench.trace import Ev, Trace
from tiny_bench import ROOT

MS = 1_000_000  # ns
READ = load_reader(os.path.join(ROOT, "bench"), "mla_decode_pct")


def _synth(kernel="mla_decode"):
    """One device, a 100 ms window: a fused decode chunk 0-50 ms holding
    two MLA decode kernels (5 + 10 ms) and an expert kernel, a prefill
    program 60-80 ms (one more MLA decode kernel there, which the reader
    leaves out)."""
    dev = "/device:TPU:0"
    tr = Trace()
    tr.ops[dev] = [
        Ev(f"{kernel}.3", 0, 5 * MS, "jit__fused_decode_chunk"),
        Ev("expert_ffn.28", 5 * MS, 30 * MS, "jit__fused_decode_chunk"),
        Ev(f"{kernel}.4", 30 * MS, 40 * MS, "jit__fused_decode_chunk"),
        Ev("fusion.2", 40 * MS, 50 * MS, "jit__fused_decode_chunk"),
        Ev(f"{kernel}.1", 60 * MS, 70 * MS, "jit__prefill_layer_module"),
        Ev("fusion.7", 70 * MS, 80 * MS, "jit__prefill_layer_module"),
    ]
    tr.modules[dev] = [Ev("jit__fused_decode_chunk", 0, 50 * MS),
                       Ev("jit__prefill_layer_module", 60 * MS, 80 * MS)]
    tr.spans = [Ev("bench.window", 0, 100 * MS)]
    return trace.reduce(tr)


def test_none_without_a_trace():
    assert READ({"trace": None}) is None


def test_share_of_busy_time_outside_prefill():
    r = _synth()
    assert r.busy_s == pytest.approx(0.070)
    assert READ({"trace": r}) == pytest.approx(100 * 0.015 / 0.070)


def test_none_where_the_trace_holds_no_such_kernel():
    assert READ({"trace": _synth("flash_decode")}) is None
