"""The profiler trace of a run, reduced to the numbers the metrics read.

``capture`` records JAX's profiler over the window; ``load`` turns the
``.xplane.pb`` it writes into plain events; ``reduce`` computes, over the
window, per device:

* busy time: the union of the intervals in which an operation ran;
* time by program (XLA module) and by operation;
* time of named kernels, and the calls each made;
* collective time with no other operation under it (exposed);
* idle gaps, each attributed to the benchmark's host span (``bench.*``
  ``TraceAnnotation``) that covers most of it.

Device timestamps and host spans share the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|send|recv", re.I)


@dataclass
class Ev:
    name: str
    start: float            # ns
    end: float              # ns
    module: str = ""


@dataclass
class Trace:
    """Plain events of one trace: per device its operations and its
    programs, and the host's ``bench.*`` spans."""

    ops: Dict[str, List[Ev]] = field(default_factory=dict)
    modules: Dict[str, List[Ev]] = field(default_factory=dict)
    spans: List[Ev] = field(default_factory=list)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[plane.name] = [
                        Ev(op_name(e.name), e.start_ns, e.end_ns,
                           _program(_stat(e, "hlo_module")))
                        for e in line.events
                        if _family(op_name(e.name)) not in CONTAINERS]
                elif line.name == "XLA Modules":
                    tr.modules[plane.name] = [
                        Ev(_program(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        tr.spans.append(Ev(e.name, e.start_ns, e.end_ns))
    return tr


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b):
    """Intervals of ``a`` not covered by ``b`` (both unions)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(text: str) -> str:
    """The instruction name of a device operation event, whose name is the
    HLO instruction's text (``%expert_ffn.28 = bf16[...] custom-call(...)``
    -> ``expert_ffn.28``)."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _family(name: str) -> str:
    """An instruction name without its instance number (``fusion.12`` ->
    ``fusion``)."""
    return re.sub(r"[.\d]+$", "", name) or name


def _program(name: str) -> str:
    """A program's name without the hash the profiler appends
    (``jit_f(123)`` -> ``jit_f``)."""
    return re.sub(r"\(\d+\)$", "", name)


# Control flow around other operations: counted in busy time through the
# operations inside it, never as an operation of its own.
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    module_s: Dict[str, float]           # program -> device seconds
    op_s: Dict[str, float]               # "program:op" -> device seconds
    kernel_s: Dict[str, float]           # kernel -> device seconds
    kernel_calls: Dict[str, int]
    kernel_s_by_module: Dict[Tuple[str, str], float]
    kernel_calls_by_module: Dict[Tuple[str, str], int]
    exposed_collective_s: float
    idle_by_host: Dict[str, float]
    devices: int

    def breakdown(self, n: int = 10) -> Dict[str, List]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(tr: Trace, kernels: Sequence[str] = ()) -> Reduced:
    """Reduce ``tr`` over the window: the ``bench.window`` host span, else
    the first to the last device event.  ``kernels`` are the instruction
    names of the kernels to time (``expert_ffn`` matches ``expert_ffn.28``)."""
    win = [s for s in tr.spans if s.name == "bench.window"]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        evs = [e for v in tr.ops.values() for e in v]
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    module_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    k_mod_s: Dict[Tuple[str, str], float] = defaultdict(float)
    k_mod_n: Dict[Tuple[str, str], int] = defaultdict(int)
    busy_total, exposed_total = 0.0, 0.0
    idle: Dict[str, float] = defaultdict(float)
    items = sorted(((s.start, s.end, s.name) for s in tr.spans
                    if s.name != "bench.window"), key=lambda t: t[0])
    spans = ([t[0] for t in items], items,
             max((e - s for s, e, _ in items), default=0.0))
    ndev = max(1, len(tr.ops))
    for dev, ops in tr.ops.items():
        mods = sorted(tr.modules.get(dev, []), key=lambda e: e.start)
        starts = [m.start for m in mods]
        inside = [e for e in ops if e.end > lo and e.start < hi]
        busy = _clip(union([(e.start, e.end) for e in inside]), lo, hi)
        busy_total += _length(busy)
        coll = union([(e.start, e.end) for e in inside
                      if COLLECTIVE.search(e.name)])
        other = union([(e.start, e.end) for e in inside
                       if not COLLECTIVE.search(e.name)])
        exposed_total += _length(_clip(_minus(coll, other), lo, hi))
        for e in inside:
            d = min(e.end, hi) - max(e.start, lo)
            mod = e.module or _enclosing(mods, starts, e)
            module_s[mod] += d
            op_s[f"{mod}:{_family(e.name)}"] += d
            for k in kernels:
                if _family(e.name) == k:
                    kernel_s[k] += d
                    kernel_calls[k] += 1
                    k_mod_s[(k, mod)] += d
                    k_mod_n[(k, mod)] += 1
        gaps = _minus([(lo, hi)], busy)
        for g0, g1 in gaps:
            idle[_host_label(spans, g0, g1)] += g1 - g0
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy_total / ndev * ns,
        module_s={k: v * ns for k, v in module_s.items()},
        op_s={k: v * ns for k, v in op_s.items()},
        kernel_s={k: v * ns for k, v in kernel_s.items()},
        kernel_calls=dict(kernel_calls),
        kernel_s_by_module={k: v * ns for k, v in k_mod_s.items()},
        kernel_calls_by_module=dict(k_mod_n),
        exposed_collective_s=exposed_total / ndev * ns,
        idle_by_host={k: v / ndev * ns for k, v in idle.items()},
        devices=ndev,
    )


def _enclosing(mods: List[Ev], starts: List[float], e: Ev) -> str:
    i = bisect.bisect_right(starts, e.start) - 1
    if i >= 0 and mods[i].end >= e.end:
        return mods[i].name
    return "?"


def _host_label(spans, g0: float, g1: float) -> str:
    """The innermost (shortest) ``bench.*`` span overlapping the gap most.
    ``spans`` is (starts, [(start, end, name)] by start, longest span)."""
    starts, items, longest = spans
    best, best_key = "host:none", (0.0, 0.0)
    for s, e, name in items[bisect.bisect_left(starts, g0 - longest):]:
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


class Capture:
    """Record the profiler over a block; ``result`` is the reduced trace.
    The trace is written under ``directory`` and removed once read."""

    def __init__(self, directory: str, kernels: Sequence[str]) -> None:
        self.directory = directory
        self.kernels = kernels
        self.result: Optional[Reduced] = None
        self.raw: Optional[Trace] = None

    def __enter__(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        if exc[0] is not None:
            return False
        paths = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.raw = load(paths[0])
        self.result = reduce(self.raw, self.kernels)
        shutil.rmtree(self.directory, ignore_errors=True)
        return False
