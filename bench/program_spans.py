"""The program's own host spans in a profile, set against the device's
idle time.

With tracing on (``repro.analysis.spans.tracing``), the program marks its
host work with ``moegen.*`` spans on the profiler's clock: the scheduler's
step, admission, prefill, decode, emit; each engine layer; sampling; every
planned transfer (``xfer``, tagged); every wait for a streamed copy
(``stream.wait``, with its bytes).  ``reduce`` computes over the window:

* ``idle_by_span``: each idle gap of each device, cut at the program
  spans' edges, each piece given to the innermost program span open over
  it (the latest start, on the main thread's line).  Keys are the span's
  name without ``moegen.``, or ``name:tag`` for ``xfer`` and
  ``stream.wait``; ``none`` where no program span is open.
* ``stream_bytes`` and ``stream_link_s``: each weight copy
  (``stream-window`` or ``expert-prefetch``) paired with the wait for it
  by (tag, key), over [issue start, wait end]; the bytes of the copies
  wholly inside the window, and the union of their intervals.

``shares`` turns that into the idle split (weight-copy issue, wait for a
copy, other host work, none; they sum to the idle share) and a lower
bound on a copy's rate while in flight.  From the command line it reads a
profile the serving launcher wrote (``repro.launch.serve --trace-dir``):

    python bench/program_spans.py DIR
"""
from __future__ import annotations

import bisect
import glob
import heapq
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace import Trace, _clip, _length, _minus, union  # noqa: E402

PREFIX = "moegen."
TAGGED = ("xfer", "stream.wait")
STREAM_TAGS = ("stream-window", "expert-prefetch")


@dataclass
class Span:
    name: str               # without the prefix
    start: float            # ns
    end: float              # ns
    stats: Dict = field(default_factory=dict)

    @property
    def key(self) -> str:
        if self.name in TAGGED:
            return f"{self.name}:{self.stats.get('tag', '')}"
        return self.name


def load_spans(path: str) -> List[Span]:
    """The ``moegen.*`` host events of the ``.xplane.pb`` at ``path``, on
    the main thread's line: the one with the most ``moegen.step`` spans,
    then the most program spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines: List[List[Span]] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            got = [Span(e.name[len(PREFIX):], e.start_ns, e.end_ns,
                        dict(e.stats))
                   for e in line.events if e.name.startswith(PREFIX)]
            if got:
                lines.append(got)
    if not lines:
        return []
    return max(lines, key=lambda ss: (sum(s.name == "step" for s in ss),
                                      len(ss)))


@dataclass
class Reduced:
    window_s: float
    idle_s: float                        # mean over devices
    idle_by_span: Dict[str, float]       # span key -> idle seconds
    stream_bytes: int
    stream_link_s: float
    stream_copies: int
    devices: int

    def top(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def window_of(tr: Trace, spans: Sequence[Span]) -> Tuple[float, float]:
    """The ``bench.window`` host span, else the program spans' extent."""
    win = [s for s in tr.spans if s.name == "bench.window"]
    if win:
        return win[0].start, win[0].end
    return min(s.start for s in spans), max(s.end for s in spans)


def reduce(tr: Trace, spans: Sequence[Span],
           window: Optional[Tuple[float, float]] = None) -> Reduced:
    """Reduce the device events of ``tr`` and the program ``spans`` over
    ``window`` (ns; default ``window_of``)."""
    lo, hi = window or window_of(tr, spans)
    segs = innermost(spans)
    idle: Dict[str, float] = defaultdict(float)
    ndev = max(1, len(tr.ops))
    for ops in tr.ops.values():
        inside = [(e.start, e.end) for e in ops if e.end > lo and e.start < hi]
        busy = _clip(union(inside), lo, hi)
        for k, v in label_gaps(_minus([(lo, hi)], busy), segs).items():
            idle[k] += v
    nbytes, link, copies = stream_copies(spans, lo, hi)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        idle_s=sum(idle.values()) / ndev * ns,
        idle_by_span={k: v / ndev * ns for k, v in idle.items()},
        stream_bytes=nbytes, stream_link_s=link * ns, stream_copies=copies,
        devices=ndev,
    )


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The timeline cut at every span edge: ``(start, end, key)`` pieces,
    each keyed by the span open over it with the latest start, ``none``
    where none is open."""
    order = sorted(range(len(spans)), key=lambda i: spans[i].start)
    edges = sorted({t for s in spans for t in (s.start, s.end)})
    heap: List[Tuple[float, int]] = []      # (-start, index) of open spans
    out: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in zip(edges, edges[1:]):
        while j < len(order) and spans[order[j]].start <= a:
            heapq.heappush(heap, (-spans[order[j]].start, order[j]))
            j += 1
        while heap and spans[heap[0][1]].end <= a:
            heapq.heappop(heap)
        key = spans[heap[0][1]].key if heap else "none"
        if out and out[-1][2] == key and out[-1][1] == a:
            out[-1] = (out[-1][0], b, key)
        else:
            out.append((a, b, key))
    return out


def label_gaps(gaps: Sequence[Tuple[float, float]],
               segs: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Each gap's length split over the pieces of ``segs`` (sorted,
    disjoint) it crosses; what no piece covers goes to ``none``."""
    starts = [s for s, _, _ in segs]
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segs) and segs[i][0] < g1:
            s, e, key = segs[i]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                out[key] += ov
                covered += ov
            i += 1
        if g1 - g0 > covered:
            out["none"] += g1 - g0 - covered
    return dict(out)


def stream_copies(spans: Sequence[Span], lo: float,
                  hi: float) -> Tuple[int, float, int]:
    """Weight copies paired issue-to-wait by (tag, key): the bytes and the
    union of [issue start, wait end] of those wholly in [lo, hi], and how
    many there were.  A wait pairs with the latest issue of its key that
    started before it and was not paired yet."""
    issues: Dict[Tuple, List[float]] = defaultdict(list)
    waits: Dict[Tuple, List[Span]] = defaultdict(list)
    for s in spans:
        tag = s.stats.get("tag")
        if tag not in STREAM_TAGS or "key" not in s.stats:
            continue
        k = (tag, str(s.stats["key"]))
        if s.name == "xfer":
            issues[k].append(s.start)
        elif s.name == "stream.wait":
            waits[k].append(s)
    nbytes, ivs = 0, []
    for k, ws in waits.items():
        starts = sorted(issues.get(k, []))
        used = -1
        for w in sorted(ws, key=lambda s: s.start):
            i = bisect.bisect_right(starts, w.start) - 1
            if i <= used:
                continue
            used = i
            if starts[i] >= lo and w.end <= hi:
                nbytes += int(w.stats.get("bytes", 0))
                ivs.append((starts[i], w.end))
    return nbytes, _length(union(ivs)), len(ivs)


def shares(r: Reduced) -> Dict[str, Optional[float]]:
    """The idle split as percent of the window, and the in-flight rate."""
    w = r.window_s
    if w <= 0:
        return {}
    issue = sum(r.idle_by_span.get(f"xfer:{t}", 0.0) for t in STREAM_TAGS)
    wait = sum(v for k, v in r.idle_by_span.items()
               if k.startswith("stream.wait:"))
    none = r.idle_by_span.get("none", 0.0)
    return {
        "idle_pct": 100.0 * r.idle_s / w,
        "stream_issue_idle_pct": 100.0 * issue / w,
        "stream_wait_idle_pct": 100.0 * wait / w,
        "host_idle_pct": 100.0 * (r.idle_s - issue - wait - none) / w,
        "none_idle_pct": 100.0 * none / w,
        "htod_link_gbs": (r.stream_bytes / r.stream_link_s / 1e9
                          if r.stream_link_s > 0 else None),
        "stream_span_gbs": r.stream_bytes / w / 1e9,
    }


def main(argv=None) -> int:
    from bench import trace

    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    paths = sorted(glob.glob(os.path.join(args[0], "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        print(f"no .xplane.pb under {args[0]}", file=sys.stderr)
        return 1
    spans = load_spans(paths[-1])
    if not spans:
        print(f"{paths[-1]} holds no {PREFIX}* spans", file=sys.stderr)
        return 1
    r = reduce(trace.load(paths[-1]), spans)
    print(json.dumps({"profile": paths[-1], "devices": r.devices,
                      "window_s": r.window_s, "shares": shares(r),
                      "stream_bytes": r.stream_bytes,
                      "stream_link_s": r.stream_link_s,
                      "stream_copies": r.stream_copies,
                      "program_idle": r.top()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
