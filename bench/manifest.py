"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

* a configuration: the ``file`` its manifest entry names (a JSON object);
* a traffic mix: ``<bench>/traffic/<traffic>.json``;
* a per-layer metric: ``<bench>/metrics/<name>.py``, whose
  ``read(ctx) -> float | None`` takes the number from the run's counters
  or its reduced trace and returns None where it finds nothing to read;
* an architecture: ``<bench>/models/<arch>.py``, where ``<arch>`` is the
  configuration file's ``architectures[0]``.  Everything that knows the
  architecture's shapes and mathematics is there, and nowhere else:

  - ``dims(config)``: a hashable shape object.  The harness, the traffic
    generator and the readers use only its ``layers``, ``vocab``,
    ``experts`` and ``top_k``; every other field is the module's own.
  - ``check_program(cfg, dims, config)``: raises unless the program's
    ModelConfig ``cfg`` serves the published widths.
  - ``host_params(seed, dims)``, ``resident_params(seed, dims)``: the
    weights drawn from the seed, in the program's parameter tree, with
    every layer in host memory or on the device.
  - ``judge(dims, seed, judged, max_seq, control)``: for each judged
    ``(prompt, served tokens)``, the gap of every served token below the
    float32 reference's best logit; with ``control``, also the gaps of
    the float8 control's choices at the same positions.
  - ``prefill_flops(dims, n)``, ``decode_flops(dims, pos)``: the model
    FLOPs of a prompt of ``n`` tokens and of one token fed at ``pos``.
  - ``expert_ffn_work(dims, copies, experts_hit)``: (FLOPs, bytes) of one
    grouped expert FFN call, for the kernel's roofline.

A later cell, configuration, architecture or metric is added by adding
such files and manifest entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List, Optional

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@dataclass
class Cell:
    """One workload of the manifest with everything it needs, resolved."""

    name: str
    chips: int
    config: Dict            # the configuration file's object
    traffic: Dict           # the traffic mix file's object
    end_to_end: List[Dict]  # manifest entries of the metrics it reports
    per_layer: List[Dict]
    readers: Dict[str, Callable]   # per-layer metric name -> read(ctx)
    model: ModuleType       # the architecture's model module


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: Dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: every cell where the entry
    has no ``workloads`` list, else the cells it lists."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(name: str, path: str) -> ModuleType:
    """The Python file ``path``, run as module ``name``.  It is entered in
    ``sys.modules`` first, as an import would, so that what it defines
    (a dataclass, say) can find its own module."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, name: str) -> Callable:
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    return load_module(f"bench_metric_{name}", path).read


def model_file(bench_dir: str, arch: str) -> str:
    return os.path.join(bench_dir, "models", f"{arch}.py")


def load_model(bench_dir: str, arch: str) -> ModuleType:
    """The model module of architecture ``arch`` (a configuration's
    ``architectures[0]``)."""
    return load_module(f"bench_model_{arch}", model_file(bench_dir, arch))


def resolve(manifest: Dict, workload: str, root: str,
            bench_dir: Optional[str] = None) -> Cell:
    """The cell ``workload`` of ``manifest``, its files read from under
    ``root`` (the checkout) and ``bench_dir`` (traffic mixes and metric
    readers; default: ``<root>/bench``)."""
    bench_dir = bench_dir or os.path.join(root, "bench")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; the manifest has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    e2e = [m for m in manifest["end_to_end"] if reports(m, workload)]
    layer = [m for m in manifest["per_layer"] if reports(m, workload)
             and any(e["name"] == m["moves"] for e in e2e)]
    readers = {m["name"]: load_reader(bench_dir, m["name"]) for m in layer}
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer,
                readers, load_model(bench_dir, config["architectures"][0]))


def problems(manifest: Dict, root: str) -> List[str]:
    """What in ``manifest`` breaks the benchmark's rules of form (names,
    units, sources, files, model modules, and each per-layer metric's
    cells reporting the end-to-end metric it moves); empty when it is
    sound."""
    out: List[str] = []
    cells = {w["name"]: w for w in manifest.get("workloads", [])}
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    e2e = {m["name"]: m for m in manifest.get("end_to_end", [])}
    layer = manifest.get("per_layer", [])
    for kind, items in (("config", configs.values()),
                        ("workload", cells.values()),
                        ("metric", list(e2e.values()) + layer)):
        for it in items:
            if not NAME.fullmatch(it["name"]):
                out.append(f"{kind} name {it['name']!r}")
    names = [m["name"] for m in list(e2e.values()) + layer]
    if len(names) != len(set(names)):
        out.append("two metrics share a name")
    for m in list(e2e.values()) + layer:
        if not UNIT.fullmatch(m["unit"]):
            out.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                out.append(f"{m['name']}: unknown cell {c!r}")
    for m in e2e.values():
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: an end-to-end metric takes its own "
                       f"clock or trace")
        if not 0 < m.get("bound", 0) <= 0.25:
            out.append(f"{m['name']}: bound {m.get('bound')!r}")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for w in cells.values():
        for key in ("config", "traffic"):
            if not NAME.fullmatch(w[key]):
                out.append(f"{w['name']}: {key} {w[key]!r}")
        if w["config"] not in configs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips {w['chips']!r}")
        if not os.path.exists(os.path.join(root, "bench", "traffic",
                                           f"{w['traffic']}.json")):
            out.append(f"{w['name']}: no traffic file {w['traffic']!r}")
        mine = [n for n, m in e2e.items() if reports(m, w["name"])]
        if "setup_s" not in mine or len(mine) < 2:
            out.append(f"{w['name']}: needs setup_s and one more "
                       f"end-to-end metric")
        if not any(reports(m, w["name"]) and m["moves"] in mine
                   for m in layer):
            out.append(f"{w['name']}: reports no per-layer metric")
    for m in layer:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for c in (m.get("workloads") or list(cells)):
            if c in cells and not reports(e2e[m["moves"]], c):
                out.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
        if not os.path.exists(os.path.join(root, "bench", "metrics",
                                           f"{m['name']}.py")):
            out.append(f"{m['name']}: no reader file")
    used = {w["config"] for w in cells.values()}
    for c in configs.values():
        if c["name"] not in used:
            out.append(f"config {c['name']} has no cell")
        path = os.path.join(root, c["file"])
        if not os.path.exists(path):
            out.append(f"config {c['name']}: no file {c['file']}")
        else:
            arch = (load_json(path).get("architectures") or [""])[0]
            model = model_file(os.path.join(root, "bench"), arch)
            if not NAME.fullmatch(arch):
                out.append(f"config {c['name']}: architecture {arch!r}")
            elif not os.path.isfile(model):
                out.append(f"config {c['name']}: no model module "
                           f"{os.path.relpath(model, root)}")
        for k in c.get("reduced", []):
            if not NAME.fullmatch(k):
                out.append(f"config {c['name']}: reduced key {k!r}")
    return out
