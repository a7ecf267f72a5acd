import copy
import json
import os

import pytest

from bench import manifest
from tiny_bench import ROOT, TINY_CONFIG, TINY_MIX, write_bench


@pytest.fixture(scope="module")
def committed():
    return manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_committed_manifest_is_sound(committed):
    assert manifest.problems(committed, ROOT) == []


def test_every_committed_cell_resolves(committed):
    for w in committed["workloads"]:
        cell = manifest.resolve(committed, w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "gen_tok_s"} <= names
        assert cell.per_layer and set(cell.readers) == {
            m["name"] for m in cell.per_layer}
        assert cell.config["serving"]["batch"] >= 1


@pytest.mark.parametrize("edit, fault", [
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda m: m["workloads"][0].update(name="tiny waves"), "workload name"),
    (lambda m: m["per_layer"][0].update(moves="ttft_p95_s"), "moves"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"].pop(1), "setup_s"),
])
def test_manifest_faults_are_named(tiny_checkout, edit, fault):
    m = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    assert manifest.problems(m, str(tiny_checkout)) == []
    edit(m)
    assert any(fault in p for p in manifest.problems(m, str(tiny_checkout)))


def test_per_layer_metric_needs_its_cells_to_report_what_it_moves(
        tiny_checkout):
    m = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    m["end_to_end"].append({"name": "ttft_p95_s", "unit": "s",
                            "better": "lower", "bound": 0.1,
                            "source": "host_clock", "workloads": []})
    m["per_layer"][0]["moves"] = "ttft_p95_s"
    assert any("does not report ttft_p95_s" in p
               for p in manifest.problems(m, str(tiny_checkout)))


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new configuration, traffic mix and metric reader, written as
    files beside a manifest entry, are found by name with no code
    change."""
    config = copy.deepcopy(TINY_CONFIG)
    config["serving"]["batch"] = 2
    mix = dict(TINY_MIX, blocks=5)
    write_bench(tmp_path, config, mix, readers={
        "steps_seen": "def read(ctx):\n    return 7.0\n"})
    m = manifest.load_json(str(tmp_path / "BENCHMARK.json"))
    assert manifest.problems(m, str(tmp_path)) == []
    cell = manifest.resolve(m, "tiny.waves", str(tmp_path))
    assert cell.config["serving"]["batch"] == 2
    assert cell.traffic["blocks"] == 5
    assert cell.readers["steps_seen"]({}) == 7.0
