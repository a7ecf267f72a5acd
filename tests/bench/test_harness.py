"""The harness end to end on the CPU at a tiny size, the check that
decides ``correct`` against faults planted in the timed path, and the
control, which has to fail the check."""
import copy
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import faults, harness, manifest
from tiny_bench import MIXTRAL, ROOT, TINY_CONFIG, TINY_MIX, write_bench

LIMIT = TINY_CONFIG["limits"]["mismatch_share"]


def tiny_cell(**serving):
    config = copy.deepcopy(TINY_CONFIG)
    config["serving"].update(serving)
    return manifest.Cell(
        "tiny.waves", 1, config, TINY_MIX,
        [{"name": "gen_tok_s", "unit": "tokens/s"},
         {"name": "setup_s", "unit": "s"}], [], {}, MIXTRAL)


def run(cell, seed=3, fault=None, tmp="/nonexistent"):
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(), tmp,
                            fault=fault)


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "mixtral-4l.offline", "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_py_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "mixtral-4l.offline", "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def sound():
    return run(tiny_cell())


@pytest.mark.parametrize("residency", ["resident", "offload"])
def test_tiny_cell_serves_correctly(residency, sound):
    out = sound if residency == "resident" else run(
        tiny_cell(residency="offload"))
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] <= LIMIT
    assert out["checks"]["judged_tokens"]["value"] >= 64
    assert out["info"]["programs_in_window"] == 0
    assert out["metrics"]["gen_tok_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_a_cell_added_by_files_runs(tmp_path):
    """A cell whose architecture, too, is new: its model module exists
    only in the checkout (a copy of the Mixtral module under another
    name, serving the program's mixtral-8x7b), and the cell is served and
    judged through it."""
    config = copy.deepcopy(TINY_CONFIG)
    config["architectures"] = ["TinyMixForCausalLM"]
    write_bench(tmp_path, config)
    m = manifest.load_json(str(tmp_path / "BENCHMARK.json"))
    assert manifest.problems(m, str(tmp_path)) == []
    cell = manifest.resolve(m, "tiny.waves", str(tmp_path))
    assert cell.model.__file__ == str(
        tmp_path / "bench" / "models" / "TinyMixForCausalLM.py")
    out = harness.run_cell(cell, 4, 1.0, True, time.perf_counter(),
                           str(tmp_path))
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["occupancy_pct"]["value"] <= 100
    assert out["device"]["window_s"] > 0


def test_a_configuration_without_its_model_module_is_named(tmp_path):
    config = copy.deepcopy(TINY_CONFIG)
    config["architectures"] = ["NoSuchForCausalLM"]
    write_bench(tmp_path, config, models={})
    m = manifest.load_json(str(tmp_path / "BENCHMARK.json"))
    path = os.path.join("bench", "models", "NoSuchForCausalLM.py")
    assert any("no model module" in p and path in p
               for p in manifest.problems(m, str(tmp_path)))
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(tmp_path / path))):
        manifest.resolve(m, "tiny.waves", str(tmp_path))


@pytest.mark.parametrize("fault", [faults.altered_token,
                                   faults.state_unchanged,
                                   faults.half_the_batch])
def test_a_fault_in_the_timed_path_is_not_correct(fault, sound):
    assert sound["correct"]
    out = run(tiny_cell(), fault=fault)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] > LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_the_check(seed):
    """The reference computed in float8 (the precision below the
    configuration's bf16), put in the program's place on the prompts and
    tokens a sound run served, comes out not correct, while the program's
    own reading on the same tokens stays within the limit."""
    out = harness.run_cell(tiny_cell(), seed, 1.0, False, time.perf_counter(),
                           "/nonexistent", control=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] > LIMIT
    program = out["info"]["gaps"]["program"]["share"][str(harness.TAU)]
    assert program <= LIMIT


class _Handle:
    def __init__(self, index, finished):
        self.index, self.finished = index, finished


def test_the_sample_spreads_over_many_requests():
    """The longest finished request and others drawn from the seed,
    finished before live, each judged whole: a 512-token request does
    not crowd the rest out."""
    handles = [_Handle(i, i < 40) for i in range(64)]
    served = {i: [1] * (512 if i == 7 else 32 + i) for i in range(64)}
    served[63] = []                      # nothing served: never judged
    sample = harness.pick_sample(handles, served, 2**31 + 9)
    assert sample[0] == 7
    assert len(sample) == len(set(sample)) == harness.SAMPLE_REQUESTS
    assert all(handles[i].finished for i in sample)
    assert sample == harness.pick_sample(handles, served, 2**31 + 9)
    assert sample != harness.pick_sample(handles, served, 5)
    few = harness.pick_sample(handles[35:], served, 3)
    assert [handles[i].finished for i in few][:5] == [True] * 5
    assert len(few) == min(28, harness.SAMPLE_REQUESTS) and 63 not in few


def test_every_judged_request_weighs_the_same():
    """A fault in a quarter of the slots reads a quarter of the judged
    requests off, however many tokens the sound ones served."""
    sound = [np.zeros(512)] + [np.zeros(32)] * 11
    broken = [np.full(16, 5.0)] * 4
    assert harness.mismatch_share(sound + broken) == 0.25
    ok, checks = harness.check(sound + broken, 0.2)
    assert not ok and checks["judged_requests"]["value"] == 16
    ok, checks = harness.check([np.zeros(100)] * 4, 0.2)
    assert not ok                        # too few requests judged
