"""Read the check's number for the program, for its control and under a
planted fault, on the chip, at a cell's own size, over several seeds in
one process.

    python bench/control.py --workload mixtral-4l.offline \
        --seeds 11,12,13 --seconds 15 --control
    python bench/control.py --workload mixtral-4l.offline \
        --seeds 21,22,23 --seconds 15 --fault half_the_batch

For each seed it makes one run of the cell as ``bench/run.py`` does and
prints one JSON line: ``correct`` and the checks it was decided from,
and the readings of the judged gaps (``info.gaps``).  With ``--control``
the control, the reference computed in float8 on the same prompts and
tokens, is put in the program's place for the decision, and the
program's own reading is kept beside it; with ``--fault`` the timed path
is broken as ``bench/faults.py`` names it.  A limit is set between the
program's largest reading over a dozen seeds or more and the control's
smallest.  The benchmark's own runs do neither.
"""
from __future__ import annotations

import time

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 11,12,13")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import jax

    from bench import faults, harness, manifest

    cell = manifest.resolve(
        manifest.load_json(os.path.join(ROOT, "BENCHMARK.json")),
        args.workload, ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 3
    fault = faults.FAULTS[args.fault] if args.fault else None
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), ROOT, fault=fault,
                               control=args.control)
        line = {"seed": seed, "control": args.control, "fault": args.fault,
                "correct": out["correct"], "checks": out["checks"],
                "gaps": out["info"]["gaps"],
                "info": {k: out["info"][k] for k in
                         ("tokens", "steps", "programs_in_window", "sample")}
                | {"reference_s": out["info"]["marks"]["reference"]}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
