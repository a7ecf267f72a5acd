"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload mixtral-4l.offline --seed 7 \
        --seconds 30 --trace 0

The cell (configuration, traffic mix, metrics) is found by name in
``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries its per-layer
metrics, the device's busy time and a breakdown.  Every run checks what
it served against the plain reference and prints each compared number
beside its limit, on standard error and last in the result line.

Runs only on a TPU with as many chips as the cell asks for: anywhere
else it exits nonzero before any work and prints no result.  JAX's
persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # JAX reads these when it is imported: every program of a run goes to
    # one fixed directory inside the checkout, whatever the machine sets
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        import jax

        import repro  # noqa: F401
        from bench import harness, manifest
    except ImportError as e:
        print(f"bench: cannot import the program from {ROOT}/src: {e}",
              file=sys.stderr)
        return 2
    cell = manifest.resolve(
        manifest.load_json(os.path.join(ROOT, "BENCHMARK.json")),
        args.workload, ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T0, ROOT)
    print(json.dumps({k: v for k, v in result.items() if k != "checks"}
                     | {"checks": result["checks"]}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
