"""Benchmark harness: one table per paper table/figure.

Prints human tables plus ``name,...`` CSV lines.  Cost-model tables use the
paper's A5000 hardware constants; engine/kernel tables measure real
execution on this machine.

``--json PATH`` additionally writes the selected tables as machine-readable
JSON (``[{"name", "columns", "rows"}, ...]``) — the perf-trajectory format
the slow CI job uploads as ``BENCH_<name>.json`` artifacts.
"""
from __future__ import annotations

import argparse
import json


def main() -> None:
    from benchmarks import (engine_walltime, expert_parallel,
                            expert_prefetch, kernels, kv_paging,
                            paper_tables)
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on suite function names")
    ap.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                    help="write the selected tables as JSON to PATH")
    args = ap.parse_args()
    enable_compile_cache()

    suites = (list(paper_tables.ALL) + list(engine_walltime.ALL)
              + list(kernels.ALL) + list(kv_paging.ALL)
              + list(expert_prefetch.ALL) + list(expert_parallel.ALL))
    csv = []
    tables = []
    for fn in suites:
        if args.only and args.only not in fn.__name__:
            continue
        table = fn()
        table.show()
        tables.append(table)
        csv.extend(table.csv_lines())
    print("\n--- CSV ---")
    for line in csv:
        print(line)
    if args.json_path:
        payload = [
            {"name": t.name, "columns": t.columns, "rows": t.rows}
            for t in tables
        ]
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {args.json_path} ({len(payload)} tables)")


if __name__ == "__main__":
    main()
