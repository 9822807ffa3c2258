#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line last.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a workload of BENCHMARK.json.  The run sets up (weights and
inputs from the seed, every program the window uses compiled or read from
the compile cache), measures for ``--seconds``, checks what the timed path
produced against the configuration's plain reference, and prints one JSON
object: ``--trace 0`` carries the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics read from a profiler trace of the window.  The numbers
compared for ``correct`` are printed with their limits as the last lines on
standard error and under ``checks``, last, in the result line.

Exit codes: 0 with a result; 2 when the program under test is missing;
3 when JAX finds no accelerator or fewer chips than the cell asks for.
``--rehearse`` runs the cell at its configuration's tiny rehearsal size
without a chip (set JAX_PLATFORMS=cpu): Pallas kernels in interpret mode,
no device metric, and a result line that is not a measurement.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal; no chip, no measurement")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under test: {ROOT / 'src' / 'repro'} is missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                                rehearse=args.rehearse)
    except harness.NoChip as e:
        print(f"no measurement: {e}", file=sys.stderr)
        return 3
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
