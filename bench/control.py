#!/usr/bin/env python3
"""The control of a cell's correctness check, at the cell's own size.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For each seed it puts the reference, with a shortcut scan in place of
exact LRU (``reference.approximate_level``: slots counted over a capped
window instead of distinct lines), where the program's answers would
be, and compares it with the plain reference exactly as a run of the
cell compares the program.  Each seed prints one JSON line with
the numbers and their limits; a sound control comes out not correct on
every seed.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import jobs, manifest, reference, run  # noqa: E402


def control(cell: manifest.Cell, seed: int) -> dict:
    work = jobs.make(cell.config, cell.traffic, seed)
    work.setup()
    placement = work.placement()
    t0 = time.perf_counter()
    want = work.reference(placement)
    t1 = time.perf_counter()
    got = work.reference(placement, level=reference.approximate_level)
    numbers = work.compare([got], placement, want)
    return {"workload": cell.name, "seed": seed,
            "correct": all(n.ok for n in numbers),
            "reference_s": t1 - t0,
            "numbers": {n.name: {"value": n.value, "limit": n.limit}
                        for n in numbers}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    run.check_device(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
