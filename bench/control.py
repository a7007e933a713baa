"""Run a cell's control (or a planted fault) on the chip at the cell's size.

  python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 8 \
      [--fault control]

For each seed, one process-local run of the cell with the fault of
:mod:`bench.faults` planted under its timed path: set-up, a short window at
the cell's own load, and the same check as a measured run. Prints one JSON
line per seed with every number compared and whether the run came out
correct; the control has to come out not correct on every seed. The
measured runs (``bench/run.py``) never plant anything.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="control")
    args = ap.parse_args(argv)
    cell = spec.workload(args.workload)
    device = run.require_chips(cell["chips"])
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           time.perf_counter(), device, fault=args.fault)
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)


if __name__ == "__main__":
    main()
