"""Record a traced run of a cell as a trace fixture for the tests.

  python bench/record_trace.py --workload terasort-1chip --seed <n> \\
      --seconds 51 --out bench/tests/data/terasort_scoped

Runs the cell once as ``bench/run.py --trace 1`` does and prints its result
line. Besides, it writes ``<out>_trace.json.gz``: the traced window's
device ops (the ``XLA Ops`` lines) and the ``bench.*`` host spans
(:func:`bench.traceread.save_events`); and ``<out>_layers.json``: the HLO
module name and op->layer map of the program that ran
(``repro.obs.layers``), kept to the ops the trace holds.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run, spec, traceread  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    cell = spec.workload(args.workload)
    spec.driver(cell["deployment"]["kind"])
    device = run.require_chips(cell["chips"])
    run.enable_compile_cache()

    kept = []
    load = traceread.load_xspace

    def keep(path):
        events = load(path)
        kept.extend(e for e in events if e.line == traceread.OPS_LINE
                    or e.plane == traceread.HOST_PLANE)
        return events

    traceread.load_xspace = keep
    result = run.run_cell(args.workload, args.seed, args.seconds, True, t0,
                          device)
    from repro.obs import layers
    module, op_layers = layers.last_run()
    names = {traceread.short_name(e.name).split(" ", 1)[0] for e in kept}
    traceread.save_events(kept, args.out + "_trace.json.gz")
    with open(args.out + "_layers.json", "w") as f:
        json.dump({"module": module,
                   "layers": {k: v for k, v in sorted(op_layers.items())
                              if k in names}}, f, indent=0)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
