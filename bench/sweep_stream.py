"""Find a stream cell's knee on the chip: the highest offered rate at which
the backlog does not grow over the window.

  python bench/sweep_stream.py --workload stream-wordcount-0.8knee \
      --seed <n> --seconds <s> --rates 1.0e6,1.5e6,2.0e6

One process sets the cell up once and offers each rate for ``--seconds``
in turn, draining the queue between rates. Each rate prints one JSON line:
the offered rate, the events delivered by the window's close per second,
the backlog (queued events) at the close and the latency percentiles of
the requests delivered by then. A rate holds when the backlog at the close
is at most one micro-batch (the batch being gathered while another runs).
The last line names the knee and 0.8 of it, which the cell's traffic file
takes as its ``rate_events_per_s``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, events/s")
    args = ap.parse_args(argv)
    cell = spec.workload(args.workload)
    run.require_chips(cell["chips"])
    run.enable_compile_cache()
    driver = spec.driver(cell["deployment"]["kind"])
    stream = driver.Cell(cell, args.seed, log=run.info)
    batch = cell["deployment"]["micro_batch"]
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        win = stream.window(args.seconds, trace=False, rate=rate, wait=False)
        holds = win.backlog_at_close <= batch
        print(json.dumps({"rate_events_per_s": rate,
                          "delivered_by_close_events_per_s":
                              win.delivered_by_close,
                          "backlog_events_at_close": win.backlog_at_close,
                          "p50_ms_delivered": win.p50_ms,
                          "p95_ms_delivered": win.end_to_end.get(
                              "stream_p95_ms"),
                          "holds": holds}), flush=True)
        if holds:
            knee = rate
        while stream.ex.queue.pending():
            stream.ex.step()
    print(json.dumps({"knee_events_per_s": knee,
                      "rate_0.8_knee": None if knee is None else 0.8 * knee}),
          flush=True)


if __name__ == "__main__":
    main()
