"""Benchmark runner: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus the roofline summary from
the dry-run artifacts when present).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run terasort   # one section
"""

import sys
import traceback


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (kernel_bench, moe_dispatch, obs_bench, roofline,
                            scalability, sdss_distribution, storage_modes,
                            streaming_bench, terasort, wan_shuffle)
    sections = {
        "terasort": terasort.run,            # paper Table 1
        "wan_shuffle": wan_shuffle.run,      # §2.2 wide-area shuffle
        "sdss": sdss_distribution.run,       # paper Figs 4-5 + stream demo
        "scalability": scalability.run,      # §3.5.2 claims
        "storage": storage_modes.run,        # paper Table 2 (files vs blocks)
        "moe_dispatch": moe_dispatch.run,    # §3.6 generalization
        "kernels": kernel_bench.run,
        "streaming": streaming_bench.run,    # §3.2 continuous micro-batches
        "obs": obs_bench.run,                # tracing/metrics overhead gate
        "roofline": roofline.run,            # dry-run aggregation
    }
    want = sys.argv[1:] or list(sections)
    print("name,us_per_call,derived")
    failed = False
    for name in want:
        try:
            for line in sections[name]():
                print(line, flush=True)
        except Exception as e:
            failed = True
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
