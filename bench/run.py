"""Run one benchmark cell once on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell's files are found by name (:mod:`bench.spec`). The run refuses to
start unless JAX finds a TPU with at least the chips the cell asks for; it
never falls back to another backend. It sets up the deployment from the
seed (counted in ``setup_s``), measures for ``--seconds``, then checks what
the timed path produced against the plain reference. Earlier stdout lines
carry what the run saw (``{"info": ...}``); the last stdout line is the
result, whose last key ``checks`` holds every number compared beside its
limit, and the same numbers are the last lines on stderr. With
``--trace 1`` a few steady seconds in the middle of the window are traced,
and the result holds the cell's per-layer metrics instead of its
end-to-end ones.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec, traceread  # noqa: E402

#: fixed path inside the checkout: the path is part of every cache key.
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> Dict[str, Any]:
    """The device JAX reports; anything but ``chips`` or more TPUs ends the
    run with a non-zero exit and no result."""
    import jax
    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    if d.platform != "tpu":
        sys.exit(f"no TPU: JAX reports {info}; the benchmark runs on the "
                 f"chip only")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX reports {len(devices)}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def info(**fields) -> None:
    print(json.dumps({"info": fields}), flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t0: float, device: Dict[str, Any],
             adjust: Optional[Callable[[dict], None]] = None,
             fault: Optional[str] = None) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result object. ``adjust``
    edits the cell's merged spec before set-up and ``fault`` plants one of
    :mod:`bench.faults`' faults under the timed path (both for the tests
    and the control runs, never for a measured run)."""
    bench = spec.benchmark()
    cell = spec.workload(name)
    if adjust is not None:
        adjust(cell)
    peaks = spec.peaks(device["kind"]) if device["platform"] == "tpu" else {}
    driver = spec.driver(cell["deployment"]["kind"])
    run = driver.Cell(cell, seed, fault=fault, log=info)
    win = run.window(seconds, trace)
    setup_s = win.start - t0
    info(setup_s=setup_s)
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    checks = run.check()
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not trace:
        measured = dict(win.end_to_end, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if spec.applies(m, name, bench["end_to_end"]):
                if m["name"] not in measured:
                    raise KeyError(f"cell {name} measured no {m['name']}")
                metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(trace=win.trace, calls=win.traced_calls,
                              geometry=run.geometry, host=win.host,
                              peaks=peaks)
        for m in bench["per_layer"]:
            if spec.applies(m, name, bench["end_to_end"]):
                value = spec.metric_reader(m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = win.trace.busy_s()
        device["window_s"] = win.trace.window_s
        breakdown = traceread.breakdown(win.trace)

    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.workload(args.workload)
    spec.driver(cell["deployment"]["kind"])     # imports the program
    device = require_chips(cell["chips"])
    spec.peaks(device["kind"])
    info(compile_cache=enable_compile_cache(), device=device)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), T0, device)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
