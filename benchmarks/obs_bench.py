"""Observability overhead benchmark: tracing must cost < 5% on terasort.

One subprocess on 8 virtual devices (XLA_FLAGS must be set before jax
initializes) runs the whole instrumented surface:

- **SPMD terasort** (``Dataflow.source().sort``): warm up WITH a tracer so
  the compile miss records hop geometry and collective counts, then time
  untraced vs traced runs on the warm compile cache in interleaved pairs —
  ``obs_overhead`` is the median traced/untraced ratio and ``--check``
  gates it below :data:`OVERHEAD_BOUND`.
- **Host terasort** over a real in-process Sector deployment, with a
  ``drop_bucket`` fault injected so the retry AND mid-job recovery series
  show up in the metrics snapshot.
- **Streaming wordcount** through a two-tenant :class:`TenantQueue` for
  the per-tenant latency series.

All three executors share ONE trace buffer (``tracer.fork``), so the
Perfetto file written to ``--trace PATH`` shows them as side-by-side
threads; CI uploads it as a workflow artifact every run. ``--check``
additionally validates the trace_event JSON (``spmd.execute`` nested in
``spmd.run`` on the SPMD track, phase→segment spans on the host track) and
that every required metric series is present in the registry snapshot.
Per-stage device time of the fused program comes from a ``jax.profiler``
trace and its op->layer map (docs/OBSERVABILITY.md), not from this bench.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

OWNER = "obs"
OVERHEAD_BOUND = 1.05            # traced/untraced wall-clock, warm cache

#: metric series the snapshot must contain after the bench (labels matter
#: for the tenant series — substring match against the snapshot keys)
REQUIRED_SERIES = [
    "spmd.runs", "spmd.shuffle.wire_bytes", "spmd.shuffle.hops",
    "spmd.collectives.all_to_all", "spmd.dropped", "spmd.cache.misses",
    "host.segments", "host.retries", "host.recoveries",
    "host.phase_seconds", "stream.batches", 'tenant.latency{tenant="',
]

_BENCH_CODE = """
import json, sys, tempfile, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.core.records import RecordCodec
from repro.launch.train import make_sector
from repro.obs import Tracer, REGISTRY
from repro.sphere.chaos import FaultPlan
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE
from repro.sphere.streaming import StreamExecutor, TenantQueue

trace_path = sys.argv[1]
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
N = 8 * 8192
keys = rng.integers(0, 2**31 - 2, size=N).astype(np.int32)
payload = np.arange(N, dtype=np.int32)
kd = jax.device_put(jnp.asarray(keys), NamedSharding(mesh, P("data")))
pd = jax.device_put(jnp.asarray(payload), NamedSharding(mesh, P("data")))
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=16)
data = {"key": kd, "payload": pd}

tracer = Tracer(track="spmd")
ex = SPMDExecutor(mesh)
with mesh:
    # warm-up WITH the tracer: the compile miss records hop geometry and
    # collective counts into the registry exactly once
    res = ex.run(df, data, trace=tracer)
    jax.block_until_ready(res.records["key"])
    assert (np.diff(res.valid_records()["key"]) >= 0).all()
    iters = 15
    t_un, t_tr = [], []
    for _ in range(iters):            # interleaved pairs, warm cache
        t0 = time.perf_counter()
        r = ex.run(df, data)
        jax.block_until_ready(r.records["key"])
        t_un.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        r = ex.run(df, data, trace=tracer)   # blocks internally (fencing)
        t_tr.append(time.perf_counter() - t0)
    overhead = float(np.median(np.asarray(t_tr) / np.asarray(t_un)))

# -- host executor: same sort over a real Sector deployment ------------------
htr = tracer.fork("host")
codec = RecordCodec.from_fields({"key": np.int32, "payload": np.int32})
hdf = Dataflow.source(codec).sort(key=lambda r: r["key"], num_buckets=8)
hk, hp = keys[:2048], payload[:2048]
root = tempfile.mkdtemp()
master, client, daemon = make_sector(root, num_slaves=4)
slices = np.split(codec.encode({"key": hk, "payload": hp}), 4)
client.upload_dataset("/ts/in", [s.tobytes() for s in slices])
daemon.run_until_stable()
spes = [SPE(i, master.slaves[i].address, master, client.session_id)
        for i in range(4)]
# drop_bucket fault: exercises SectorClient.recover mid-job, so the
# host.retries AND host.recoveries series are non-empty in the snapshot
chaos = FaultPlan(kind="drop_bucket", phase=0, seed=0)
hres = HostExecutor(master, client, spes, daemon=daemon).run(
    hdf, [f"/ts/in.{i:05d}" for i in range(4)], trace=htr, chaos=chaos)
hvr = hres.valid_records()
assert (np.diff(hvr["key"]) >= 0).all()
assert not hres.errors, hres.errors

# -- streaming: two-tenant queue -> tenant.latency series --------------------
strr = tracer.fork("stream")
def _emit(rec):
    return {"key": rec["key"].astype(jnp.int32),
            "value": jnp.ones_like(rec["key"], jnp.int32)}
def _count(rec, valid):
    k, v, dropped = reduce_by_key_sum(rec["key"], rec["value"], valid)
    return {"key": k, "value": v}, k >= 0, dropped
sdf = (Dataflow.stream_source()
       .map(_emit)
       .shuffle(by=lambda r: default_hash(r["key"], 8), num_buckets=8)
       .reduce(_count))
q = TenantQueue()
q.register("rt", weight=2.0, priority=0)
q.register("batch", weight=1.0, priority=1)
sex = StreamExecutor(SPMDExecutor(mesh), sdf, micro_batch=64,
                     carry_capacity=16, queue=q, trace=strr)
for i in range(6):
    sex.submit({"key": np.arange(16, dtype=np.int32) % 5},
               tenant="rt" if i % 2 else "batch")
batches = sex.drain()

tracer.to_perfetto(trace_path)
out = {
    "overhead": overhead, "iters": iters, "n_records": N,
    "untraced_us": float(np.median(t_un) * 1e6),
    "traced_us": float(np.median(t_tr) * 1e6),
    "phase_times": hres.phase_times,
    "host_retries": hres.retries, "host_recoveries": hres.recoveries,
    "stream_batches": len(batches),
    "snapshot": REGISTRY.snapshot(),
}
print("RESULT " + json.dumps(out))
"""


def bench(trace_path: str) -> Dict[str, object]:
    env = dict(os.environ)
    # semantics on 8 virtual CPU devices: the child never needs the chip,
    # which a parent that has touched JAX may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _BENCH_CODE, trace_path],
                          env=env, capture_output=True, text=True,
                          timeout=520)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])


def _contained(events: List[dict], outer: dict, prefix: str) -> List[dict]:
    """X-events on ``outer``'s tid, named ``prefix*``, inside its window."""
    lo, hi = outer["ts"], outer["ts"] + outer["dur"]
    return [e for e in events
            if e.get("ph") == "X" and e["tid"] == outer["tid"]
            and e["name"].startswith(prefix)
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]


def check_trace(trace_path: str) -> List[str]:
    """Validate the Perfetto trace_event JSON: loadable, and the stage→hop
    nesting exists on BOTH executor tracks."""
    failures: List[str] = []
    try:
        with open(trace_path) as f:
            payload = json.load(f)
        events = payload["traceEvents"]
    except (OSError, ValueError, KeyError) as e:
        return [f"trace {trace_path} unreadable: {e!r}"]
    xs = [e for e in events if e.get("ph") == "X"]
    for e in xs:
        if not all(k in e for k in ("name", "ts", "dur", "pid", "tid")):
            failures.append(f"malformed trace event {e}")
            return failures
    tracks = {e["args"]["name"]: e["tid"] for e in events
              if e.get("ph") == "M" and e.get("name") == "thread_name"}
    for want in ("spmd", "host", "stream"):
        if want not in tracks:
            failures.append(f"missing {want!r} track in trace")
    # SPMD: the root span must contain the execute span
    runs = [e for e in xs if e["tid"] == tracks.get("spmd")
            and e["name"] == "spmd.run"]
    if not runs:
        failures.append("no spmd.run span")
    elif not _contained(xs, runs[0], "spmd.execute"):
        failures.append("no spmd.execute span nested inside spmd.run")
    # host: each phase span must contain segment spans; phase 1 (bucket
    # sort) follows the hop[0]:buckets materialization span
    phases = [e for e in xs if e["tid"] == tracks.get("host")
              and e["name"].startswith("phase[")]
    if not phases:
        failures.append("no host phase[i] spans")
    elif not _contained(xs, phases[0], "segment["):
        failures.append("no segment[i] span nested inside host phase[0]")
    if not any(e["name"].startswith("hop[") and
               e["tid"] == tracks.get("host") for e in xs):
        failures.append("no host hop[i]:buckets span")
    if not any(e["name"].startswith("stream.batch[") for e in xs):
        failures.append("no stream.batch[i] spans")
    return failures


def check(res: Dict[str, object], trace_path: str) -> List[str]:
    failures: List[str] = []
    ratio = float(res["overhead"])
    if not ratio == ratio or ratio > OVERHEAD_BOUND:   # NaN-safe
        failures.append(f"tracing overhead {ratio:.3f}x exceeds the "
                        f"{OVERHEAD_BOUND:.2f}x bound")
    snap = res["snapshot"]
    for series in REQUIRED_SERIES:
        if not any(k.startswith(series) for k in snap):
            failures.append(f"metric series {series!r} missing from "
                            f"snapshot")
    # schema stability: every snapshot entry carries its type and the
    # type-specific required fields
    for k, v in snap.items():
        t = v.get("type")
        want = {"counter": ("value",), "gauge": ("value",),
                "histogram": ("count", "sum", "p50", "p99")}.get(t)
        if want is None or any(f not in v for f in want):
            failures.append(f"snapshot entry {k!r} breaks schema: {v}")
            break
    if int(res["host_recoveries"]) < 1:
        failures.append("drop_bucket fault produced no recovery")
    failures.extend(check_trace(trace_path))
    return failures


def _merge_json(json_path: str, res: Dict[str, object]) -> None:
    try:
        with open(json_path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {"schema": "repro.kernel_bench.v1", "results": {}}
    payload.setdefault("results", {})
    payload["results"]["obs_overhead"] = {
        "owner": OWNER,
        "ratio": res["overhead"], "bound": OVERHEAD_BOUND,
        "untraced_us": res["untraced_us"], "traced_us": res["traced_us"],
        "iters": res["iters"], "records": res["n_records"],
        "note": "traced/untraced terasort wall time, warm compile cache, "
                "median of interleaved pairs, 8 virtual devices",
    }
    payload["results"]["obs_stage_times"] = {
        "owner": OWNER,
        "host_phases": res["phase_times"],
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)


def run(csv: bool = True, json_path: str | None = None,
        trace_path: str = "obs_trace.json") -> List[str]:
    res = bench(trace_path)
    lines = [
        f"obs_overhead,{res['traced_us']:.0f},"
        f"ratio={res['overhead']:.3f}x (bound {OVERHEAD_BOUND:.2f}x) "
        f"untraced={res['untraced_us']:.0f}us over {res['iters']} pairs",
        f"obs_trace,0,perfetto written to {trace_path}",
        f"obs_host_phases,{len(res['phase_times'])},"
        f"retries={res['host_retries']} recoveries={res['host_recoveries']}",
    ]
    if json_path:
        _merge_json(json_path, res)
        lines.append(f"obs_bench_json,0,merged into {json_path}")
    run.last_result = res
    return lines


def main() -> None:
    args = sys.argv[1:]
    do_check = "--check" in args
    json_path = None
    trace_path = "obs_trace.json"
    usage = "usage: obs_bench.py [--json PATH] [--trace PATH] [--check]"
    if "--json" in args:
        idx = args.index("--json") + 1
        if idx >= len(args):
            print(usage)
            sys.exit(2)
        json_path = args[idx]
    elif do_check:
        json_path = "BENCH_kernels.json"
    if "--trace" in args:
        idx = args.index("--trace") + 1
        if idx >= len(args):
            print(usage)
            sys.exit(2)
        trace_path = args[idx]
    for line in run(json_path=json_path, trace_path=trace_path):
        print(line)
    if do_check:
        res = run.last_result
        failures = check(res, trace_path)
        if failures:
            for msg in failures:
                print(f"CHECK FAILED: {msg}")
            sys.exit(1)
        print(f"CHECK OK: tracing overhead {res['overhead']:.3f}x < "
              f"{OVERHEAD_BOUND:.2f}x on warm-cache terasort; Perfetto "
              f"trace and metrics-snapshot schema valid")


if __name__ == "__main__":
    main()
