"""Pallas kernel microbenchmarks vs the jnp oracles — and vs the retired
argsort send path.

On this CPU container the Pallas kernels run in interpret mode, so the
``*_pallas_interp`` rows measure *oracle-equivalent semantics* plus
interpreter overhead, not TPU performance; the jnp rows (the fused O(n)
send path the shuffles run with ``use_pallas=False``, and the XLA oracles)
are real compiled-CPU numbers. On a real TPU set REPRO_PALLAS_INTERPRET=0.

Cases:

- ``bucket_hist``       — MXU one-hot histogram vs jnp one-hot oracle.
- ``partition_pack``    — the ISSUE-4 headline: the fused O(n) partition/
                          pack send path vs the stable-argsort + histogram
                          + gather layout it replaced, on the 2^16-record
                          shuffle send microbenchmark.
- ``bitonic_sort``      — multi-segment bitonic kernel vs XLA row sort.
- ``segmented_sort``    — stage-2 economics: sorting bpd bucket-major
                          segments of R/bpd vs one R-row segment
                          (O(R log² (R/bpd)) vs O(R log² R)).
- ``segmented_sort_table`` — the autotuner's evidence: a (R, bpd) grid of
                          KV segment sorts (the real stage-2 hot path)
                          timed per algorithm {bitonic, radix, oracle} and
                          through the autotuned entry point; per-cell
                          ``autotune_choice`` rows record what the
                          autotuner picked and why (measured Melem/s, or
                          the reason a candidate was skipped). The
                          resolved table is persisted as ``autotune_table``
                          (pre-loadable via REPRO_AUTOTUNE_TABLE; CI
                          uploads it as a workflow artifact).
- ``wire_bytes_per_hop``   — the ISSUE-5 headline: bytes one flat shuffle
                          hop ships for int32-pair records under the fused
                          one-wire-tensor frame (payload rows + one
                          count-header row per tile) vs the retired
                          4-tensor layout (data + valid + bucket + src,
                          each capacity-padded).
- ``collectives_per_hop``  — jaxpr-counted ``all_to_all`` per hop (flat /
                          hierarchical, shuffle / combine, chunked), traced
                          on 8 virtual devices in a subprocess; also checks
                          the chunked (W=4) hop delivers the identical
                          record multiset as W=1.

``--json PATH`` additionally writes the machine-readable
``BENCH_kernels.json`` (the perf trajectory; CI runs this as a smoke step
and ``--check`` asserts the fused partition path beats the argsort layout,
the fused frame halves int32-pair wire bytes, collectives-per-hop stays at
1 flat / 2 hierarchical per chunk, the segmented stage-2 speedup holds the
1.3x floor, and on every sweep cell the autotuned entry point reaches at
least 0.95x of the best measured candidate — in particular it is never
slower than the jnp oracle).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.records import WireFrame
from repro.kernels import autotune, ops, ref
from repro.obs.metrics import REGISTRY

#: the (R, bpd) grid of the autotune sweep — R total records per shard,
#: bpd buckets per device (so each cell sorts bpd segments of R/bpd).
SWEEP_R = (1 << 14, 1 << 16)
SWEEP_BPD = (1, 4, 16, 64)

#: every row this bench writes into BENCH_kernels.json is stamped with this
#: owner; the merge keeps prior rows stamped by OTHER owners (streaming,
#: chaos, obs benches) and rewrites only its own.
OWNER = "kernel"


def _time(fn, *args, iters=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / iters


def _time_grid(fns: Dict[str, object], args, iters: int = 6) -> Dict[str, float]:
    """Best-of-N timing of several callables on the same inputs, rounds
    interleaved so slow drift (CPU frequency, background load) hits every
    candidate equally, and the order rotated each round so no candidate is
    permanently stuck running cache-cold behind a particular neighbour —
    used for the autotune table, where the per-cell gate compares
    candidates against each other and a systematic 5% skew between
    separate timing loops would be a false failure."""
    for fn in fns.values():           # compile outside the timed region
        jax.block_until_ready(fn(*args))
    names = list(fns)
    best = {name: float("inf") for name in names}
    for i in range(iters):
        for name in names[i % len(names):] + names[:i % len(names)]:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[name](*args))
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def _argsort_send_layout(num_dest: int, capacity: int):
    """The pre-ISSUE-4 send path (stable argsort + bincount + gather),
    preserved here as the baseline the fused path must beat."""

    @jax.jit
    def layout(dest, col):
        n = dest.shape[0]
        order = jnp.argsort(dest, stable=True)
        counts = jnp.bincount(dest, length=num_dest)
        offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
        cap_iota = jnp.arange(capacity, dtype=jnp.int32)[None, :]
        src = jnp.clip(offsets[:, None] + cap_iota, 0, n - 1).reshape(-1)
        origin = jnp.take(order.astype(jnp.int32), src)
        tile = jnp.take(col, origin, axis=0).reshape(
            (num_dest, capacity) + col.shape[1:])
        return tile, cap_iota < counts[:, None]

    return layout


_COLLECTIVES_CODE = """
import jax, numpy as np, jax.numpy as jnp, dataclasses, json
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core.introspect import collective_counts
from repro.core.shuffle import ShufflePlan

mesh1 = jax.make_mesh((8,), ("data",))
mesh2 = jax.make_mesh((2, 4), ("dc", "node"))
N = 8 * 512
n_local = N // 8
flat = ShufflePlan.for_mesh(mesh1, 16, n_local, 2.5, ("data",))
hier = ShufflePlan.for_mesh(mesh2, 16, n_local, 2.5, ("dc", "node"))
d0 = jnp.zeros((N, 3), jnp.int32)
b0 = jnp.zeros((N,), jnp.int32)

def shuf(plan):
    def f(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return r.data, r.valid, r.dropped
    return f

def shuf_comb(plan):
    def f(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return plan.combine(r.data.astype(jnp.float32) * 2.0, r, n_local)
    return f

def count3(fn, mesh, spec):
    f = shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                  out_specs=(spec, spec, P()), check_vma=False)
    return collective_counts(f, d0, b0)["all_to_all"]

def count2(fn, mesh, spec):
    f = shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                  out_specs=(spec, spec), check_vma=False)
    return collective_counts(f, d0, b0)["all_to_all"]

s1, s2 = P("data"), P(("dc", "node"))
out = {
    "flat_shuffle": count3(shuf(flat), mesh1, s1),
    "hier_shuffle": count3(shuf(hier), mesh2, s2),
    "flat_shuffle_w4": count3(shuf(dataclasses.replace(flat, chunks=4)),
                              mesh1, s1),
    "hier_shuffle_w4": count3(shuf(dataclasses.replace(hier, chunks=4)),
                              mesh2, s2),
}
out["flat_with_combine"] = count2(shuf_comb(flat), mesh1, s1)
out["hier_with_combine"] = count2(shuf_comb(hier), mesh2, s2)

# chunked W=4 must deliver the identical record multiset as W=1
rng = np.random.default_rng(0)
data = rng.integers(0, 1 << 20, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
def run_plan(plan):
    dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh1, s1))
    bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh1, s1))
    def udf(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return r.data.reshape(-1, 3), r.valid.reshape(-1), r.dropped
    with mesh1:
        rd, rv, drop = shard_map(udf, mesh=mesh1, in_specs=(s1, s1),
                                 out_specs=(s1, s1, P()),
                                 check_vma=False)(dd, bd)
    assert int(drop) == 0
    return sorted(map(tuple, np.asarray(rd)[np.asarray(rv)]))
out["chunked_match"] = run_plan(flat) == run_plan(
    dataclasses.replace(flat, chunks=4))
print("RESULT " + json.dumps(out))
"""


def collectives_per_hop() -> Dict[str, object]:
    """jaxpr-count all_to_all per shuffle hop on 8 virtual devices (own
    subprocess: XLA_FLAGS must be set before jax initializes)."""
    env = dict(os.environ)
    # semantics on 8 virtual CPU devices: the child never needs the chip,
    # which a parent that has touched JAX may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _COLLECTIVES_CODE], env=env,
                          capture_output=True, text=True, timeout=520)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])


def wire_bytes_per_hop(n: int = 1 << 16, num_dest: int = 8) -> Dict[str, float]:
    """Static wire accounting of one flat shuffle hop over int32-pair
    records: the fused one-tensor frame vs the retired 4-tensor layout."""
    capacity = 2 * n // num_dest
    rec_bytes = 8                              # (key, value) int32 pair
    # retired layout: data + valid(bool byte) + bucket(i32) + src(i32),
    # each its own capacity-padded all_to_all tile
    legacy = num_dest * capacity * (rec_bytes + 1 + 4 + 4)
    fused_min = num_dest * WireFrame("int32", (2,)).tile_nbytes(capacity)
    fused_full = num_dest * WireFrame(
        "int32", (2,), meta=("bucket", "src")).tile_nbytes(capacity)
    return {
        "n": n, "num_dest": num_dest, "capacity": capacity,
        "rec_bytes": rec_bytes,
        "legacy_4tensor_bytes": legacy,
        "fused_frame_bytes_min": fused_min,    # wire_meta="min" (dataflow)
        "fused_frame_bytes_full": fused_full,  # wire_meta="full" (combine)
        "reduction_min": legacy / fused_min,
        "reduction_full": legacy / fused_full,
    }


def run(csv: bool = True, json_path: str | None = None) -> List[str]:
    rng = np.random.default_rng(0)
    lines: List[str] = []
    results: Dict[str, Dict[str, float]] = {}

    def record(name: str, t: float, elems: int, extra: str = ""):
        results[name] = {"us_per_call": t * 1e6,
                         "melem_per_s": elems / t / 1e6,
                         "owner": OWNER}
        lines.append(f"kernel_{name},{t * 1e6:.1f},"
                     f"{elems / t / 1e6:.2f}Melem/s{extra}")

    # -- bucket histogram -----------------------------------------------------
    n, buckets = 1 << 16, 256
    ids = jnp.asarray(rng.integers(0, buckets, size=n).astype(np.int32))
    record("bucket_hist_pallas_interp",
           _time(lambda x: ops.bucket_histogram(x, buckets), ids), n)
    record("bucket_hist_oracle",
           _time(lambda x: ref.bucket_histogram_ref(x, buckets), ids), n)

    # -- fused partition/pack vs the argsort send path ------------------------
    n, num_dest = 1 << 16, 8
    capacity = 2 * n // num_dest
    dest = jnp.asarray(rng.integers(0, num_dest, size=n).astype(np.int32))
    data = jnp.asarray(rng.integers(0, 1 << 30, size=(n, 4)).astype(np.int32))
    baseline = _argsort_send_layout(num_dest, capacity)
    fused = jax.jit(lambda d, x: ops.partition_pack(
        [x], d, num_dest, capacity, use_pallas=False))
    fused_k = jax.jit(lambda d, x: ops.partition_pack(
        [x], d, num_dest, capacity, use_pallas=True))
    t_arg = _time(baseline, dest, data)
    t_fused = _time(fused, dest, data)
    t_fused_k = _time(fused_k, dest, data)
    record("partition_argsort_baseline", t_arg, n)
    record("partition_pack_fused", t_fused, n,
           extra=f" speedup_vs_argsort={t_arg / t_fused:.2f}x")
    record("partition_pack_pallas_interp", t_fused_k, n)
    results["partition_speedup_vs_argsort"] = {
        "ratio": t_arg / t_fused, "n": n, "num_dest": num_dest,
        "owner": OWNER}

    # -- bitonic sort (multi-segment blocks) ----------------------------------
    rows, cols = 8, 4096
    keys = jnp.asarray(rng.integers(0, 1 << 30,
                                    size=(rows, cols)).astype(np.int32))
    vals = jnp.asarray(np.arange(rows * cols,
                                 dtype=np.int32).reshape(rows, cols))
    record("bitonic_sort_8x4096_pallas_interp",
           _time(lambda k, v: ops.sort_kv_segments(k, v, algo="bitonic"),
                 keys, vals), rows * cols)
    record("bitonic_sort_8x4096_oracle",
           _time(ref.sort_kv_segments_ref, keys, vals), rows * cols)

    # -- segmented stage-2 sort: bpd segments of R/bpd vs one of R ------------
    # pinned to the bitonic kernel so the trajectory metric keeps its
    # historical meaning (segment economics of ONE algorithm, not the
    # autotuner picking different winners at the two shapes)
    r, bpd = 1 << 16, 16
    flat = jnp.asarray(rng.integers(0, 1 << 30, size=r).astype(np.int32))
    seg = flat.reshape(bpd, r // bpd)
    seg_times = _time_grid(
        {"seg": lambda _: ops.sort_segments(seg, algo="bitonic"),
         "one": lambda _: ops.sort_segments(flat.reshape(1, r),
                                            algo="bitonic")},
        (None,))
    t_seg, t_one = seg_times["seg"], seg_times["one"]
    record("segmented_sort_16x4096_pallas_interp", t_seg, r,
           extra=f" speedup_vs_single_segment={t_one / t_seg:.2f}x")
    record("segmented_sort_1x65536_pallas_interp", t_one, r)
    record("segmented_sort_16x4096_oracle",
           _time(lambda x: ref.sort_segments_ref(x), seg), r)
    # published through the metrics registry too, so one snapshot carries
    # the perf trajectory alongside the runtime series
    REGISTRY.gauge("kernel.segmented_speedup_vs_single").set(t_one / t_seg)
    results["segmented_speedup_vs_single"] = {
        "ratio": t_one / t_seg, "r": r, "bpd": bpd, "owner": OWNER,
        "metric": "kernel.segmented_speedup_vs_single",
        "registry_value": REGISTRY.gauge(
            "kernel.segmented_speedup_vs_single").value}

    # -- autotune sweep: (R, bpd) × {bitonic, radix, oracle} KV cells ---------
    # Every cell is timed three ways on the same data: each candidate pinned
    # via algo=..., then the autotuned entry point (algo=None, which is what
    # the stage-2 hot path actually calls). autotune.choose() supplies the
    # decision record — its own synthetic-data measurements and the reason
    # any candidate was skipped (radix in interpret mode is only measured
    # inside its envelope; there are no silent caps).
    table: Dict[str, Dict[str, object]] = {}
    for r_tot in SWEEP_R:
        for bpd_c in SWEEP_BPD:
            s = r_tot // bpd_c
            k = jnp.asarray(rng.integers(
                0, np.iinfo(np.int32).max,
                size=(bpd_c, s)).astype(np.int32))
            v = jnp.asarray(np.arange(r_tot, dtype=np.int32)
                            .reshape(bpd_c, s))
            ch = autotune.choose(bpd_c, s, jnp.int32, kv=True)
            fns = {a: (lambda kk, vv, a=a:
                       ops.sort_kv_segments(kk, vv, algo=a))
                   for a in autotune.ALGOS if a not in ch.skipped}
            fns["autotuned"] = ops.sort_kv_segments
            times = _time_grid(fns, (k, v))
            # heavy-tail CPU timing: the autotuned entry dispatches to one
            # of the pinned candidates, so if its noise floor is >5% off
            # the best pinned one the estimate hasn't converged — pool more
            # interleaved rounds (elementwise min) before recording.
            for _ in range(2):
                t_best = min(t for a, t in times.items() if a != "autotuned")
                if times["autotuned"] <= t_best / 0.95:
                    break
                more = _time_grid(fns, (k, v))
                times = {a: min(times[a], more[a]) for a in times}
            per_algo = {a: r_tot / t / 1e6 for a, t in times.items()
                        if a != "autotuned"}
            t_auto = times["autotuned"]
            auto_melem = r_tot / t_auto / 1e6
            cell = f"{bpd_c}x{s}"
            table[cell] = {
                "r": r_tot, "bpd": bpd_c, "segment_len": s,
                "melem_per_s": per_algo,
                "autotuned_melem_per_s": auto_melem,
                "chosen": ch.algo, "source": ch.source,
                "skipped": dict(ch.skipped)}
            results[f"autotune_choice_{cell}"] = {
                "owner": OWNER, "algo": ch.algo, "source": ch.source,
                "melem_per_s": dict(ch.melem),
                "skipped": dict(ch.skipped)}
            lines.append(
                f"kernel_sort_kv_{cell},{t_auto * 1e6:.1f},"
                f"{auto_melem:.2f}Melem/s autotuned={ch.algo} " +
                " ".join(f"{a}={m:.2f}" for a, m in sorted(per_algo.items())))
    results["segmented_sort_table"] = {"owner": OWNER, "cells": table}
    results["autotune_table"] = {"owner": OWNER,
                                 "entries": autotune.export_table()}

    # -- one-wire-tensor shuffle: wire bytes + collective counts per hop ------
    wb = wire_bytes_per_hop()
    results["wire_bytes_per_hop"] = dict(wb, owner=OWNER)
    lines.append(
        f"kernel_wire_bytes_per_hop,0,"
        f"legacy={wb['legacy_4tensor_bytes']} "
        f"fused_min={wb['fused_frame_bytes_min']} "
        f"reduction={wb['reduction_min']:.2f}x "
        f"(int32-pair records, {wb['num_dest']} dests, "
        f"cap={wb['capacity']})")
    cc = collectives_per_hop()
    results["collectives_per_hop"] = dict(cc, owner=OWNER)
    lines.append(
        f"kernel_collectives_per_hop,0,"
        f"flat={cc['flat_shuffle']} hier={cc['hier_shuffle']} "
        f"flat_w4={cc['flat_shuffle_w4']} hier_w4={cc['hier_shuffle_w4']} "
        f"flat+combine={cc['flat_with_combine']} "
        f"hier+combine={cc['hier_with_combine']} "
        f"chunked_match={cc['chunked_match']} "
        f"(all_to_all per hop; was 4 flat / 9 hier / 7 / 15 with combine)")

    if json_path:
        from repro.kernels.autotune import interpret_default
        # other benches (streaming, chaos, obs) merge their trajectory
        # points into the same file, each stamped with an "owner" field —
        # keep every row another owner wrote, rewrite only our own.
        # Rows without an owner stamp are legacy kernel rows.
        try:
            with open(json_path) as f:
                prior = json.load(f).get("results", {})
            results.update({k: v for k, v in prior.items()
                            if isinstance(v, dict)
                            and v.get("owner", OWNER) != OWNER
                            and k not in results})
        except (OSError, ValueError):
            pass
        payload = {
            "schema": "repro.kernel_bench.v1",
            "backend": jax.default_backend(),
            "pallas_interpret": interpret_default(),
            "note": ("CPU container: Pallas rows run in interpret mode; "
                     "jnp/XLA rows are compiled. The trajectory point is "
                     "partition_speedup_vs_argsort (fused O(n) send path "
                     "vs the retired stable-argsort layout)."),
            "results": results,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        lines.append(f"kernel_bench_json,0,written {json_path}")
    return lines


def main() -> None:
    args = sys.argv[1:]
    json_path = None
    check = "--check" in args
    if "--json" in args:
        idx = args.index("--json") + 1
        if idx >= len(args):
            print("usage: kernel_bench.py [--json PATH] [--check]")
            sys.exit(2)
        json_path = args[idx]
    elif check:
        json_path = "BENCH_kernels.json"
    for line in run(json_path=json_path):
        print(line)
    if check:
        with open(json_path) as f:
            payload = json.load(f)
        res = payload["results"]
        failures = []
        ratio = res["partition_speedup_vs_argsort"]["ratio"]
        if ratio <= 1.0:
            failures.append(f"fused partition path is not beating the "
                            f"argsort layout (speedup {ratio:.2f}x)")
        wb = res["wire_bytes_per_hop"]
        if wb["reduction_min"] < 2.0:
            failures.append(f"fused frame is not >=2x smaller than the "
                            f"4-tensor layout ({wb['reduction_min']:.2f}x)")
        cc = res["collectives_per_hop"]
        if cc["flat_shuffle"] > 1 or cc["flat_shuffle_w4"] > 4:
            failures.append(f"flat shuffle regressed above 1 all_to_all per "
                            f"hop per chunk ({cc['flat_shuffle']}, "
                            f"W4={cc['flat_shuffle_w4']})")
        if cc["hier_shuffle"] > 2 or cc["hier_shuffle_w4"] > 8:
            failures.append(f"hierarchical shuffle regressed above 2 "
                            f"all_to_all per hop per chunk "
                            f"({cc['hier_shuffle']}, "
                            f"W4={cc['hier_shuffle_w4']})")
        if cc["flat_with_combine"] > 2 or cc["hier_with_combine"] > 4:
            failures.append(f"combine collective count regressed "
                            f"(flat {cc['flat_with_combine']} > 2 or hier "
                            f"{cc['hier_with_combine']} > 4)")
        if not cc["chunked_match"]:
            failures.append("chunked (W=4) shuffle delivery differs from "
                            "W=1")
        seg = res["segmented_speedup_vs_single"]["ratio"]
        if seg < 1.3:
            failures.append(f"segmented stage-2 sort speedup vs single "
                            f"segment fell below the 1.3x floor "
                            f"({seg:.2f}x)")
        for cell, row in sorted(
                res["segmented_sort_table"]["cells"].items()):
            best_algo = max(row["melem_per_s"], key=row["melem_per_s"].get)
            best = row["melem_per_s"][best_algo]
            if row["autotuned_melem_per_s"] < 0.95 * best:
                failures.append(
                    f"autotuned sort_kv_segments at {cell} runs "
                    f"{row['autotuned_melem_per_s']:.2f} Melem/s, below "
                    f"0.95x the best candidate {best_algo} ({best:.2f}; "
                    f"autotuner chose {row['chosen']})")
            oracle = row["melem_per_s"].get("oracle")
            if oracle and row["autotuned_melem_per_s"] < 0.95 * oracle:
                failures.append(
                    f"autotuned sort_kv_segments at {cell} is slower than "
                    f"the jnp oracle ({row['autotuned_melem_per_s']:.2f} vs "
                    f"{oracle:.2f} Melem/s)")
        if failures:
            for msg in failures:
                print(f"CHECK FAILED: {msg}")
            sys.exit(1)
        ncells = len(res["segmented_sort_table"]["cells"])
        print(f"CHECK OK: fused partition {ratio:.2f}x vs argsort; wire "
              f"bytes {wb['reduction_min']:.2f}x smaller; collectives/hop "
              f"flat={cc['flat_shuffle']} hier={cc['hier_shuffle']}; "
              f"W=4 delivery matches W=1; segmented speedup {seg:.2f}x "
              f">= 1.3; autotuned sort within 0.95x of the best candidate "
              f"on all {ncells} sweep cells")


if __name__ == "__main__":
    main()
