"""Smoke run of Sphere's main path on one TPU chip (or four with --chips 4).

Drives the system through the entry points a user calls and checks every
answer against a plain reference; each phase prints one JSON line, and any
failure ends the run with a non-zero exit:

  device    what JAX reports; anything but a TPU stops the run;
  kernels   the partition, bitonic and radix Pallas kernels compiled by
            Mosaic (``interpret=False``) at the terasort cell's shapes, each
            equal bit for bit to its ``repro.kernels.ref`` oracle;
  terasort  the paper's Table 1 workload (``Dataflow.source(codec).sort``
            on ``SPMDExecutor``, Pallas partition/pack send path, stage-2
            kernel chosen by the autotuner) over 100-byte records, N sized
            to the largest power of two whose compiled program fits 12 GiB,
            checked against ``np.argsort`` on the host;
  stream    the streaming word count of ``examples/streaming_wordcount.py``
            under ``StreamExecutor``: three weighted tenants, 2^20-event
            micro-batches, checkpoints sealed into an in-process Sector
            deployment, one compile, final counts equal to ``np.bincount``.

``--chips 4`` runs only the cross-chip shuffle and what it is compared
with: terasort at 2^21 records per chip (the one-chip N spread over four
chips) on a flat ``(4,)`` mesh and on a ``(2, 2)`` ``(dc, node)``
hierarchical plan.

The times printed are smoke observations, not benchmark numbers. The last
line is ``{"ok": true, "device": {...}}``.

  python chip_smoke.py [--seed S] [--chips 1|4]
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.introspect import collective_counts  # noqa: E402
from repro.core.records import RecordCodec  # noqa: E402
from repro.core.shuffle import ShufflePlan  # noqa: E402
from repro.kernels import autotune, ref  # noqa: E402
from repro.kernels.bitonic_sort import sort_kv_segments_pallas  # noqa: E402
from repro.kernels.partition import partition_rank_pallas  # noqa: E402
from repro.kernels.radix_sort import sort_kv_segments_radix  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.sphere.dataflow import Dataflow, SPMDExecutor  # noqa: E402

#: TeraSort record: 100 bytes, cut from a 10-byte key to one int32 key.
PAYLOAD_BYTES = 96
CODEC = RecordCodec.from_fields({"key": np.int32,
                                 "payload": (np.uint8, (PAYLOAD_BYTES,))})
#: compiled-program bound (arguments + outputs + temporaries) out of the
#: chip's 16 GiB, leaving room for the process's other live buffers.
MEMORY_LIMIT = 12 << 30
MIN_LOG2_N = 23
#: records per chip of the four-chip comparison: 2^21, so the four chips
#: hold the one-chip run's 2^23 records. Compiling the (dc, node) program
#: takes host memory and time that grow with the per-chip count (the TPU
#: compiler re-lays out the 100-byte uint8 wire rows): 11.5 GB and 168 s
#: at 2^20, 22.8 GB and 292 s at 2^21 when compiled for a described v5e:2x2
#: on a CPU host. Larger per-chip counts were not tried on the chip host;
#: the one-chip 2^23 per chip stays the goal once the wire is repaired.
FOUR_CHIP_LOG2_N = 21
#: per-bucket headroom over the uniform share, for the shuffle and the
#: stage-2 segments alike.
CAPACITY_FACTOR = 1.5
#: stage-2 segment length the bucket count is chosen for: one power of two
#: for the bitonic network and inside the radix kernel's envelope.
SEGMENT = 4096
STEADY_CALLS = 3

STREAM_BATCH = 1 << 20
STREAM_REQUEST = 1 << 16
STREAM_REQUESTS_PER_TENANT = 48     # 144 requests -> 9 micro-batches
STREAM_VOCAB = 50_000
TENANTS = {"free": 1.0, "pro": 3.0, "enterprise": 4.0}


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def timed(fn):
    """(result, seconds) of ``fn()``, ending in block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- terasort ----------------------------------------------------------------


class Terasort:
    """One terasort deployment: a mesh layout, its executor and pipeline,
    at ``n_per_device`` records per chip. ``sort_algo=None`` lets the
    autotuner pick the stage-2 kernel, as users get it."""

    def __init__(self, mesh, axes, n_per_device: int, hierarchical=False,
                 sort_algo=None):
        self.mesh, self.axes, self.n_per_device = mesh, axes, n_per_device
        ndev = mesh.size
        self.n = n_per_device * ndev
        self.spec = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
        # buckets per chip such that a stage-2 segment (capacity_factor
        # headroom over the uniform share of the receive buffer) fits
        # SEGMENT slots
        recv = ShufflePlan.for_mesh(mesh, ndev, n_per_device, CAPACITY_FACTOR,
                                    axes).recv_slots
        bpd = -(-int(recv * CAPACITY_FACTOR) // (SEGMENT - 1))
        num_buckets = bpd * ndev
        plan = (ShufflePlan.for_mesh(mesh, num_buckets, n_per_device,
                                     CAPACITY_FACTOR, axes, use_pallas=True)
                if hierarchical else None)
        self.df = Dataflow.source(CODEC).sort(
            key=lambda r: r["key"], num_buckets=num_buckets,
            capacity_factor=CAPACITY_FACTOR)
        self.ex = SPMDExecutor(mesh, axes=axes, plan=plan, use_pallas=True,
                               sort_algo=sort_algo)
        # the stage-2 cell as the executor will sort it
        self.recv, self.bpd, self.seg_cap = self.ex.sort_cell(
            self.df.stages[-1], n_per_device)

    def shapes(self):
        return ({"key": jax.ShapeDtypeStruct((self.n,), jnp.int32,
                                             sharding=self.spec),
                 "payload": jax.ShapeDtypeStruct(
                     (self.n, PAYLOAD_BYTES), jnp.uint8, sharding=self.spec)},
                jax.ShapeDtypeStruct((self.n,), jnp.bool_, sharding=self.spec))

    def compile(self):
        """AOT-compile the fused program (resolving the stage-2 kernel
        through the autotuner, as a run does); returns (compiled, seconds,
        memory bytes)."""
        t0 = time.perf_counter()
        compiled = self.ex.program(self.df).lower(*self.shapes()).compile()
        secs = time.perf_counter() - t0
        m = compiled.memory_analysis()
        mem = {"arguments": m.argument_size_in_bytes,
               "outputs": m.output_size_in_bytes,
               "temporaries": m.temp_size_in_bytes}
        mem["total"] = sum(mem.values())
        return compiled, secs, mem

    def all_to_all(self) -> int:
        return collective_counts(self.ex.program(self.df),
                                 *self.shapes())["all_to_all"]

    def make_records(self, seed: int):
        """Uniform int32 keys over [0, 2^31 - 1) (the range the default
        splitters cut evenly) and a 96-byte payload whose first 4 bytes are
        the record number (gensort's row id), made on the chips, sharded."""
        n = self.n

        def make():
            kk, kp = jax.random.split(jax.random.key(seed))
            keys = jax.random.randint(kk, (n,), 0, 2**31 - 1, jnp.int32)
            rowid = jax.lax.bitcast_convert_type(
                jnp.arange(n, dtype=jnp.uint32), jnp.uint8)
            fill = jax.random.bits(kp, (n, PAYLOAD_BYTES - 4), jnp.uint8)
            return {"key": keys,
                    "payload": jnp.concatenate([rowid, fill], axis=1)}

        return jax.jit(make, out_shardings=self.spec)()

    def run(self, records, valid):
        res = self.ex.run(self.df, records, valid=valid)
        jax.block_until_ready((res.records, res.valid, res.dropped))
        return res

    def cell_choice(self):
        key = autotune.cell_key(self.bpd, self.seg_cap, jnp.int32, kv=True)
        table = autotune.export_table()
        check(key in table, f"terasort resolved the cell {key}")
        return table[key]


def host_check(records_h, res, n: int):
    """Globally sorted, a permutation of the input, payload riding with
    its key — against np.argsort on the host. Returns (keys, payload) of
    the output's valid rows."""
    keys_h, pay_h = records_h["key"], records_h["payload"]
    valid = np.asarray(res.valid)
    out_k = np.asarray(res.records["key"])[valid]
    out_p = np.asarray(res.records["payload"])[valid]
    check(out_k.shape[0] == n, f"{out_k.shape[0]} of {n} records delivered")
    check(np.array_equal(out_k, keys_h[np.argsort(keys_h, kind="stable")]),
          "keys equal np.argsort order")
    rowid = out_p[:, :4].copy().view("<u4").ravel()
    seen = np.zeros(n, bool)
    seen[rowid] = True
    check(bool(seen.all()), "output is a permutation of the input")
    check(np.array_equal(keys_h[rowid], out_k), "every key kept its record")
    check(np.array_equal(pay_h[rowid], out_p),
          "every payload rode with its key")
    return out_k, out_p


def size_terasort(mesh, axes):
    """Largest power-of-two N >= 2^23 whose compiled program fits
    MEMORY_LIMIT; returns (Terasort, compile seconds, memory, sizing)."""
    best, sizing = None, {}
    log2 = MIN_LOG2_N
    while True:
        ts = Terasort(mesh, axes, 1 << log2)
        _, secs, mem = ts.compile()
        sizing[f"2^{log2}"] = mem["total"]
        if mem["total"] > MEMORY_LIMIT:
            break
        best = (ts, secs, mem)
        log2 += 1
    check(best is not None,
          f"2^{MIN_LOG2_N} records fit {MEMORY_LIMIT} bytes ({sizing})")
    return best + (sizing,)


def run_terasort(ts: Terasort, seed: int):
    records = ts.make_records(seed)
    valid = jax.device_put(np.ones(ts.n, bool), ts.spec)
    records_h = jax.tree.map(np.asarray, records)
    res, first = timed(lambda: ts.run(records, valid))
    steady = []
    for _ in range(STEADY_CALLS):
        res, secs = timed(lambda: ts.run(records, valid))
        steady.append(secs)
    dropped = int(res.dropped)
    check(dropped == 0, f"dropped == 0 (got {dropped})")
    out = host_check(records_h, res, ts.n)
    return records, res, out, first, steady


# -- phases ------------------------------------------------------------------


def phase_device(chips: int):
    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    emit("device", **info)
    if d.platform != "tpu":
        sys.exit(f"no TPU: JAX reports {info}; this smoke never runs on "
                 f"another backend")
    if len(devices) < chips:
        sys.exit(f"--chips {chips} needs {chips} devices, JAX reports "
                 f"{len(devices)}")
    return info


def phase_kernels(ts: Terasort, seed: int):
    """Each kernel compiled by Mosaic at the terasort cell's shapes, equal
    bit for bit to its oracle."""
    key = jax.random.key(seed + 1)
    rows = {}

    def compiled(fn, *args):
        c = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in c.as_text(), "kernel lowered to Mosaic")
        return c

    # partition rank: the send hop (one destination per chip) and the
    # stage-2 regroup (bpd segments, id bpd = invalid overflow slot)
    for name, n, num_dest in (("partition_send", ts.n_per_device,
                               ts.mesh.size),
                              ("partition_regroup", ts.recv, ts.bpd)):
        dest = jax.random.randint(key, (n,), 0, num_dest + 1, jnp.int32)
        c = compiled(lambda d, nd=num_dest: partition_rank_pallas(
            d, nd, interpret=False), dest)
        (rank, counts), secs = timed(lambda: c(dest))
        want_rank, want_counts = ref.partition_rank_ref(dest, num_dest)
        in_range = dest < num_dest
        check(bool(jnp.array_equal(counts, want_counts)), f"{name} counts")
        check(bool(jnp.all(jnp.where(in_range, rank == want_rank, True))),
              f"{name} ranks")
        rows[name] = {"shape": [n], "num_dest": num_dest, "seconds": secs}

    # segment sorts at the stage-2 cell: distinct keys (an odd multiplier
    # is a bijection mod 2^32), so even the unstable network must match
    # the stable oracle's permutation exactly
    shape = (ts.bpd, ts.seg_cap)
    salt = jax.random.bits(key, (), jnp.uint32)
    keys = jax.lax.bitcast_convert_type(
        jnp.arange(shape[0] * shape[1], dtype=jnp.uint32)
        * jnp.uint32(2654435761)
        + salt, jnp.int32).reshape(shape)
    vals = jnp.arange(shape[0] * shape[1], dtype=jnp.int32).reshape(shape)
    want_k, want_v = ref.sort_kv_segments_ref(keys, vals)
    for name, kernel in (("bitonic", sort_kv_segments_pallas),
                         ("radix", sort_kv_segments_radix)):
        c = compiled(lambda k, v, f=kernel: f(k, v, interpret=False),
                     keys, vals)
        (got_k, got_v), secs = timed(lambda: c(keys, vals))
        check(bool(jnp.array_equal(got_k, want_k)), f"{name} keys")
        check(bool(jnp.array_equal(got_v, want_v)), f"{name} payload")
        rows[name] = {"shape": list(shape), "seconds": secs}
    emit("kernels", interpret=False, tpu_custom_call=True,
         equal_to_oracle=True, kernels=rows,
         note="first call after compile; smoke observation")


def phase_terasort(ts: Terasort, compile_s, mem, sizing, seed: int):
    _, _, _, first, steady = run_terasort(ts, seed)
    choice = ts.cell_choice()
    emit("terasort", records=ts.n, log2_records=ts.n.bit_length() - 1,
         record_bytes=CODEC.nbytes, input_bytes=ts.n * CODEC.nbytes,
         reduced=["key: TeraSort's 10-byte key cut to one int32 (4 bytes); "
                  "payload 96 bytes keeps the 100-byte record"],
         num_buckets=ts.bpd * ts.mesh.size, capacity_factor=CAPACITY_FACTOR,
         cell=[ts.bpd, ts.seg_cap], memory_analysis=mem,
         memory_limit=MEMORY_LIMIT, sizing_total_bytes=sizing,
         compile_s=compile_s, first_call_s=first, steady_call_s=steady,
         dropped=0, sort_choice={k: choice[k] for k in
                                 ("algo", "source", "melem", "skipped")},
         checks=["globally sorted == np.argsort", "permutation",
                 "payload rides with key"],
         note="times are smoke observations, not benchmark numbers")


class _RecordingClient:
    """Sector client that remembers what it uploaded (the read-back check
    compares against it)."""

    def __init__(self, client):
        self._client = client
        self.uploads = collections.OrderedDict()

    def upload(self, path, data):
        self.uploads[path] = bytes(data)
        return self._client.upload(path, data)

    def __getattr__(self, name):
        return getattr(self._client, name)


def phase_stream(seed: int):
    import streaming_wordcount
    from repro.launch.train import make_sector
    from repro.sphere.chaos import StreamCheckpoint
    from repro.sphere.streaming import StreamExecutor, TenantQueue

    mesh = jax.make_mesh((1,), ("data",))
    queue = TenantQueue(quantum=float(STREAM_REQUEST))
    for tenant, weight in TENANTS.items():
        queue.register(tenant, weight=weight)
    ex = StreamExecutor(SPMDExecutor(mesh),
                        streaming_wordcount.build_pipeline(),
                        micro_batch=STREAM_BATCH,
                        carry_capacity=STREAM_VOCAB, queue=queue)
    rng = np.random.default_rng(seed)
    submitted = []
    with tempfile.TemporaryDirectory() as root:
        master, client, daemon = make_sector(root, num_slaves=4)
        recorder = _RecordingClient(client)
        ex.attach_sector(master, recorder, daemon=daemon)
        for _ in range(STREAM_REQUESTS_PER_TENANT):
            for tenant in TENANTS:
                # word ids with a Zipf-like skew, as in natural text
                words = ((rng.zipf(1.2, STREAM_REQUEST) - 1) % STREAM_VOCAB
                         ).astype(np.uint16)
                submitted.append(words)
                ex.submit({"word": words}, tenant=tenant)
        t0 = time.perf_counter()
        batches = ex.drain()
        wall = time.perf_counter() - t0
        stats = ex.stats()
        # read one uploaded checkpoint back from Sector
        path, sent = next(reversed(recorder.uploads.items()))
        got = client.download(path)
        check(got == sent, f"checkpoint {path} reads back byte-identical")
        ckpt = StreamCheckpoint.from_bytes(got)
    events = np.concatenate(submitted)
    snap = ex.carry_state()
    counts = np.zeros(STREAM_VOCAB, np.int64)
    check(len(np.unique(snap["key"])) == len(snap["key"]), "one row per word")
    counts[snap["key"]] = snap["value"]
    check(np.array_equal(counts, np.bincount(events, minlength=STREAM_VOCAB)),
          "final snapshot == np.bincount of everything submitted")
    check(stats["cache"]["misses"] == 1,
          f"exactly one compile (cache {stats['cache']})")
    check(len(batches) >= 8, f">= 8 micro-batches (got {len(batches)})")
    check(sum(b.dropped for b in batches) == 0, "no record dropped")
    emit("stream", micro_batch=STREAM_BATCH, batches=len(batches),
         events=int(events.size), tenants=TENANTS, vocab=STREAM_VOCAB,
         compiles=stats["cache"]["misses"], cache=stats["cache"],
         snapshot_equals_bincount=True,
         checkpoints_uploaded=len(recorder.uploads),
         checkpoint_read_back={"path": path, "bytes": len(got),
                               "step": ckpt.step, "identical": True},
         drain_s=wall, run_s=stats["run_seconds"],
         records_per_s=stats["records_per_s"],
         served={t: s["records_served"]
                 for t, s in stats["tenants"].items()},
         note="times are smoke observations, not benchmark numbers")


def phase_four_chips(n_per_device: int, seed: int):
    """Flat (4,) vs hierarchical (2, 2) terasort at the same per-chip N."""
    flat_mesh = jax.make_mesh((4,), ("data",))
    hier_mesh = jax.make_mesh((2, 2), ("dc", "node"))
    outs = {}
    for name, ts in (
            ("flat", Terasort(flat_mesh, ("data",), n_per_device)),
            ("hierarchical", Terasort(hier_mesh, ("dc", "node"),
                                      n_per_device, hierarchical=True))):
        _, compile_s, mem = ts.compile()
        check(mem["total"] <= MEMORY_LIMIT, f"{name} fits {MEMORY_LIMIT}")
        records, res, (out_k, out_p), first, steady = run_terasort(ts, seed)
        shards = {}
        for label, arr in (("input_key", records["key"]),
                           ("input_payload", records["payload"]),
                           ("output_payload", res.records["payload"])):
            sizes = {str(s.device.id): s.data.nbytes
                     for s in arr.addressable_shards}
            check(len(sizes) == 4 and len(set(sizes.values())) == 1
                  and sum(sizes.values()) == arr.nbytes,
                  f"{name} {label} split evenly over 4 chips: {sizes}")
            shards[label] = sizes
        a2a = ts.all_to_all()
        check(a2a == (2 if name == "hierarchical" else 1),
              f"{name}: {a2a} all_to_all for one hop")
        outs[name] = (out_k, out_p)
        emit(f"terasort_4chip_{name}", records=ts.n,
             records_per_chip=n_per_device,
             reduced=[f"2^{FOUR_CHIP_LOG2_N} records per chip, not the "
                      f"one-chip 2^{MIN_LOG2_N}: the (dc, node) program's "
                      f"compile needs host memory growing with it"],
             mesh=dict(ts.mesh.shape),
             num_buckets=ts.bpd * 4, cell=[ts.bpd, ts.seg_cap],
             memory_analysis_per_device=mem, shard_bytes=shards,
             all_to_all=a2a, compile_s=compile_s, first_call_s=first,
             steady_call_s=steady, dropped=0,
             sort_choice=ts.cell_choice()["algo"],
             note="times are smoke observations, not benchmark numbers")
    (fk, fp), (hk, hp) = outs["flat"], outs["hierarchical"]
    fo = np.argsort(fp[:, :4].copy().view("<u4").ravel())
    ho = np.argsort(hp[:, :4].copy().view("<u4").ravel())
    check(np.array_equal(fk, hk), "flat and hierarchical key sequences")
    check(np.array_equal(fp[fo], hp[ho]), "flat and hierarchical multisets")
    emit("terasort_4chip_compare", flat_equals_hierarchical=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    info = phase_device(args.chips)
    enable_compile_cache()
    if args.chips == 4:
        phase_four_chips(1 << FOUR_CHIP_LOG2_N, args.seed)
    else:
        one = jax.make_mesh((1,), ("data",))
        ts, compile_s, mem, sizing = size_terasort(one, ("data",))
        phase_kernels(ts, args.seed)
        phase_terasort(ts, compile_s, mem, sizing, args.seed)
        phase_stream(args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
