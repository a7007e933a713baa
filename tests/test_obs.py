"""Observability subsystem tests: span tracer, metrics registry, and the
instrumentation wired through the SPMD / host / streaming executors.

Tracer determinism follows the repo's virtual-clock discipline: inject a
counter clock and every duration is an exact integer, so assertions never
race the wall clock. Executor tests run on the 1-device mesh (collectives
still appear in the jaxpr; hop geometry is still recorded) and against
real tmp-dir Sector deployments for the host path."""

import collections
import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.core.records import RecordCodec
from repro.launch.train import make_sector
from repro.obs import NULL_TRACER, REGISTRY, MetricsRegistry, Tracer
from repro.obs import layers
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE
from repro.sphere.streaming import StreamExecutor, TenantQueue

NB = 8


@pytest.fixture(autouse=True)
def _fresh_registry():
    REGISTRY.reset()
    yield
    REGISTRY.reset()


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


# -- tracer ------------------------------------------------------------------


def test_span_nesting_and_virtual_clock():
    tr = Tracer(clock=_fake_clock())
    with tr.span("outer", kind="test") as outer:
        with tr.span("inner") as inner:
            pass
        outer.set(post=1)
    spans = {s.name: s for s in tr.buffer.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    # clock ticks: outer@1, inner@2..3, outer ends @4
    assert spans["inner"].duration == 1.0
    assert spans["outer"].duration == 3.0
    assert spans["outer"].attrs == {"kind": "test", "post": 1}


def test_span_records_exception_and_still_closes():
    tr = Tracer(clock=_fake_clock())
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    (sp,) = tr.buffer.spans()
    assert sp.end is not None
    assert sp.attrs["error"] == "ValueError: nope"


def test_perfetto_export_is_valid_trace_event_json(tmp_path):
    tr = Tracer(clock=_fake_clock())
    with tr.span("stage[0]", records=4):
        with tr.span("hop[0]"):
            tr.event("retry", segment=1)
    fork = tr.fork("host")
    with fork.span("phase[0]"):
        pass
    path = tr.to_perfetto(str(tmp_path / "t.json"))
    payload = json.loads(open(path).read())
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    kinds = collections.Counter(e["ph"] for e in evs)
    assert kinds == {"M": 2, "X": 3, "i": 1}       # 2 tracks, 3 spans, 1 evt
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert names == {"main", "host"}
    # nesting is expressed by time containment on the same tid
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    outer, inner = xs["stage[0]"], xs["hop[0]"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert xs["phase[0]"]["tid"] != outer["tid"]


def test_flame_self_time_excludes_children():
    tr = Tracer(clock=_fake_clock())
    with tr.span("a"):                 # 1..6: dur 5
        with tr.span("b"):             # 2..5: dur 3
            with tr.span("c"):         # 3..4: dur 1
                pass
    flame = tr.flame()
    rows = {l.split()[-1]: l.split() for l in flame.splitlines()[1:]}
    assert float(rows["main/a"][0]) == 5000.0          # total ms
    assert float(rows["main/a"][1]) == 2000.0          # self = 5 - 3
    assert float(rows["main/a/b"][1]) == 2000.0        # self = 3 - 1
    assert float(rows["main/a/b/c"][1]) == 1000.0


def test_tracer_thread_safety_and_per_thread_parenting():
    tr = Tracer()
    errs = []

    def work(i):
        try:
            for j in range(50):
                with tr.span(f"w{i}"):
                    with tr.span(f"w{i}.child"):
                        pass
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    spans = tr.buffer.spans()
    assert len(spans) == 4 * 50 * 2
    by_id = {s.span_id: s for s in spans}
    for s in spans:                     # children parent within their thread
        if s.parent_id is not None:
            assert by_id[s.parent_id].name == s.name.split(".")[0]


def test_null_tracer_is_falsy_noop():
    assert not NULL_TRACER
    assert NULL_TRACER.fork("x") is NULL_TRACER
    with NULL_TRACER.span("a", k=1) as sp:
        sp.set(more=2)                  # all swallowed
    NULL_TRACER.event("e")


# -- metrics -----------------------------------------------------------------


def test_counter_gauge_labels_and_type_clash():
    reg = MetricsRegistry()
    reg.counter("x.n").inc()
    reg.counter("x.n").inc(2)
    reg.counter("x.n", tenant="a").inc(5)
    reg.gauge("x.g").set(3.5)
    with pytest.raises(ValueError):
        reg.gauge("x.n")                # name already a counter
    with pytest.raises(ValueError):
        reg.counter("x.n").inc(-1)      # counters are monotonic
    snap = reg.snapshot()
    assert snap["x.n"]["value"] == 3
    assert snap['x.n{tenant="a"}']["value"] == 5
    assert snap["x.g"] == {"type": "gauge", "value": 3.5}


def test_histogram_percentiles_are_deterministic():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.6, 3.0, 3.5, 100.0):
        h.observe(v)
    # percentile = smallest bucket UPPER bound covering the quantile — a
    # pure function of the multiset, independent of observation order
    assert h.percentile(50) == 2.0
    assert h.percentile(99) == float("inf")   # overflow bucket
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["sum"] == pytest.approx(110.1)
    assert snap["buckets"] == {"1.0": 1, "2.0": 2, "4.0": 2, "inf": 1}
    # same observations, shuffled: identical snapshot
    h2 = MetricsRegistry().histogram("lat", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (100.0, 3.0, 1.5, 0.5, 3.5, 1.6):
        h2.observe(v)
    assert h2.snapshot() == snap


def test_snapshot_json_roundtrip_sorted(tmp_path):
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.counter("a", x="1").inc()
    reg.histogram("c").observe(0.5)
    path = str(tmp_path / "m.json")
    reg.to_json(path)
    loaded = json.loads(open(path).read())
    assert list(loaded) == sorted(loaded)
    assert loaded == json.loads(reg.to_json())


# -- SPMD executor instrumentation -------------------------------------------


def _wordcount(stream=False):
    def _emit(rec):
        return {"key": rec["key"].astype(jnp.int32),
                "value": jnp.ones_like(rec["key"], jnp.int32)}

    def _count(rec, valid):
        k, v, dropped = reduce_by_key_sum(rec["key"], rec["value"], valid)
        return {"key": k, "value": v}, k >= 0, dropped

    src = Dataflow.stream_source() if stream else Dataflow.source()
    return (src.map(_emit)
            .shuffle(by=lambda r: default_hash(r["key"], NB), num_buckets=NB)
            .reduce(_count))


def _records(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"key": rng.integers(0, 9, size=n).astype(np.int32)}


def _counts(res):
    rec = res.valid_records()
    return {int(k): int(v) for k, v in zip(rec["key"], rec["value"])}


def test_spmd_traced_run_spans_hops_and_metrics():
    mesh = jax.make_mesh((1,), ("data",))
    ex = SPMDExecutor(mesh)
    tr = Tracer()
    res = ex.run(_wordcount(), _records(), trace=tr)
    assert res.trace is tr
    names = [s.name for s in tr.buffer.spans()]
    # compile miss: lower / compile / introspect, then execute, then root
    assert names == ["spmd.lower", "spmd.compile", "spmd.introspect",
                     "spmd.execute", "spmd.run"]
    root = tr.buffer.spans()[-1]
    assert root.attrs["cache"] == "miss"
    assert root.attrs["wire_bytes"] > 0
    assert root.attrs["hops"], "hop geometry missing from the root span"
    snap = REGISTRY.snapshot()
    assert snap["spmd.runs"]["value"] == 1
    assert snap["spmd.shuffle.hops"]["value"] == 1
    assert snap["spmd.shuffle.wire_bytes"]["value"] == root.attrs["wire_bytes"]
    assert snap["spmd.collectives.all_to_all"]["value"] >= 1
    assert snap["spmd.cache.misses"]["value"] == 1
    assert snap["spmd.dropped"]["value"] == 0


def test_spmd_cache_hit_skips_compile_spans():
    mesh = jax.make_mesh((1,), ("data",))
    ex = SPMDExecutor(mesh)
    df = _wordcount()
    ex.run(df, _records())                       # untraced warm-up
    tr = Tracer()
    ex.run(df, _records(), trace=tr)
    names = [s.name for s in tr.buffer.spans()]
    assert names == ["spmd.execute", "spmd.run"]
    assert tr.buffer.spans()[-1].attrs["cache"] == "hit"
    assert REGISTRY.snapshot()["spmd.cache.hits"]["value"] == 1


def test_untraced_run_records_no_spans_but_counts_runs():
    mesh = jax.make_mesh((1,), ("data",))
    ex = SPMDExecutor(mesh)
    res = ex.run(_wordcount(), _records())
    assert res.trace is None
    snap = REGISTRY.snapshot()
    assert snap["spmd.runs"]["value"] == 1
    assert snap["spmd.shuffle.wire_bytes"]["value"] > 0
    # sync-requiring series are only recorded under a tracer
    assert "spmd.dropped" not in snap


# -- spans on the profiler's clock -------------------------------------------


def _host_events(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` session (host spans, no Python
    calls); the host plane's events as ``(name, start_ns, end_ns)``."""
    import glob
    import os
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]


def test_tracer_spans_reach_the_profiler(tmp_path):
    tr = Tracer(clock=_fake_clock())

    def work():
        with tr.span("outer", kind="test"):
            with tr.span("inner"):
                pass
    events = _host_events(tmp_path, work)
    got = {n: (s, e) for n, s, e in events if n in ("outer", "inner")}
    assert got["outer"][0] <= got["inner"][0]
    assert got["inner"][1] <= got["outer"][1]
    # the tracer's own record keeps its clock and attributes
    assert [s.name for s in tr.buffer.spans()] == ["inner", "outer"]
    assert tr.buffer.spans()[-1].attrs == {"kind": "test"}


def test_untraced_run_spans_enclose_the_program_ops(tmp_path):
    """With NULL_TRACER, a run still leaves ``spmd.run``, ``spmd.execute``
    and ``spmd.sentinel_check`` on the host plane of a profile, on the
    clock of the program's op events, which the op->layer map names."""
    mesh = jax.make_mesh((1,), ("data",))
    ex = SPMDExecutor(mesh)
    df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=4)
    rec = {"key": jnp.asarray(np.arange(256, dtype=np.int32)[::-1])}
    valid = jnp.ones((256,), jnp.bool_)
    ex.run(df, rec, valid=valid)                 # compiles outside
    jax.block_until_ready((rec, valid))

    def run():
        res = ex.run(df, rec, valid=valid)
        jax.block_until_ready(res.records)
    events = _host_events(tmp_path, run)
    module, op_map = layers.last_run()
    assert module == "jit_sphere_spmd" and op_map
    spans = {n: (s, e) for n, s, e in events if n.startswith("spmd.")}
    assert set(spans) == {"spmd.run", "spmd.execute", "spmd.sentinel_check"}
    ops = [(s, e) for n, s, e in events if n in op_map]
    assert ops
    run_s, run_e = spans["spmd.run"]
    assert all(run_s <= s and e <= run_e for s, e in ops)
    assert spans["spmd.execute"][0] <= min(s for s, _ in ops)
    check_s, check_e = spans["spmd.sentinel_check"]
    assert run_s <= check_s and check_e <= run_e


def test_published_map_is_the_program_that_ran():
    mesh = jax.make_mesh((1,), ("data",))
    ex = SPMDExecutor(mesh)
    df = _wordcount()
    ex.run(df, _records())
    module, op_map = layers.last_run()
    assert module == "jit_sphere_spmd"
    assert op_map is ex._cache[next(iter(ex._cache))].layers
    # the wordcount's map and reduce run in the UDF scope, its shuffle hop
    # in the exchange scope
    assert {"sphere.udf", "sphere.exchange", "sphere.move",
            "sphere.rank"} <= set(op_map.values())
    assert set(op_map.values()) <= set(layers.SCOPES.values())


_CACHED_MAP = """
import json, jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.obs import layers
from repro.sphere.dataflow import Dataflow, SPMDExecutor
ex = SPMDExecutor(jax.make_mesh((1,), ("data",)))
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=4)
ex.run(df, {"key": jnp.arange(64, dtype=jnp.int32)[::-1]})
print(json.dumps(layers.last_run()))
"""


def test_op_layer_map_of_a_program_from_the_compile_cache(tmp_path):
    """The second process loads the executable from the persistent compile
    cache and reads the same op->layer map from it."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    outs, entries = [], []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CACHED_MAP], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        outs.append(json.loads(out.stdout.strip().splitlines()[-1]))
        entries.append(sorted(os.listdir(tmp_path / "cache")))
    assert entries[0] and entries[1] == entries[0]     # nothing recompiled
    assert outs[0][0] == "jit_sphere_spmd"
    assert outs[1] == outs[0] and "sphere.bucket" in outs[0][1].values()


# -- host executor instrumentation -------------------------------------------


def _deploy(tmp_path, n=160, num_slaves=4, n_files=4):
    rng = np.random.default_rng(7)
    pages = rng.integers(0, 9, size=n).astype(np.int32)
    codec = RecordCodec.from_fields({"key": np.int32})
    master, client, daemon = make_sector(str(tmp_path), num_slaves=num_slaves)
    slices = np.split(codec.encode({"key": pages}), n_files)
    client.upload_dataset("/obs/in", [s.tobytes() for s in slices])
    daemon.run_until_stable()
    spes = [SPE(i, master.slaves[i].address, master, client.session_id)
            for i in range(num_slaves)]
    paths = [f"/obs/in.{i:05d}" for i in range(n_files)]
    want = dict(collections.Counter(pages.tolist()))
    codec_df = (Dataflow.source(codec)
                .map(lambda r: {"key": r["key"].astype(jnp.int32),
                                "value": jnp.ones_like(r["key"],
                                                       jnp.int32)})
                .shuffle(by=lambda r: default_hash(r["key"], NB),
                         num_buckets=NB)
                .reduce(lambda r, v: _reduce(r, v)))
    return master, client, spes, paths, want, codec_df


def _reduce(rec, valid):
    k, v, dropped = reduce_by_key_sum(rec["key"], rec["value"], valid)
    return {"key": k, "value": v}, k >= 0, dropped


def test_host_phase_times_without_tracer(tmp_path):
    master, client, spes, paths, want, df = _deploy(tmp_path)
    res = HostExecutor(master, client, spes).run(df, paths)
    assert _counts(res) == want
    assert res.trace is None
    assert [p["phase"] for p in res.phase_times] == [0, 1]
    assert res.phase_times[0]["terminator"] == "shuffle"
    assert res.phase_times[1]["terminator"] == "output"
    for p in res.phase_times:
        assert p["seconds"] > 0
        assert p["engine_s"] > 0          # SphereResult.elapsed_s flows in
        assert p["seconds"] >= p["engine_s"]
        assert p["segments"] > 0
    snap = REGISTRY.snapshot()
    assert snap["host.segments"]["value"] == sum(
        p["segments"] for p in res.phase_times)
    assert snap["host.phase_seconds"]["count"] == 2


def test_host_traced_run_segment_and_retry_spans(tmp_path):
    master, client, spes, paths, want, df = _deploy(tmp_path)
    spes[0].fail_after = 0                # first pick crashes -> retry
    tr = Tracer(track="host")
    res = HostExecutor(master, client, spes).run(df, paths, trace=tr)
    assert _counts(res) == want
    assert res.retries >= 1
    names = [s.name for s in tr.buffer.spans()]
    kinds = {n.split("[")[0] for n in names}
    assert {"host.run", "phase", "segment", "spe.read", "spe.udf",
            "hop"} <= kinds
    failed = [s for s in tr.buffer.spans()
              if s.name.startswith("segment[")
              and s.attrs.get("outcome") == "spe_failure"]
    assert failed, "the injected SPE crash left no failed-segment span"
    retry_events = [e for e in tr.buffer.events() if e.name == "retry"]
    assert len(retry_events) == res.retries
    snap = REGISTRY.snapshot()
    assert snap["host.retries"]["value"] == res.retries
    # spans parent correctly: every segment span sits under a phase span
    by_id = {s.span_id: s for s in tr.buffer.spans()}
    for s in tr.buffer.spans():
        if s.name.startswith("segment["):
            assert by_id[s.parent_id].name.startswith("phase[")


# -- streaming instrumentation -----------------------------------------------


def test_stream_batch_spans_and_tenant_latency_series():
    mesh = jax.make_mesh((1,), ("data",))
    q = TenantQueue()
    q.register("rt", weight=2.0, priority=0)
    q.register("batch", weight=1.0, priority=1)
    tr = Tracer(track="stream")
    ex = StreamExecutor(SPMDExecutor(mesh), _wordcount(stream=True),
                        micro_batch=16, carry_capacity=8, queue=q, trace=tr)
    for i in range(4):
        ex.submit(_records(8, seed=i), tenant="rt" if i % 2 else "batch")
    batches = ex.drain()
    assert batches
    batch_spans = [s for s in tr.buffer.spans()
                   if s.name.startswith("stream.batch[")]
    assert len(batch_spans) == len(batches)
    for s in batch_spans:
        assert s.attrs["records"] > 0
        assert "carry_rows" in s.attrs and "admission_wait_max" in s.attrs
    # the inner SPMD spans share the buffer (same trace through the stack)
    assert any(s.name == "spmd.run" for s in tr.buffer.spans())
    snap = REGISTRY.snapshot()
    assert snap["stream.batches"]["value"] == len(batches)
    for tenant in ("rt", "batch"):
        assert snap[f'tenant.admitted{{tenant="{tenant}"}}']["value"] == 2
        assert snap[f'tenant.delivered{{tenant="{tenant}"}}']["value"] == 2
        lat = snap[f'tenant.latency{{tenant="{tenant}"}}']
        assert lat["type"] == "histogram" and lat["count"] == 2
