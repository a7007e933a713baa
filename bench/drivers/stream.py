"""Stream: a multi-tenant word count under an open loop of requests.

The deployment (``configs/stream_wordcount_3tenant.json``) is the word
count pipeline (map each word to ``(word, 1)``, hash-shuffle, reduce by key
with the count carried across micro-batches) under ``StreamExecutor`` with a
weighted ``TenantQueue``, a ``StreamCheckpoint`` sealed into an in-process
Sector deployment at every batch. The traffic mix fixes the offered rate;
:mod:`bench.gen` makes the requests from the seed before the window.

The generator is single-threaded and open: each request is submitted when
it falls due, with ``now=`` its due time, and ``step()`` runs in between.
A request is delivered when the ``step()`` that carried it returns; its
latency is delivery minus due time, so a stall is charged to every request
it delays. After the window closes the loop keeps stepping until every
request that was due in it is delivered (for at most a minute).
"""

from __future__ import annotations

import shutil
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import faults, gen, spec, traceread, window
from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.sector import (Master, NodeAddress, ReplicationDaemon,
                          SectorClient, SecurityServer, SlaveNode, Topology)
from repro.sphere.dataflow import Dataflow, SPMDExecutor
from repro.sphere.streaming import QueueFull, StreamExecutor, TenantQueue

#: seconds of the window a ``--trace 1`` run traces, from its middle.
TRACED_S = 3.0
#: offset of the warm-up requests' seed from the run's: beyond any seed a
#: run is given, so no run's window repeats another's warm-up.
WARMUP_SEED = 1 << 40
#: how long after the window closes a due request may still be delivered.
LATE_S = 60.0


def pipeline(num_buckets: int) -> Dataflow:
    """The word count of ``examples/streaming_wordcount.py``: the user's
    code of this deployment."""
    def emit(rec):
        return {"key": rec["word"].astype(jnp.int32),
                "value": jnp.ones_like(rec["word"], jnp.int32)}

    def count(rec, valid):
        keys, sums, dropped = reduce_by_key_sum(rec["key"], rec["value"],
                                                valid)
        return {"key": keys, "value": sums}, keys >= 0, dropped

    return (Dataflow.stream_source()
            .map(emit)
            .shuffle(by=lambda r: default_hash(r["key"], num_buckets),
                     num_buckets=num_buckets)
            .reduce(count))


def sector(root: str, slaves: int, replication: int):
    """An in-process Sector deployment: master, ``slaves`` slaves over two
    racks, one client."""
    sec = SecurityServer()
    sec.add_user("bench", "pw")
    sec.allow_slaves("10.0.0.0/8")
    master = Master(sec, replication_factor=replication)
    topo = Topology(pods=1, racks=2, nodes_per_rack=(slaves + 1) // 2)
    for i in range(slaves):
        master.register_slave(SlaveNode(i, topo.address_of(i),
                                        f"{root}/slave{i}",
                                        ip=f"10.0.0.{i + 1}"))
    client = SectorClient(master, "bench", "pw",
                          client_addr=NodeAddress(0, 0, 0))
    return master, client, ReplicationDaemon(master)


class _RecordingClient:
    """Sector client that remembers the last checkpoint it uploaded."""

    def __init__(self, client):
        self._client = client
        self.last: Optional[Tuple[str, bytes]] = None

    def upload(self, path, data):
        self.last = (path, bytes(data))
        return self._client.upload(path, data)

    def __getattr__(self, name):
        return getattr(self._client, name)


class Cell:
    def __init__(self, cell: Dict[str, Any], seed: int,
                 fault: Optional[str] = None,
                 log: Callable[..., None] = lambda **k: None):
        self.dep, self.mix = cell["deployment"], cell["mix"]
        self.seed, self.fault, self.log = seed, fault, log
        dep = self.dep
        self.reference = spec.reference(dep["reference"])
        self.size = self.mix["request_events"]
        queue = TenantQueue(quantum=float(self.size),
                            capacity=dep["queue_capacity_per_tenant"])
        for tenant, weight in dep["tenants"].items():
            queue.register(tenant, weight=weight)
        mesh = jax.make_mesh(tuple(cell["mesh"]), tuple(cell["axes"]))
        inner = SPMDExecutor(mesh, axes=tuple(cell["axes"]))
        inner.run = faults.stream_inner(fault, inner.run, dep["vocab"])
        self.ex = StreamExecutor(inner, pipeline(dep["num_buckets"]),
                                 micro_batch=dep["micro_batch"],
                                 carry_capacity=dep["vocab"], queue=queue)
        self._root = tempfile.mkdtemp(prefix="bench_sector_")
        master, client, daemon = sector(self._root, dep["sector_slaves"],
                                        dep["sector_replication"])
        self.client = _RecordingClient(client)
        self.ex.attach_sector(master, self.client, daemon=daemon)
        self.submitted = []
        self.geometry = {}
        # warm up: full micro-batches from a stream of their own, counted
        # in the reference like every other request
        per_batch = dep["micro_batch"] // self.size
        warm = gen.requests(dict(self.mix, rate_events_per_s=self.size),
                            dep, dep["warmup_batches"] * per_batch,
                            abs(int(seed)) + WARMUP_SEED)
        for words, tenant in zip(warm.words, warm.tenant):
            self.ex.submit({"word": words}, tenant=tenant)
            self.submitted.append(words)
        while self.ex.queue.pending():
            self.ex.step()

    def window(self, seconds: float, trace: bool, rate: Optional[float] = None,
               wait: bool = True):
        """Offer the mix (at ``rate`` events/s when given: the knee sweep)
        for ``seconds``; with ``wait``, keep stepping until every request
        due in the window is delivered."""
        mix = self.mix if rate is None else dict(self.mix,
                                                 rate_events_per_s=rate)
        reqs = gen.requests(mix, self.dep, seconds, self.seed)
        count = len(reqs.due)
        ex, clock = self.ex, time.perf_counter
        delivered_at = np.full(count, np.nan)
        deliveries = np.zeros(count, np.int64)
        index: Dict[int, int] = {}
        self.batch_dropped = 0
        refused = 0
        stats0 = ex.stats()
        traced = tracer = None
        i = 0
        start = clock()
        due = start + reqs.due
        deadline = start + seconds + LATE_S
        backlog_at_close = None
        late = []
        # per step: wall time, and the executor's own time of it (the
        # program's run_seconds counter)
        step_wall, step_run = [], []
        run_before = run_start = ex._run_seconds
        while True:
            now = clock()
            if trace and tracer is None and now - start >= (seconds
                                                            - TRACED_S) / 2:
                tracer, traced_from = traceread.Profile(), clock()
            if tracer is not None and traced is None \
                    and now - traced_from >= TRACED_S:
                traced = tracer.stop()
            if backlog_at_close is None and now - start >= seconds:
                backlog_at_close = ex.queue.pending() * self.size
                if not wait:
                    break
            if i < count and due[i] <= now:
                late.append(now - due[i])
                with jax.profiler.TraceAnnotation("bench.stream.submit"):
                    while i < count and due[i] <= now:
                        dup = 2 if self.fault == "control" and i == 0 else 1
                        for _ in range(dup):
                            try:
                                tk = ex.submit({"word": reqs.words[i]},
                                               tenant=reqs.tenant[i],
                                               now=float(due[i]))
                                index[tk.req_id] = i
                            except QueueFull:
                                refused += 1
                        self.submitted.append(reqs.words[i])
                        i += 1
            if ex.queue.pending():
                t0 = clock()
                with jax.profiler.TraceAnnotation("bench.stream.step"):
                    batch = ex.step(now=now)
                t1 = clock()
                step_wall.append(t1 - t0)
                step_run.append(ex._run_seconds - run_before)
                run_before = ex._run_seconds
                if batch is not None:
                    self.batch_dropped += batch.dropped
                    for tk in batch.delivered:
                        j = index[tk.req_id]
                        deliveries[j] += 1
                        delivered_at[j] = t1
            elif i < count:
                with jax.profiler.TraceAnnotation("bench.stream.wait"):
                    time.sleep(max(0.0, min(due[i], deadline) - clock()))
            else:
                break
            if clock() > deadline:
                break
        if tracer is not None and traced is None:
            traced = tracer.stop()
        if backlog_at_close is None:
            backlog_at_close = 0
        stats = ex.stats()
        self.compiles_in_window = (stats["cache"]["misses"]
                                   - stats0["cache"]["misses"])
        self.batch_failures = (stats["batch_failures"]
                               - stats0["batch_failures"])
        self.deliveries = deliveries
        steps = len(step_wall)
        run_s = ex._run_seconds - run_start
        got = ~np.isnan(delivered_at)
        e2e = {}
        if got.any():
            e2e["stream_events_per_s"] = window.open_loop_rate(
                start, delivered_at[got], np.full(int(got.sum()), self.size))
            e2e["stream_p95_ms"] = window.latency_ms(due[got],
                                                     delivered_at[got], 95)
        host = {"host_ms_per_batch": (sum(step_wall) - run_s)
                / max(steps, 1) * 1e3,
                "executor_ms_per_batch": run_s / max(steps, 1) * 1e3}
        p50 = (window.latency_ms(due[got], delivered_at[got], 50)
               if got.any() else None)
        by_close = (np.count_nonzero(delivered_at[got] <= start + seconds)
                    * self.size / seconds)
        self.log(requests=count, offered_events_per_s=count * self.size
                 / seconds, delivered=int(got.sum()), refused=refused,
                 steps=steps, backlog_events_at_close=backlog_at_close,
                 p50_ms=p50, delivered_by_close_events_per_s=by_close,
                 generator_late_ms_max=max(late, default=0.0) * 1e3,
                 compiles_in_window=self.compiles_in_window,
                 step_s_median=float(np.median(step_wall)) if steps else None,
                 step_s_max=max(step_wall, default=None),
                 executor_s_max=max(step_run, default=None),
                 slowest_step=int(np.argmax(step_wall)) if steps else None,
                 **e2e, **host)
        return SimpleNamespace(
            start=start, end_to_end=e2e, attempted=count,
            failed=int(count - got.sum()), host=host,
            trace=traced, traced_calls=None,
            backlog_at_close=backlog_at_close, delivered_by_close=by_close,
            p50_ms=p50)

    def check(self) -> Dict[str, Tuple[float, float]]:
        """Every number compared with its limit (all exact: limit 0)."""
        path, sent = self.client.last
        ckpt_differs = self.client.download(path) != sent
        snap = self.ex.carry_state()
        del self.ex                     # free the program's state
        shutil.rmtree(self._root, ignore_errors=True)
        got = self.reference.compare(snap["key"], snap["value"],
                                     np.concatenate(self.submitted),
                                     self.dep["vocab"])
        checks = {k: (float(v), 0.0) for k, v in got.items()}
        checks["requests_not_delivered_once"] = (
            float(np.count_nonzero(self.deliveries != 1)), 0.0)
        checks["records_dropped"] = (float(self.batch_dropped), 0.0)
        checks["batches_failed"] = (float(self.batch_failures), 0.0)
        checks["compiles_in_window"] = (float(self.compiles_in_window), 0.0)
        checks["checkpoint_differs"] = (float(ckpt_differs), 0.0)
        return checks
