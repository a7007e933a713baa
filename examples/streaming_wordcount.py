"""Streaming wordcount: one compiled pipeline serving three tenants
(paper §3.2 — "Sphere takes streams as inputs and produces streams as
outputs" — run continuously instead of once).

The batch executors run a ``Dataflow`` pipeline one time over one dataset.
Here the SAME stage graph is declared with ``Dataflow.stream_source()`` and
handed to a :class:`~repro.sphere.streaming.StreamExecutor`:

- requests (small record batches) are admitted into a
  :class:`~repro.sphere.streaming.TenantQueue` with weighted fair share
  (free=1, pro=3, enterprise=4) and bounded per-tenant queues;
- every ``step()`` assembles one fixed-shape micro-batch from the fairest
  mix of queued requests and runs the compiled program once — zero
  recompiles after the first batch (watch ``cache_info()``);
- the word counts accumulate across batches in bounded carry state, so the
  final snapshot equals a one-shot batch run over everything submitted.

Run:  PYTHONPATH=src python examples/streaming_wordcount.py
"""

import _bootstrap

if __name__ == "__main__":      # importers (chip_smoke.py) bring their devices
    _bootstrap.setup(devices=8)

import collections

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.sphere.dataflow import Dataflow, SPMDExecutor
from repro.sphere.streaming import StreamExecutor, TenantQueue

NUM_BUCKETS = 8
VOCAB = 26
MICRO_BATCH = 8 * 32


def build_pipeline() -> Dataflow:
    def emit(rec):
        return {"key": rec["word"].astype(jnp.int32),
                "value": jnp.ones_like(rec["word"], jnp.int32)}

    def count(rec, valid):
        keys, sums, dropped = reduce_by_key_sum(rec["key"], rec["value"],
                                                valid)
        return {"key": keys, "value": sums}, keys >= 0, dropped

    return (Dataflow.stream_source()
            .map(emit)
            .shuffle(by=lambda r: default_hash(r["key"], NUM_BUCKETS),
                     num_buckets=NUM_BUCKETS)
            .reduce(count))


def main() -> None:
    df = build_pipeline()
    print(f"pipeline: {df.describe()}")

    queue = TenantQueue(quantum=32.0)
    for tenant, weight in (("free", 1.0), ("pro", 3.0), ("enterprise", 4.0)):
        queue.register(tenant, weight=weight)
    mesh = jax.make_mesh((8,), ("data",))
    ex = StreamExecutor(SPMDExecutor(mesh), df, micro_batch=MICRO_BATCH,
                        carry_capacity=VOCAB, queue=queue)

    rng = np.random.default_rng(0)
    submitted = []
    for _ in range(24):                 # a burst of requests from each tenant
        for tenant in ("free", "pro", "enterprise"):
            words = rng.integers(0, VOCAB, size=32).astype(np.uint8)
            submitted.append(words)
            ex.submit({"word": words}, tenant=tenant)

    while queue.pending():
        batch = ex.step()
        if batch is None:
            break
        snap = ex.carry_state()
        print(f"batch {batch.step}: {len(batch.delivered)} requests, "
              f"{int(np.asarray(snap['value']).sum())} words counted so far")

    snap = ex.carry_state()
    got = {int(k): int(v) for k, v in zip(snap["key"], snap["value"])}
    want = dict(collections.Counter(
        np.concatenate(submitted).astype(int).tolist()))
    assert got == want, "streamed counts diverged from ground truth"

    stats = ex.stats()
    print(f"cache: {stats['cache']['misses']} compile, "
          f"{stats['cache']['hits']} reuses")
    for tenant, t in stats["tenants"].items():
        print(f"  {tenant:<11} weight={t['weight']:.0f} "
              f"served={t['records_served']} records "
              f"p50_wait={t['latency_p50']:.3f}s")
    assert stats["cache"]["misses"] == 1, "stream recompiled mid-flight"
    print("final snapshot == one-shot ground truth (verified)")


if __name__ == "__main__":
    main()
