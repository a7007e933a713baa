"""Terasort: the paper's Table 1 sort as a closed loop of back-to-back calls.

The deployment (``configs/terasort_100b.json``) is ``Dataflow.source(codec)
.sort(key)`` over 100-byte records on ``SPMDExecutor(use_pallas=True)``,
with the stage-2 segment sort chosen by the program's autotuner. Set-up
lets the autotuner measure the stage-2 cell on the chip, makes the records
on the chips from the seed (sharded over the cell's mesh) and runs one
call, which compiles. The window then calls the program back to back on those records,
each call ending in ``block_until_ready``. Two calls' outputs are kept for
the check: one drawn from the seed among the first three, and the last.
"""

from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import faults, spec, traceread, window
from repro.core.records import RecordCodec
from repro.core.shuffle import ShufflePlan
from repro.kernels import autotune
from repro.sphere.dataflow import Dataflow, SPMDExecutor

#: calls the traced part of a ``--trace 1`` window holds.
TRACED_CALLS = 2


def seed32(seed: int) -> int:
    """A 31-bit seed for ``jax.random.key`` from any whole number."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               & 0x7FFFFFFF)


class Cell:
    def __init__(self, cell: Dict[str, Any], seed: int,
                 fault: Optional[str] = None,
                 log: Callable[..., None] = lambda **k: None):
        dep = cell["deployment"]
        self.log = log
        self.seed = seed
        self.fault = fault
        self.reference = spec.reference(dep["reference"])
        self.payload_bytes = dep["payload_bytes"]
        self.codec = RecordCodec.from_fields(
            {"key": np.int32, "payload": (np.uint8, (self.payload_bytes,))})
        axes = tuple(cell["axes"])
        self.mesh = jax.make_mesh(tuple(cell["mesh"]), axes)
        ndev = self.mesh.size
        n_local = dep["records_per_chip"]
        self.n = n_local * ndev
        self.spec = NamedSharding(self.mesh,
                                  P(axes if len(axes) > 1 else axes[0]))
        # buckets per chip such that a stage-2 segment (capacity-factor
        # headroom over the uniform share of the receive buffer) holds at
        # most `segment` slots
        cf = dep["capacity_factor"]
        recv = ShufflePlan.for_mesh(self.mesh, ndev, n_local, cf,
                                    axes).recv_slots
        bpd = -(-int(recv * cf) // (dep["segment"] - 1))
        self.df = Dataflow.source(self.codec).sort(
            key=lambda r: r["key"], num_buckets=bpd * ndev,
            capacity_factor=cf)
        self.ex = SPMDExecutor(self.mesh, axes=axes, use_pallas=True)
        stage = self.ex.sort_cell(self.df.stages[-1], n_local)
        # the shapes the roofline readers compute bytes from: the rank
        # kernel runs on the send (n_local ids over the chips) and on the
        # stage-2 regroup (recv ids over the chip's buckets)
        self.geometry = {
            "rank_calls": [[n_local, ndev],
                           [stage.recv, stage.buckets_per_device]],
            "stage2": [stage.buckets_per_device, stage.segment],
        }
        # resolve the stage-2 kernel with the program's own autotuner before
        # the first call, outside any trace: called while the program is
        # traced, its candidates are staged instead of run, and it would
        # pick from staging times (PERF.md, Open questions)
        choice = autotune.choose(stage.buckets_per_device, stage.segment,
                                 jnp.int32, kv=True)
        self.records = self._make_records(seed32(seed))
        self.valid = jax.device_put(np.ones(self.n, bool), self.spec)
        jax.block_until_ready((self.records, self.valid))
        self.call = faults.terasort_call(fault, self)
        first = self.call()                   # compiles
        jax.block_until_ready(first.records)
        del first
        log(records=self.n, records_per_chip=n_local, mesh=cell["mesh"],
            num_buckets=bpd * ndev, stage2_cell=self.geometry["stage2"],
            stage2_pick=choice.algo, stage2_melem=dict(choice.melem),
            stage2_source=choice.source)

    def _make_records(self, key_seed: int):
        """Uniform int32 keys over [0, 2^31 - 1) (the range the default
        splitters cut evenly) and a payload whose first 4 bytes are the
        record number (gensort's row id), made on the chips, sharded."""
        n, width = self.n, self.payload_bytes

        def make():
            kk, kp = jax.random.split(jax.random.key(key_seed))
            keys = jax.random.randint(kk, (n,), 0, 2**31 - 1, jnp.int32)
            rowid = jax.lax.bitcast_convert_type(
                jnp.arange(n, dtype=jnp.uint32), jnp.uint8)
            fill = jax.random.bits(kp, (n, width - 4), jnp.uint8)
            return {"key": keys,
                    "payload": jnp.concatenate([rowid, fill], axis=1)}

        return jax.jit(make, out_shardings=self.spec)()

    def _one_call(self):
        with jax.profiler.TraceAnnotation("bench.terasort.call"):
            res = self.call()
            jax.block_until_ready((res.records, res.valid, res.dropped))
        return res

    def window(self, seconds: float, trace: bool):
        keep_at = int(np.random.default_rng(abs(int(self.seed))).integers(3))
        self.dropped = []
        self.kept = []
        traced = None
        start = time.perf_counter()
        ends = []
        while True:
            elapsed = time.perf_counter() - start
            if trace and traced is None and elapsed >= seconds / 2 - 2:
                traced, res = self._traced()
                ends.extend(traced.ends)
                self.dropped.extend(r.dropped for r in traced.results)
            else:
                res = self._one_call()
                ends.append(time.perf_counter())
                self.dropped.append(res.dropped)
            if len(ends) - 1 >= keep_at and not self.kept:
                self.kept.append(res)
            if ends[-1] - start >= seconds:
                break
        self.kept.append(res)
        rate = window.closed_loop_rate(start, ends, self.n)
        self.log(calls=len(ends), window_s=ends[-1] - start,
                 call_s=np.diff([start] + ends).tolist(),
                 sort_records_per_s=rate)
        return SimpleNamespace(
            start=start, end_to_end={"sort_records_per_s": rate},
            attempted=len(ends), failed=0, host={},
            trace=traced.trace if traced else None,
            traced_calls=TRACED_CALLS)

    def _traced(self):
        profile = traceread.Profile()
        results, ends = [], []
        for _ in range(TRACED_CALLS):
            results.append(self._one_call())
            ends.append(time.perf_counter())
        return SimpleNamespace(trace=profile.stop(), ends=ends,
                               results=results), results[-1]

    def check(self) -> Dict[str, Tuple[float, float]]:
        """Every number compared with its limit (all exact: limit 0)."""
        dropped = int(sum(int(d) for d in self.dropped))
        outs = [(np.asarray(r.valid), np.asarray(r.records["key"]),
                 np.asarray(r.records["payload"])) for r in self.kept]
        keys_in = np.asarray(self.records["key"])
        payload_in = np.asarray(self.records["payload"])
        # free the program's state before the reference runs
        del self.kept, self.records, self.valid, self.ex, self.call
        worst: Dict[str, int] = {}
        for valid, keys, payload in outs:
            got = self.reference.compare(keys_in, payload_in,
                                         keys[valid], payload[valid])
            for k, v in got.items():
                worst[k] = max(worst.get(k, 0), v)
        checks = {k: (float(v), 0.0) for k, v in worst.items()}
        checks["dropped"] = (float(dropped), 0.0)
        return checks
