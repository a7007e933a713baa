"""TeraSort with its real order: 100-byte records sorted by all 10 key bytes.

The deployment (``configs/terasort_100b_key10.json``) is
``Dataflow.source(codec).sort(key=lambda r: r["key"])`` with ``key`` a
``uint8[10]`` field and a ``uint8[90]`` value (gensort's layout), on
``SPMDExecutor(use_pallas=True)``: the program orders the records by the
key's three big-endian words, lexicographically. What is the same as the
int32 terasort — the window of back-to-back calls, the traced calls, the
two outputs kept and their check — is ``drivers/terasort.py``'s; this
driver makes the records and the pipeline, resolves the stage-2 pick for
the compound cell in set-up, and plants its own faults:

- ``control``: the reference in the program's place, breaking one
  guarantee: the keys sorted and each value left where it was;
- ``last_word_ignored``: the program sorting by the key's first 8 bytes
  only, as if the last word (bytes 8-9) were not compared;
- ``record_lost``: one record left out of every call.

``key_distribution`` ``uniform`` is gensort's (the cell); ``tie_heavy``
(tests and checks only) draws the leading 8 bytes from one value per 8
records, so the last word decides most neighbours' order.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import spec
from repro.core.records import RecordCodec
from repro.core.shuffle import ShufflePlan
# a program without compound byte keys has no key_words: the cell then
# fails here, at once
from repro.core.sort import key_words  # noqa: F401
from repro.kernels import autotune
from repro.sphere.dataflow import Dataflow, SPMDExecutor

terasort = spec.driver("terasort")

FAULTS = ("control", "last_word_ignored", "record_lost")


class Cell(terasort.Cell):
    def __init__(self, cell: Dict[str, Any], seed: int,
                 fault: Optional[str] = None,
                 log: Callable[..., None] = lambda **k: None):
        dep = cell["deployment"]
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown terasort_bytekey fault {fault!r}")
        self.log = log
        self.seed = seed
        self.fault = fault
        self.reference = spec.reference(dep["reference"])
        self.key_bytes = dep["key_bytes"]
        self.payload_bytes = dep["payload_bytes"]
        self.distribution = dep["key_distribution"]
        self.codec = RecordCodec.from_fields({
            "key": (np.uint8, (self.key_bytes,)),
            "payload": (np.uint8, (self.payload_bytes,))})
        axes = tuple(cell["axes"])
        self.mesh = jax.make_mesh(tuple(cell["mesh"]), axes)
        ndev = self.mesh.size
        n_local = dep["records_per_chip"]
        self.n = n_local * ndev
        self.spec = NamedSharding(self.mesh,
                                  P(axes if len(axes) > 1 else axes[0]))
        # buckets per chip as terasort's: a stage-2 segment holds at most
        # `segment` slots
        cf = dep["capacity_factor"]
        recv = ShufflePlan.for_mesh(self.mesh, ndev, n_local, cf,
                                    axes).recv_slots
        bpd = -(-int(recv * cf) // (dep["segment"] - 1))
        self.df = self._sort_by(lambda r: r["key"], bpd * ndev, cf)
        self.ex = SPMDExecutor(self.mesh, axes=axes, use_pallas=True)
        stage = self.ex.sort_cell(self.df.stages[-1], n_local)
        words = -(-self.key_bytes // 4)
        self.geometry = {
            "rank_calls": [[n_local, ndev],
                           [stage.recv, stage.buckets_per_device]],
            "stage2": [stage.buckets_per_device, stage.segment],
            "stage2_words": words,
        }
        # the stage-2 pick for the compound cell, measured outside any
        # trace (configs' assumed.stage2_pick)
        choice = autotune.choose(stage.buckets_per_device, stage.segment,
                                 jnp.int32, kv=True, words=words)
        self.records = self._make_records(terasort.seed32(seed))
        self.valid = jax.device_put(np.ones(self.n, bool), self.spec)
        jax.block_until_ready((self.records, self.valid))
        self.call = self._call(fault, bpd * ndev, cf)
        first = self.call()                   # compiles
        jax.block_until_ready(first.records)
        del first
        log(records=self.n, records_per_chip=n_local, mesh=cell["mesh"],
            num_buckets=bpd * ndev, stage2_cell=self.geometry["stage2"],
            stage2_words=words, stage2_pick=choice.algo,
            stage2_melem=dict(choice.melem), stage2_source=choice.source,
            key_distribution=self.distribution)

    def _sort_by(self, key: Callable, num_buckets: int, cf: float):
        return Dataflow.source(self.codec).sort(
            key=key, num_buckets=num_buckets, capacity_factor=cf)

    def _make_records(self, key_seed: int):
        """Keys of uniform random bytes (gensort's default), or tie-heavy,
        and a value whose first 4 bytes are the record number (gensort's
        row id), made on the chips, sharded."""
        n, kb, width = self.n, self.key_bytes, self.payload_bytes
        tie_heavy = self.distribution == "tie_heavy"
        if not tie_heavy and self.distribution != "uniform":
            raise ValueError(f"unknown key_distribution "
                             f"{self.distribution!r}")

        def make():
            kk, kl, ki, kp = jax.random.split(jax.random.key(key_seed), 4)
            keys = jax.random.bits(kk, (n, kb), jnp.uint8)
            if tie_heavy:
                lead = jax.random.bits(kl, (max(1, n // 8), 8), jnp.uint8)
                pick = jax.random.randint(ki, (n,), 0, lead.shape[0])
                keys = keys.at[:, :8].set(lead[pick])
            rowid = jax.lax.bitcast_convert_type(
                jnp.arange(n, dtype=jnp.uint32), jnp.uint8)
            fill = jax.random.bits(kp, (n, width - 4), jnp.uint8)
            return {"key": keys,
                    "payload": jnp.concatenate([rowid, fill], axis=1)}

        return jax.jit(make, out_shardings=self.spec)()

    def _call(self, fault: Optional[str], num_buckets: int, cf: float):
        """The call the window makes, with ``fault`` planted."""
        df = self.df
        valid = self.valid
        if fault == "last_word_ignored":
            df = self._sort_by(lambda r: r["key"][:, :8], num_buckets, cf)
        elif fault == "record_lost":
            valid = jax.device_put(np.arange(self.n) > 0, self.spec)
        elif fault == "control":
            @jax.jit
            def keys_only(records):
                k = records["key"]
                cols = jax.lax.sort(tuple(k[:, j] for j in range(k.shape[1])),
                                    num_keys=k.shape[1])
                return {"key": jnp.stack(cols, axis=1),
                        "payload": records["payload"]}

            return lambda: SimpleNamespace(
                records=keys_only(self.records), valid=self.valid,
                dropped=jnp.zeros((), jnp.int32))
        return lambda: self.ex.run(df, self.records, valid=valid)
