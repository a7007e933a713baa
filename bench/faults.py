"""Faults planted under the timed path, and the controls.

A measured run plants nothing. The tests (``bench/tests``) and the control
runs (``bench/control.py``) plant one of these and expect the run's
``correct`` to come out false:

- ``control``: the reference put in the program's place, breaking one
  guarantee its deployment states: terasort sorts the keys and leaves each
  payload where it was (records not intact); the stream delivers one
  request twice (at-least-once, not exactly-once);
- ``unchanged``: a step that returns its state unchanged (terasort hands
  back its input, the stream keeps its carry);
- ``half``: half of the batch left out;
- ``no_exchange``: the exchange between chips left out (each chip sorts
  its own records);
- ``altered``: one answer altered where it is produced (a payload byte of
  the sorted output, a word of the stream's batch).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

TERASORT = ("control", "unchanged", "half", "no_exchange", "altered")
STREAM = ("control", "unchanged", "half", "altered")


def terasort_call(fault: Optional[str], cell: Any) -> Callable[[], Any]:
    """The call the terasort window makes, with ``fault`` planted."""
    ex, df = cell.ex, cell.df

    def program(valid=None):
        return ex.run(df, cell.records,
                      valid=cell.valid if valid is None else valid)

    if fault is None:
        return program
    if fault == "control":
        @jax.jit
        def keys_only(records):
            return {"key": jnp.sort(records["key"]),
                    "payload": records["payload"]}

        return lambda: SimpleNamespace(records=keys_only(cell.records),
                                       valid=cell.valid,
                                       dropped=jnp.zeros((), jnp.int32))
    if fault == "unchanged":
        def unchanged():
            res = program()
            return SimpleNamespace(records=cell.records, valid=cell.valid,
                                   dropped=res.dropped)
        return unchanged
    if fault == "half":
        half = jax.device_put(np.arange(cell.n) < cell.n // 2, cell.spec)
        return lambda: program(half)
    if fault == "altered":
        @jax.jit
        def alter(payload, valid):
            n, width = payload.shape
            hit = ((jnp.arange(n) == jnp.argmax(valid))[:, None]
                   & (jnp.arange(width) == width - 1)[None, :])
            return payload + hit.astype(payload.dtype)

        def altered():
            res = program()
            records = dict(res.records,
                           payload=alter(res.records["payload"], res.valid))
            return SimpleNamespace(records=records, valid=res.valid,
                                   dropped=res.dropped)
        return altered
    if fault == "no_exchange":
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        spec = cell.spec.spec

        def local_sort(keys, payload):
            order = jnp.argsort(keys, stable=True)
            return keys[order], payload[order]

        sort_shards = jax.jit(shard_map(local_sort, mesh=cell.mesh,
                                        in_specs=(spec, spec),
                                        out_specs=(spec, spec)))

        def no_exchange():
            k, p = sort_shards(cell.records["key"], cell.records["payload"])
            return SimpleNamespace(records={"key": k, "payload": p},
                                   valid=cell.valid,
                                   dropped=jnp.zeros((), jnp.int32))
        return no_exchange
    raise ValueError(f"unknown terasort fault {fault!r}")


def stream_inner(fault: Optional[str], run: Callable[..., Any],
                 vocab: int) -> Callable[..., Any]:
    """``SPMDExecutor.run`` as the stream's executor calls it, with
    ``fault`` planted (``control`` is planted by the stream driver's
    generator)."""
    if fault in (None, "control"):
        return run
    if fault == "unchanged":
        def unchanged(pipeline, batch, valid=None, carry=None, **kw):
            res = run(pipeline, batch, valid=valid, carry=carry, **kw)
            res.carry = carry
            return res
        return unchanged
    if fault == "half":
        def half(pipeline, batch, valid=None, carry=None, **kw):
            valid = valid.copy()
            valid[len(valid) // 2:] = False
            return run(pipeline, batch, valid=valid, carry=carry, **kw)
        return half
    if fault == "altered":
        def altered(pipeline, batch, valid=None, carry=None, **kw):
            words = batch["word"].copy()
            words[0] = (int(words[0]) + 1) % vocab
            return run(pipeline, dict(batch, word=words), valid=valid,
                       carry=carry, **kw)
        return altered
    raise ValueError(f"unknown stream fault {fault!r}")
