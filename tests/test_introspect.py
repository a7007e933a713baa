"""Edge cases for the jaxpr collective counter (core/introspect.py).

The one-wire-tensor acceptance contract is structural — N ``all_to_all``
per hop — so the counter itself needs coverage: it must recurse through
nested pjit / closed-call sub-jaxprs, scale with the chunk factor W, and
must NOT let unrelated collectives (a ``psum`` inside the UDF) inflate the
``all_to_all`` count. All cases trace on the 1-device mesh: the collectives
still appear in the jaxpr, so no virtual-device subprocess is needed.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core.introspect import (COLLECTIVE_PRIMITIVES, collective_counts,
                                   primitive_counts)
from repro.core.shuffle import ShufflePlan

NB = 8


def _mesh():
    return jax.make_mesh((1,), ("data",))


def _plan(mesh, chunks=None):
    plan = ShufflePlan.for_mesh(mesh, NB, 512, 2.5, ("data",))
    return dataclasses.replace(plan, chunks=chunks) if chunks else plan


def _shuffle_fn(plan, extra=None):
    def f(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        data = r.data
        if extra is not None:
            data = extra(data)
        return data, r.valid, r.dropped
    return f


def _wrap(mesh, fn):
    return shard_map(fn, mesh=mesh, in_specs=(P("data"), P("data")),
                     out_specs=(P("data"), P("data"), P()), check_vma=False)


def _args():
    return (jnp.zeros((512, 3), jnp.int32), jnp.zeros((512,), jnp.int32))


def test_chunked_hop_scales_all_to_all_by_w():
    """chunks=W splits the one wire tensor into W chunked exchanges — the
    counter must see exactly W all_to_all, W in {1, 2, 4}."""
    mesh = _mesh()
    d, b = _args()
    for w in (1, 2, 4):
        f = _wrap(mesh, _shuffle_fn(_plan(mesh, chunks=w)))
        counts = collective_counts(f, d, b)
        assert counts["all_to_all"] == w, (w, counts)


def test_counts_recurse_through_nested_pjit():
    """A shuffle buried two jit levels down (pjit sub-jaxpr inside a pjit
    sub-jaxpr) is still counted — the walk recurses through every
    ClosedJaxpr found in equation params."""
    mesh = _mesh()
    d, b = _args()
    inner = jax.jit(_wrap(mesh, _shuffle_fn(_plan(mesh, chunks=2))))

    @jax.jit
    def outer(d, b):
        data, valid, dropped = inner(d, b)
        return data + 1, valid, dropped

    counts = collective_counts(outer, d, b)
    assert counts["all_to_all"] == 2, counts
    # the same program traced without the jit wrappers agrees
    flat = collective_counts(_wrap(mesh, _shuffle_fn(_plan(mesh, chunks=2))),
                             d, b)
    assert flat["all_to_all"] == counts["all_to_all"]


def test_counts_recurse_through_closed_call():
    """jax.checkpoint wraps its body in a closed-call-style sub-jaxpr; the
    collectives inside must still be found."""
    mesh = _mesh()
    d, b = _args()
    body = jax.checkpoint(_wrap(mesh, _shuffle_fn(_plan(mesh))))
    counts = collective_counts(body, d, b)
    assert counts["all_to_all"] == 1, counts


def test_udf_psum_does_not_inflate_all_to_all():
    """Regression: a psum inside the UDF (a legitimate user collective)
    must show up under "psum" and leave the all_to_all hop count alone."""
    mesh = _mesh()
    d, b = _args()

    def with_psum(data):
        s = jax.lax.psum(data.sum(), "data")
        return data + s.astype(data.dtype)

    plain = collective_counts(_wrap(mesh, _shuffle_fn(_plan(mesh))), d, b)
    noisy = collective_counts(
        _wrap(mesh, _shuffle_fn(_plan(mesh), extra=with_psum)), d, b)
    assert plain["all_to_all"] == noisy["all_to_all"] == 1
    # the hop itself psums the drop count; the UDF adds exactly one more,
    # and none of it leaks into the all_to_all tally
    assert noisy["psum"] == plain["psum"] + 1
    # every reported key is a known collective, zero-filled when absent
    assert set(noisy) == set(COLLECTIVE_PRIMITIVES)


def test_primitive_counts_plain_function():
    """primitive_counts on a collective-free function: no collectives, and
    ordinary primitives are tallied."""
    counts = primitive_counts(lambda x: jnp.sin(x) + jnp.cos(x),
                              jnp.ones((4,)))
    assert counts.get("sin") == 1 and counts.get("cos") == 1
    assert all(counts.get(c, 0) == 0 for c in COLLECTIVE_PRIMITIVES)
