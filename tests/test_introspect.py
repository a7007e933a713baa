"""Edge cases for the jaxpr collective counter (core/introspect.py), and
the compiled program's op->layer map.

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
                                   layer_of, module_name, op_layers,
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


# -- the op->layer map of a compiled program ---------------------------------


@pytest.mark.parametrize("op_name,layer", [
    ("jit(sphere_spmd)/sphere.exchange/sphere.move/mul", "sphere.move"),
    ("jit(f)/sphere.move/jit(_partition_rank)/sphere.rank/pallas_call",
     "sphere.rank"),
    ("jit(f)/sphere.exchange/partition.rank/pallas_call", "sphere.rank"),
    ("jit(f)/sphere.exchange/partition.move/jit(_take)/gather",
     "sphere.move"),
    ("jit(sphere_spmd)/jit(searchsorted)/while", None),
    ("jit(f)/spherical.move/gather", None),
    ("jit(f)/sphere.unknown/gather", None),
    ("jit(moe)/partition.move/scatter", "sphere.move"),
    ("gather", None),
    ("", None),
])
def test_layer_of_is_the_innermost_layer_scope(op_name, layer):
    assert layer_of(op_name) == layer


_HLO_HEAD = """HloModule jit_sphere_spmd, is_scheduled=true

%fused (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %gather.3 = s32[8]{0} gather(%param_0), metadata={op_name="gather" stack_frame_id=7}
}

%wide.body (q: (s32[8])) -> (s32[8]) {
  %q = (s32[8]{0}) parameter(0)
  %get-tuple-element.8 = s32[8]{0} get-tuple-element(%q), index=0
  %dynamic-update-slice.9 = s32[8]{0} dynamic-update-slice(%get-tuple-element.8)
  ROOT %tuple.10 = (s32[8]{0}) tuple(%dynamic-update-slice.9)
}

%wide.body.1 (r: (s32[8])) -> (s32[8]) {
  %r = (s32[8]{0}) parameter(0)
  %get-tuple-element.20 = s32[8]{0} get-tuple-element(%r), index=0
  %copy.21 = s32[8]{0} copy(%get-tuple-element.20)
  ROOT %tuple.22 = (s32[8]{0}) tuple(%copy.21)
}

ENTRY %main (p: s32[8], p2: s32[8]) -> s32[8] {
"""
_HLO_ENTRY = [
    '  %p = s32[8]{0} parameter(0), metadata={op_name="records[\\\'key\\\']"}',
    '  %p2 = s32[8]{0} parameter(1)',
    '  %copy.1 = s32[8]{0} copy(%p)',
    '  %fusion.2 = s32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused, metadata={op_type="x" op_name="jit(f)/sphere.bucket/add" stack_frame_id=7}',
    '  %gather.4 = s32[8]{0} gather(%fusion.2), metadata={op_name="gather" stack_frame_id=7}',
    '  %gather.5 = s32[8]{0} gather(%fusion.2), metadata={op_name="gather" stack_frame_id=9}',
    '  %copy.6 = s32[8]{0} copy(%gather.4)',
    '  %while.7 = (s32[8]{0}) while(%copy.6), condition=%c, body=%b, metadata={op_name="jit(f)/sphere.udf/while"}',
    '  %tuple.11 = (s32[8]{0}) tuple(%while.7)',
    '  %while.12 = (s32[8]{0}) while(%tuple.11), condition=%c, body=%wide.body',
    '  %gather.13 = s32[8]{0} gather(%p2), metadata={op_name="jit(f)/sphere.exchange/partition.move/jit(_take)/gather"}',
    '  %tuple.14 = (s32[8]{0}) tuple(%gather.13)',
    '  %while.15 = (s32[8]{0}) while(%tuple.14), condition=%c, body=%wide.body.1',
    '  %get-tuple-element.16 = s32[8]{0} get-tuple-element(%while.15), index=0',
    '  %all-to-all.17 = s32[8]{0} all-to-all(%get-tuple-element.16), dimensions={0}, metadata={op_name="jit(f)/sphere.exchange/all_to_all"}',
    '  %copy.18 = s32[8]{0} copy(%all-to-all.17)',
    '  %bitcast.19 = s32[8]{0} bitcast(%copy.18), metadata={op_name="jit(f)/sphere.move/reshape"}',
    '  ROOT %tuple.23 = ((s32[8]{0})) tuple(%while.12)',
]
_HLO = _HLO_HEAD + "\n".join(_HLO_ENTRY) + "\n}\n"


def test_op_layers_reads_metadata_and_resolves_what_the_compiler_made():
    got = op_layers(_HLO)
    assert module_name(_HLO) == "jit_sphere_spmd"
    # named by its own op_name; a while is an instruction like any other
    assert got["fusion.2"] == "sphere.bucket"
    assert got["while.7"] == "sphere.udf"
    # partition/pack's own scope, inside the exchange, counts as a row move
    assert got["gather.13"] == "sphere.move"
    # a rewritten gather keeps its stack frame: the scoped fusion.2 of the
    # same frame names its layer (in a fused computation too)
    assert got["gather.4"] == got["gather.3"] == "sphere.bucket"
    # a frame no scoped instruction shares resolves through its operand
    assert got["gather.5"] == "sphere.bucket"
    # no metadata: the layer of what it reads and what reads it ...
    assert got["copy.1"] == "sphere.bucket"
    # ... and none where those disagree
    assert "copy.6" not in got
    # a loop the compiler wrote: the while takes the layer of what it
    # reads, and its body's instructions the layer of the while
    assert got["while.12"] == "sphere.udf"
    assert got["dynamic-update-slice.9"] == got["q"] == "sphere.udf"
    # what carries the all-to-all's data, a loop that lays out the wire
    # and its body included, is the exchange's, though it reads a row move
    for name in ("tuple.14", "while.15", "get-tuple-element.16", "copy.18",
                 "r", "get-tuple-element.20", "copy.21"):
        assert got[name] == "sphere.exchange", name
    assert got["bitcast.19"] == "sphere.move"


def test_op_layers_does_not_depend_on_the_order_of_the_text():
    body = _HLO_ENTRY[:-1]
    for order in (body[::-1], body[1::2] + body[::2]):
        text = _HLO_HEAD + "\n".join(order + _HLO_ENTRY[-1:]) + "\n}\n"
        assert op_layers(text) == op_layers(_HLO)


_COVERAGE = """
import collections, json, re
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.introspect import op_layers
from repro.core.shuffle import ShufflePlan
from repro.obs.layers import SCOPES
from repro.sphere.dataflow import Dataflow, SPMDExecutor

shape, axes, n = %(shape)r, %(axes)r, 1024
mesh = jax.make_mesh(shape, axes)
spec = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
plan = (ShufflePlan.for_mesh(mesh, 8, n, 1.5, axes, use_pallas=True)
        if len(axes) == 2 else None)
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=8,
                            capacity_factor=1.5)
ex = SPMDExecutor(mesh, axes=axes, plan=plan, use_pallas=True,
                  sort_algo="bitonic")
rec = {"key": jax.ShapeDtypeStruct((4 * n,), jnp.int32, sharding=spec),
       "payload": jax.ShapeDtypeStruct((4 * n, 96), jnp.uint8,
                                       sharding=spec)}
valid = jax.ShapeDtypeStruct((4 * n,), jnp.bool_, sharding=spec)
compiled = ex.program(df).lower(rec, valid).compile()
got = op_layers(compiled)
kinds = ("fusion", "custom-call", "sort", "gather", "scatter",
         "all-to-all", "while")
seen, missing = collections.Counter(), []
for line in compiled.as_text().splitlines():
    m = re.match(r"^\\s*(?:ROOT\\s+)?%%([\\w.\\-]+) = .*? ([a-z][\\w\\-]*)\\(", line)
    if m and m.group(2) in kinds:
        seen[m.group(2)] += 1
        if got.get(m.group(1)) not in SCOPES.values():
            missing.append(line[:160])
print(json.dumps({"seen": seen, "missing": missing,
                  "layers": sorted(set(got.values()))}))
"""


@pytest.mark.parametrize("shape,axes", [((4,), ("data",)),
                                        ((2, 2), ("dc", "node"))],
                         ids=["flat", "dc_node"])
def test_every_op_of_the_fused_terasort_has_a_layer(shape, axes):
    """On 4 virtual CPU devices, the fused terasort program's fusions,
    custom calls, sorts, gathers, scatters, all-to-alls and while loops
    each map to one of the six layer scopes."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _COVERAGE % {"shape": shape, "axes": axes}],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["missing"] == []
    for kind in ("fusion", "all-to-all", "while", "gather", "scatter"):
        assert out["seen"].get(kind, 0) > 0, (kind, out["seen"])
    assert out["layers"] == sorted(["sphere.bucket", "sphere.rank",
                                    "sphere.move", "sphere.exchange",
                                    "sphere.segsort"])
