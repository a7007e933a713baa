"""Hierarchical (two-level, wide-area) shuffle: equivalence with the flat
path, drop accounting under capacity pressure, and the threaded consumers
(terasort over a (dc, node) mesh, wide-area MoE expert parallelism).

SPMD tests run in subprocesses on 8 virtual CPU devices (see test_spmd.py
for why); plan-geometry and WAN-model tests run host-side.
"""

import os
import sys

import pytest

from test_spmd import SRC, run_spmd

ROOT = os.path.join(os.path.dirname(__file__), "..")


PRELUDE = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core.shuffle import ShufflePlan, sphere_shuffle
mesh1 = jax.make_mesh((8,), ("data",))
mesh2 = jax.make_mesh((2, 4), ("dc", "node"))
rng = np.random.default_rng(0)

def run_plan(mesh, spec, plan, data, buckets):
    dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh, spec))
    bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh, spec))
    def udf(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return (r.data.reshape(-1, 3), r.valid.reshape(-1),
                r.bucket.reshape(-1), r.dropped)
    with mesh:
        out = shard_map(udf, mesh=mesh, in_specs=(spec, spec),
                        out_specs=(spec, spec, spec, P()),
                        check_vma=False)(dd, bd)
    return [np.asarray(o) for o in out]
"""


def test_hier_delivery_multiset_equals_flat():
    """Acceptance property: a (dc=2, node=4) hierarchical shuffle delivers
    exactly the same multiset of (bucket, record) pairs as the flat 8-device
    shuffle, each record landing on its bucket's owner device."""
    run_spmd(PRELUDE + """
N = 8 * 512
data = rng.integers(0, 1000, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
flat_plan = ShufflePlan.for_mesh(mesh1, 16, N // 8, 2.5, ("data",))
hier_plan = ShufflePlan.for_mesh(mesh2, 16, N // 8, 2.5, ("dc", "node"))
fd, fv, fb, fdrop = run_plan(mesh1, P("data"), flat_plan, data, buckets)
hd, hv, hb, hdrop = run_plan(mesh2, P(("dc", "node")), hier_plan, data, buckets)
assert int(fdrop) == 0 and int(hdrop) == 0
flat_set = sorted(map(tuple, np.concatenate([fb[fv][:, None], fd[fv]], 1)))
hier_set = sorted(map(tuple, np.concatenate([hb[hv][:, None], hd[hv]], 1)))
assert len(flat_set) == N
assert flat_set == hier_set
# ownership: global device d = (dc, node) row-major owns buckets 2d, 2d+1
per = hb.reshape(8, -1); pv = hv.reshape(8, -1)
for d in range(8):
    bs = per[d][pv[d]]
    assert ((bs // 2) == d).all()
""")


def test_hier_drop_accounting_under_capacity_pressure():
    """Every record is either delivered once or counted dropped exactly once
    — across both stages, including records invalidated by out-of-range
    bucket ids (sent nowhere, dropped nowhere)."""
    run_spmd(PRELUDE + """
N = 8 * 512
data = rng.integers(0, 1000, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
buckets[rng.random(N) < 0.1] = -1                      # padding records
n_valid = int((buckets >= 0).sum())

for caps in [(2048, 40), (24, 2048), (24, 40)]:        # squeeze B, A, both
    plan = ShufflePlan(num_buckets=16, axes=("dc", "node"), shape=(2, 4),
                       capacities=caps)
    hd, hv, hb, hdrop = run_plan(mesh2, P(("dc", "node")), plan, data, buckets)
    delivered = int(hv.sum())
    assert int(hdrop) > 0, caps                        # pressure was real
    assert delivered + int(hdrop) == n_valid, (caps, delivered, int(hdrop))
    # delivered records still live on their owner device
    per = hb.reshape(8, -1); pv = hv.reshape(8, -1)
    for d in range(8):
        assert ((per[d][pv[d]] // 2) == d).all()

# flat baseline obeys the same conservation law
flat = ShufflePlan(num_buckets=16, axes=("data",), shape=(8,),
                   capacities=(40,))
fd, fv, fb, fdrop = run_plan(mesh1, P("data"), flat, data, buckets)
assert int(fv.sum()) + int(fdrop) == n_valid
""")


def test_hier_combine_roundtrip():
    """plan.combine inverts the two-level route: every processed record
    returns to its origin row exactly once."""
    run_spmd(PRELUDE + """
N = 8 * 256
n_local = N // 8
data = rng.standard_normal((N, 4)).astype(np.float32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
plan = ShufflePlan.for_mesh(mesh2, 16, n_local, 2.5, ("dc", "node"))
dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh2, P(("dc", "node"))))
bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh2, P(("dc", "node"))))
def udf(d, b):
    r = plan.shuffle(d, b.reshape(-1))
    combined, hits = plan.combine(r.data * 3.0, r, n_local)
    return combined, hits, r.dropped
with mesh2:
    comb, hits, drop = shard_map(
        udf, mesh=mesh2, in_specs=(P(("dc", "node")), P(("dc", "node"))),
        out_specs=(P(("dc", "node")), P(("dc", "node")), P()),
        check_vma=False)(dd, bd)
assert int(drop) == 0
assert (np.asarray(hits) == 1).all()
np.testing.assert_allclose(np.asarray(comb), data * 3.0, rtol=1e-6)

# under stage-B capacity pressure the flat-path contract must hold: a
# dropped record comes back with hits == 0 (not a silent zero with hits 1)
tight = ShufflePlan(num_buckets=16, axes=("dc", "node"), shape=(2, 4),
                    capacities=(2048, 40))
def udf2(d, b):
    r = tight.shuffle(d, b.reshape(-1))
    combined, hits = tight.combine(r.data * 3.0, r, n_local)
    return combined, hits, r.dropped
with mesh2:
    comb2, hits2, drop2 = shard_map(
        udf2, mesh=mesh2, in_specs=(P(("dc", "node")), P(("dc", "node"))),
        out_specs=(P(("dc", "node")), P(("dc", "node")), P()),
        check_vma=False)(dd, bd)
comb2, hits2 = np.asarray(comb2), np.asarray(hits2)
assert int(drop2) > 0
assert int(hits2.sum()) + int(drop2) == N
np.testing.assert_allclose(comb2[hits2 == 1], data[hits2 == 1] * 3.0,
                           rtol=1e-6)
assert (comb2[hits2 == 0] == 0).all()
""")


def test_hier_terasort_globally_sorted():
    run_spmd(PRELUDE + """
from repro.core.sort import terasort, is_globally_sorted
N = 8 * 2048
keys = rng.integers(0, 2**31 - 2, size=N).astype(np.int32)
payload = np.arange(N, dtype=np.int32)
kd = jax.device_put(jnp.asarray(keys), NamedSharding(mesh2, P(("dc", "node"))))
pd = jax.device_put(jnp.asarray(payload), NamedSharding(mesh2, P(("dc", "node"))))
with mesh2:
    res = terasort(kd, pd, mesh2, axis=("dc", "node"), use_pallas=True,
                   sort_algo="bitonic")
assert int(res.dropped) == 0
assert is_globally_sorted(res, 8)
vk = np.asarray(res.keys)[np.asarray(res.valid)]
vp = np.asarray(res.payload)[np.asarray(res.valid)]
assert len(vk) == N
assert (keys[vp] == vk).all()
assert (np.sort(vk) == np.sort(keys)).all()
""")


def test_hier_moe_matches_dense_dispatch():
    run_spmd(PRELUDE + """
import dataclasses
from repro.configs import get_smoke_config
from repro.models import moe as moe_mod
cfg = get_smoke_config("qwen3_moe_30b_a3b")
cfg = dataclasses.replace(cfg, capacity_factor=8.0)    # no drops -> exact
params, _ = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, tp=8)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.bfloat16)
with mesh2:
    xs = jax.device_put(x, NamedSharding(mesh2, P("dc", "node", None)))
    out_h, aux_h = moe_mod.moe_apply_sphere(params, xs, cfg, mesh2, (),
                                            ep_axes=("dc", "node"))
out_d, aux_d = moe_mod.moe_apply_dense(params, x, cfg)
err = float(jnp.max(jnp.abs(out_h.astype(jnp.float32)
                            - out_d.astype(jnp.float32))))
assert int(aux_h["moe_dropped"]) == 0, aux_h
assert err < 0.3, err
print("wide-area moe sphere-vs-dense max err:", err)
""")


# -- host-side (no subprocess) ------------------------------------------------


def test_plan_geometry_and_validation():
    sys.path.insert(0, SRC)
    from repro.core.shuffle import ShufflePlan
    from repro.sector.topology import Topology

    p = ShufflePlan.from_topology(Topology(pods=4, racks=1, nodes_per_rack=30),
                                  num_buckets=120, n_local=1200)
    assert p.hierarchical and p.shape == (4, 30)
    assert p.num_devices == 120 and p.buckets_per_device == 1
    assert p.recv_slots == 4 * p.capacities[1]

    flat = ShufflePlan.from_topology(Topology(pods=1, racks=2,
                                              nodes_per_rack=4),
                                     num_buckets=16, n_local=64)
    assert not flat.hierarchical and flat.shape == (8,)

    with pytest.raises(ValueError):
        ShufflePlan(num_buckets=7, axes=("a",), shape=(4,), capacities=(1,))
    with pytest.raises(ValueError):
        ShufflePlan(num_buckets=8, axes=("a", "b"), shape=(2, 4),
                    capacities=(1,))
    with pytest.raises(ValueError):
        p.wan_profile(2, 4, rec_bytes=100)  # topology mismatch


def test_wan_model_hier_bytes_at_most_inverse_nodes_of_flat():
    """Acceptance criterion: on the paper's 4×30 testbed model, the
    hierarchical shuffle puts ≤ 1/nodes_per_dc of the flat shuffle's bytes
    on the WAN (wire accounting), and exactly 1/nodes of the flows."""
    sys.path.insert(0, SRC)
    sys.path.insert(0, ROOT)
    from benchmarks.wan_shuffle import model_wan_round

    m = model_wan_round(dcs=4, nodes=30)
    assert m["wire_ratio"] <= 1.0 / 30 + 1e-9
    assert m["flow_ratio"] == pytest.approx(1.0 / 30)
    # both paths move the identical useful payload; hierarchical never
    # ships more padded slots than flat
    assert m["slot_ratio"] <= 1.0
    assert m["hier"]["wan_slot_bytes"] >= m["useful_bytes"]
