"""One-wire-tensor shuffle hops (WireFrame framing + chunked exchange).

Covers the ISSUE-5 acceptance surface:

- host-side WireFrame row/tile codec round-trips across dtypes;
- jaxpr-inspection: exactly 1 ``all_to_all`` per flat shuffle hop, 2 per
  hierarchical hop (shuffle and combine each), × chunks;
- delivery bit-identical to the retired multi-collective (4-tensor) path
  across dtypes, skew, and drop cases;
- the chunked (W=4) exchange delivers the same multiset as W=1 and
  conserves the drop accounting;
- ShufflePlan wire/frame geometry (chunks, recv_slots, wan_profile frame
  accounting).

SPMD tests run in subprocesses on 8 virtual CPU devices (see test_spmd.py).
"""

import sys

import numpy as np
import pytest

from test_spmd import SRC, run_spmd

from repro.core.records import WireFrame


# -- host-side WireFrame codec -------------------------------------------------


@pytest.mark.parametrize("dtype,shape", [
    ("int32", (3,)), ("float32", (4,)), ("uint8", (5,)), ("int16", ()),
    ("bool", (2,)),
])
def test_frame_rows_roundtrip(dtype, shape):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    n = 17
    if dtype == "bool":
        payload = rng.random((n,) + shape) > 0.5
    elif dtype == "float32":
        payload = rng.random((n,) + shape).astype(dtype)
    else:
        info = np.iinfo(dtype)
        payload = rng.integers(info.min, int(info.max) + 1,
                               size=(n,) + shape).astype(dtype)
    bucket = rng.integers(0, 1 << 20, n).astype(np.int32)
    src = np.arange(n, dtype=np.int32)
    frame = WireFrame.for_payload(jnp.asarray(payload),
                                  meta=("bucket", "src"))
    rows = frame.frame_rows(jnp.asarray(payload), bucket=bucket, src=src)
    assert rows.shape == (n, frame.row_nbytes)
    pay, valid, metas = frame.open_rows(rows)
    assert valid is None
    np.testing.assert_array_equal(np.asarray(pay), payload)
    np.testing.assert_array_equal(np.asarray(metas["bucket"]), bucket)
    np.testing.assert_array_equal(np.asarray(metas["src"]), src)


def test_frame_explicit_valid_zeroes_invalid_rows():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    payload = rng.random((8, 3)).astype(np.float32)
    valid = np.array([1, 0, 1, 1, 0, 1, 0, 1], bool)
    src = np.arange(8, dtype=np.int32)
    frame = WireFrame.for_payload(jnp.asarray(payload), meta=("src",),
                                  explicit_valid=True)
    rows = np.asarray(frame.frame_rows(jnp.asarray(payload),
                                       valid=jnp.asarray(valid), src=src))
    assert (rows[~valid] == 0).all(), "invalid rows must not leak bytes"
    pay, v, metas = frame.open_rows(jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(v), valid)
    np.testing.assert_array_equal(np.asarray(pay)[valid], payload[valid])
    np.testing.assert_array_equal(np.asarray(metas["src"])[valid], src[valid])


def test_frame_seal_open_counts():
    """seal/open carry per-tile counts through the wire: valid is the
    prefix mask, clamped against corrupt counts."""
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    d, cap = 4, 6
    frame = WireFrame("int32", (2,))
    tiles = jnp.asarray(rng.integers(0, 255, (d, cap, frame.row_nbytes))
                        .astype(np.uint8))
    counts = jnp.asarray([0, 3, 6, 99], jnp.int32)   # 99 -> clamped to cap
    wire = frame.seal(tiles, counts)
    assert wire.shape == (d, cap + 1, frame.row_nbytes)
    _, valid, _ = frame.open(wire)
    np.testing.assert_array_equal(np.asarray(valid).sum(axis=1),
                                  [0, 3, 6, cap])


def test_frame_geometry_and_validation():
    # rows pad to the count header width in positional mode
    assert WireFrame("uint8", ()).row_nbytes == 4
    assert WireFrame("uint8", (), explicit_valid=True).row_nbytes == 2
    f = WireFrame("int32", (2,), meta=("bucket", "src"))
    assert f.row_nbytes == 8 + 8
    assert f.tile_nbytes(10) == 11 * 16        # + count header row
    fe = WireFrame("int32", (2,), meta=("src",), explicit_valid=True)
    assert fe.row_nbytes == 1 + 4 + 8
    assert fe.tile_nbytes(10) == 10 * 13       # no header row
    import jax.numpy as jnp
    with pytest.raises(ValueError):
        f.frame_rows(jnp.zeros((3, 2), jnp.int32))          # missing meta
    with pytest.raises(ValueError):
        fe.frame_rows(jnp.zeros((3, 2), jnp.int32),
                      src=jnp.zeros(3, jnp.int32))          # missing valid
    with pytest.raises(ValueError):
        f.open_rows(jnp.zeros((3, 5), jnp.uint8))           # wrong width
    with pytest.raises(ValueError):
        fe.seal(jnp.zeros((2, 4, 13), jnp.uint8), jnp.zeros(2, jnp.int32))


def test_plan_chunk_geometry():
    sys.path.insert(0, SRC)
    from repro.core.shuffle import ShufflePlan

    p = ShufflePlan(num_buckets=16, axes=("data",), shape=(8,),
                    capacities=(10,), chunks=4)
    assert p.stage_slots(0) == 4 * 3           # ceil(10/4)=3 per chunk
    assert p.recv_slots == 8 * 12
    h = ShufflePlan(num_buckets=16, axes=("dc", "node"), shape=(2, 4),
                    capacities=(24, 40), chunks=1)
    assert h.recv_slots == 2 * 40
    with pytest.raises(ValueError):
        ShufflePlan(num_buckets=16, axes=("data",), shape=(8,),
                    capacities=(10,), chunks=0)


def test_wan_profile_frame_accounting():
    sys.path.insert(0, SRC)
    from repro.core.shuffle import ShufflePlan

    flat = ShufflePlan(num_buckets=8, axes=("w",), shape=(8,),
                       capacities=(100,))
    p = flat.wan_profile(2, 4, rec_bytes=8)
    # legacy = data + valid + bucket + src; fused(min) = payload + count row
    assert p["wan_legacy_bytes"] == p["wan_tiles"] * 100 * 17
    pm = flat.wan_profile(2, 4, rec_bytes=8, wire_meta="min")
    assert pm["wan_frame_bytes"] == p["wan_tiles"] * 101 * 8
    assert p["wan_legacy_bytes"] / pm["wan_frame_bytes"] > 2.0
    # chunked rounds: W tiles of ceil(cap/W)+1 rows each
    flat4 = ShufflePlan(num_buckets=8, axes=("w",), shape=(8,),
                        capacities=(100,), chunks=4)
    p4 = flat4.wan_profile(2, 4, rec_bytes=8, wire_meta="min")
    assert p4["wan_rounds"] == 4
    assert p4["wan_frame_bytes"] == p["wan_tiles"] * 4 * 26 * 8
    # hierarchical full meta carries bucket+src+pos and the legacy path
    # shipped 5 tensors
    hier = ShufflePlan(num_buckets=8, axes=("d", "n"), shape=(2, 4),
                       capacities=(50, 100))
    ph = hier.wan_profile(2, 4, rec_bytes=8)
    assert ph["wan_legacy_bytes"] == ph["wan_tiles"] * 100 * 21
    assert ph["wan_frame_bytes"] == ph["wan_tiles"] * 101 * 20
    with pytest.raises(ValueError):
        hier.wan_profile(2, 4, rec_bytes=8, wire_meta="bogus")


# -- SPMD (subprocess) ---------------------------------------------------------


PRELUDE = """
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core.shuffle import ShufflePlan
from repro.kernels import ops as kops
mesh1 = jax.make_mesh((8,), ("data",))
mesh2 = jax.make_mesh((2, 4), ("dc", "node"))
rng = np.random.default_rng(0)


def legacy_sphere_shuffle(data, bucket_ids, num_buckets, capacity, axis_name):
    \"\"\"The retired multi-collective path: four separate all_to_all
    (data/valid/bucket/src), kept verbatim as the equivalence oracle.\"\"\"
    axis_size = 8
    bpd = num_buckets // axis_size
    a2a = lambda x: jax.lax.all_to_all(x, axis_name, split_axis=0,
                                       concat_axis=0, tiled=True)
    ids = bucket_ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < num_buckets)
    dest = jnp.where(ok, ids // bpd, axis_size)
    (send_data, send_ids), in_range, origin, dropped_local = \\
        kops.partition_pack([data, ids], dest, axis_size, capacity)
    send_bucket = jnp.where(in_range, send_ids, -1)
    send_src = jnp.where(in_range, origin, -1)
    return (a2a(send_data), a2a(in_range), a2a(send_bucket), a2a(send_src),
            jax.lax.psum(dropped_local, axis_name))


def run_flat(plan, data, buckets):
    spec = P("data")
    dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh1, spec))
    bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh1, spec))
    def udf(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return r.data, r.valid, r.bucket, r.src_pos, r.dropped
    with mesh1:
        out = shard_map(udf, mesh=mesh1, in_specs=(spec, spec),
                        out_specs=(spec,) * 4 + (P(),),
                        check_vma=False)(dd, bd)
    return [np.asarray(o) for o in out]


def run_legacy(num_buckets, capacity, data, buckets):
    spec = P("data")
    dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh1, spec))
    bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh1, spec))
    def udf(d, b):
        return legacy_sphere_shuffle(d, b.reshape(-1), num_buckets,
                                     capacity, "data")
    with mesh1:
        out = shard_map(udf, mesh=mesh1, in_specs=(spec, spec),
                        out_specs=(spec,) * 4 + (P(),),
                        check_vma=False)(dd, bd)
    return [np.asarray(o) for o in out]
"""


def test_collective_counts_per_hop():
    """Acceptance: exactly 1 all_to_all per flat hop, 2 per hierarchical
    hop, for shuffle and combine each — and chunks=W multiplies the shuffle
    counts by W."""
    run_spmd(PRELUDE + """
from repro.core.introspect import collective_counts
N = 8 * 512
n_local = N // 8
d0 = jnp.zeros((N, 3), jnp.int32)
b0 = jnp.zeros((N,), jnp.int32)
flat = ShufflePlan.for_mesh(mesh1, 16, n_local, 2.5, ("data",))
hier = ShufflePlan.for_mesh(mesh2, 16, n_local, 2.5, ("dc", "node"))

def shuffle_only(plan):
    def f(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return r.data, r.valid, r.dropped
    return f

def shuffle_combine(plan):
    def f(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return plan.combine(r.data.astype(jnp.float32) * 2.0, r, n_local)
    return f

def a2a_count(fn, mesh, spec, outs):
    f = shard_map(fn, mesh=mesh, in_specs=(spec, spec), out_specs=outs,
                  check_vma=False)
    return collective_counts(f, d0, b0)["all_to_all"]

s1, s2 = P("data"), P(("dc", "node"))
assert a2a_count(shuffle_only(flat), mesh1, s1, (s1, s1, P())) == 1
assert a2a_count(shuffle_only(hier), mesh2, s2, (s2, s2, P())) == 2
for w in (2, 4):
    fw = dataclasses.replace(flat, chunks=w)
    hw = dataclasses.replace(hier, chunks=w)
    assert a2a_count(shuffle_only(fw), mesh1, s1, (s1, s1, P())) == w
    assert a2a_count(shuffle_only(hw), mesh2, s2, (s2, s2, P())) == 2 * w
# shuffle + combine: flat 1+1, hier 2+2
assert a2a_count(shuffle_combine(flat), mesh1, s1, (s1, s1)) == 2
assert a2a_count(shuffle_combine(hier), mesh2, s2, (s2, s2)) == 4
print("collective counts ok")
""")


def test_fused_matches_legacy_multicollective_path():
    """Acceptance: the one-tensor hop is bit-identical to the retired
    4-collective path — same valid mask, same data/bucket/src on every
    valid slot, same drop count — across dtypes, skew, and drop pressure."""
    run_spmd(PRELUDE + """
N = 8 * 512
cases = []
# uniform int32 records, no pressure
b = rng.integers(0, 16, size=N).astype(np.int32)
cases.append(("uniform_i32",
              rng.integers(0, 1000, (N, 3)).astype(np.int32), b, 256))
# float32 payload rides the same byte frame
cases.append(("uniform_f32",
              rng.standard_normal((N, 4)).astype(np.float32), b, 256))
# invalid ids (emit-nothing) sprinkled in
b2 = b.copy(); b2[rng.random(N) < 0.1] = -1
cases.append(("padding", rng.integers(0, 1000, (N, 3)).astype(np.int32),
              b2, 256))
# heavy skew under capacity pressure -> drops, earliest-kept
b3 = np.where(rng.random(N) < 0.7, 3, b).astype(np.int32)
cases.append(("skew_drops", rng.integers(0, 1000, (N, 3)).astype(np.int32),
              b3, 64))
for name, data, buckets, cap in cases:
    plan = ShufflePlan(num_buckets=16, axes=("data",), shape=(8,),
                       capacities=(cap,))
    nd, nv, nb_, ns, ndrop = run_flat(plan, data, buckets)
    ld, lv, lb, ls, ldrop = run_legacy(16, cap, data, buckets)
    assert int(ndrop) == int(ldrop), (name, int(ndrop), int(ldrop))
    np.testing.assert_array_equal(nv, lv.reshape(nv.shape), err_msg=name)
    m = nv  # compare only real slots (empty slots hold zeros vs garbage)
    np.testing.assert_array_equal(nd[m], ld.reshape(nd.shape)[m],
                                  err_msg=name)
    np.testing.assert_array_equal(nb_[m], lb.reshape(nb_.shape)[m],
                                  err_msg=name)
    np.testing.assert_array_equal(ns[m], ls.reshape(ns.shape)[m],
                                  err_msg=name)
    print(name, "ok, dropped", int(ndrop))
""")


def test_chunked_exchange_matches_unchunked():
    """W=4 delivers the identical multiset as W=1 (no pressure), conserves
    records under drop pressure, and the hierarchical chunked path still
    equals the flat delivery multiset."""
    run_spmd(PRELUDE + """
N = 8 * 512
data = rng.integers(0, 1 << 20, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
base = ShufflePlan.for_mesh(mesh1, 16, N // 8, 2.5, ("data",))

def multiset(d, v, b):
    d2 = d.reshape(-1, 3); v2 = v.reshape(-1); b2 = b.reshape(-1)
    return sorted(map(tuple, np.concatenate([b2[v2][:, None], d2[v2]], 1)))

d1, v1, b1, _, drop1 = run_flat(base, data, buckets)
ref = multiset(d1, v1, b1)
assert int(drop1) == 0 and len(ref) == N
for w in (2, 4):
    dw, vw, bw, _, dropw = run_flat(dataclasses.replace(base, chunks=w),
                                    data, buckets)
    assert int(dropw) == 0
    assert multiset(dw, vw, bw) == ref, w

# hierarchical chunked == flat delivery
hier = dataclasses.replace(
    ShufflePlan.for_mesh(mesh2, 16, N // 8, 2.5, ("dc", "node")), chunks=2)
spec = P(("dc", "node"))
dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh2, spec))
bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh2, spec))
def udf(d, b):
    r = hier.shuffle(d, b.reshape(-1))
    return r.data, r.valid, r.bucket, r.dropped
with mesh2:
    hd, hv, hb, hdrop = shard_map(udf, mesh=mesh2, in_specs=(spec, spec),
                                  out_specs=(spec,) * 3 + (P(),),
                                  check_vma=False)(dd, bd)
hd, hv, hb = map(np.asarray, (hd, hv, hb))
assert int(hdrop) == 0
assert multiset(hd, hv, hb) == ref

# drop conservation under chunked capacity pressure
buckets3 = np.where(rng.random(N) < 0.7, 3, buckets).astype(np.int32)
tight = ShufflePlan(num_buckets=16, axes=("data",), shape=(8,),
                    capacities=(64,), chunks=4)
dt, vt, bt, _, dropt = run_flat(tight, data, buckets3)
assert int(dropt) > 0
assert int(vt.sum()) + int(dropt) == N
print("chunked ok")
""")


def test_chunked_combine_roundtrip_and_moe():
    """Combine still inverts a chunked shuffle, and the chunked MoE
    dispatch matches the dense reference."""
    run_spmd(PRELUDE + """
N = 8 * 256
n_local = N // 8
data = rng.standard_normal((N, 4)).astype(np.float32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
plan = dataclasses.replace(
    ShufflePlan.for_mesh(mesh2, 16, n_local, 2.5, ("dc", "node")), chunks=2)
spec = P(("dc", "node"))
dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh2, spec))
bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh2, spec))
def udf(d, b):
    r = plan.shuffle(d, b.reshape(-1))
    combined, hits = plan.combine(r.data * 3.0, r, n_local)
    return combined, hits, r.dropped
with mesh2:
    comb, hits, drop = shard_map(udf, mesh=mesh2, in_specs=(spec, spec),
                                 out_specs=(spec, spec, P()),
                                 check_vma=False)(dd, bd)
assert int(drop) == 0
assert (np.asarray(hits) == 1).all()
np.testing.assert_allclose(np.asarray(comb), data * 3.0, rtol=1e-6)

import dataclasses as dc
from repro.configs import get_smoke_config
from repro.models import moe as moe_mod
cfg = get_smoke_config("qwen3_moe_30b_a3b")
cfg = dc.replace(cfg, capacity_factor=8.0)
params, _ = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, tp=8)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.bfloat16)
with mesh2:
    xs = jax.device_put(x, NamedSharding(mesh2, P("dc", "node", None)))
    out_h, aux_h = moe_mod.moe_apply_sphere(params, xs, cfg, mesh2, (),
                                            ep_axes=("dc", "node"), chunks=2)
out_d, aux_d = moe_mod.moe_apply_dense(params, x, cfg)
err = float(jnp.max(jnp.abs(out_h.astype(jnp.float32)
                            - out_d.astype(jnp.float32))))
assert int(aux_h["moe_dropped"]) == 0, aux_h
assert err < 0.3, err
print("chunked combine + moe ok, err", err)
""")


def test_wire_meta_min_ships_no_metadata():
    """wire_meta='min' (the dataflow executor's setting) returns bucket and
    src_pos as None and still delivers the full record multiset."""
    run_spmd(PRELUDE + """
N = 8 * 512
data = rng.integers(0, 1000, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
plan = ShufflePlan.for_mesh(mesh1, 16, N // 8, 2.5, ("data",))
spec = P("data")
dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh1, spec))
bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh1, spec))
def udf(d, b):
    r = plan.shuffle(d, b.reshape(-1), wire_meta="min")
    assert r.bucket is None and r.src_pos is None
    return r.data, r.valid, r.dropped
with mesh1:
    rd, rv, drop = shard_map(udf, mesh=mesh1, in_specs=(spec, spec),
                             out_specs=(spec, spec, P()),
                             check_vma=False)(dd, bd)
rd, rv = np.asarray(rd).reshape(-1, 3), np.asarray(rv).reshape(-1)
assert int(drop) == 0
assert sorted(map(tuple, rd[rv])) == sorted(map(tuple, data))
print("wire_meta=min ok")
""")
