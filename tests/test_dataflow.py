"""Unified dataflow API tests: RecordCodec round-trips, cross-executor
equivalence (the same pipeline on SPMD and Sector/SPE), and the satellite
regressions (empty-bucket dtype, reduce truncation reporting, non-int32
map_reduce)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from test_spmd import run_spmd

from repro.core.records import RecordCodec


# -- RecordCodec ---------------------------------------------------------------


DTYPES = ["int32", "uint8", "int16", "float32", "bool", "int8", "uint32"]


def _example(rng, dtype, n, shape):
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return rng.random((n,) + shape) > 0.5
    if dt.kind == "f":
        return rng.random((n,) + shape).astype(dt)
    info = np.iinfo(dt)
    return rng.integers(info.min, int(info.max) + 1,
                        size=(n,) + shape).astype(dt)


@pytest.mark.parametrize("dtype", DTYPES)
def test_codec_roundtrip_single_field(dtype):
    rng = np.random.default_rng(0)
    rec = {"x": _example(rng, dtype, 9, (2,))}
    codec = RecordCodec.from_example(rec)
    packed = codec.pack(rec)
    encoded = codec.encode(rec)
    # jax and numpy paths must be byte-identical (host<->SPMD interop)
    np.testing.assert_array_equal(np.asarray(packed), encoded)
    for out in (codec.unpack(packed), codec.decode(encoded)):
        got = out["x"]
        assert np.asarray(got).dtype == rec["x"].dtype
        np.testing.assert_array_equal(np.asarray(got), rec["x"])


def test_codec_mixed_pytree_and_layout():
    rng = np.random.default_rng(1)
    rec = {"word": np.arange(5, dtype=np.uint8),
           "vec": rng.random((5, 3)).astype(np.float32),
           "ok": np.array([1, 0, 1, 1, 0], bool)}
    # insertion order = byte layout, even though dicts flatten sorted
    codec = RecordCodec.from_fields(
        {"word": np.uint8, "vec": (np.float32, (3,)), "ok": np.bool_})
    assert codec.nbytes == 1 + 12 + 1
    enc = codec.encode(rec)
    assert enc.shape == (5, 14)
    assert (enc[:, 0] == rec["word"]).all()          # word is byte 0
    np.testing.assert_array_equal(np.asarray(codec.pack(rec)), enc)
    dec = codec.decode(enc.tobytes())
    for k in rec:
        np.testing.assert_array_equal(dec[k], rec[k])
    # multi-leading-dim unpack (shuffle receive layout)
    import jax.numpy as jnp
    u = codec.unpack(jnp.asarray(enc).reshape(1, 5, 14))
    assert np.asarray(u["vec"]).shape == (1, 5, 3)


def test_codec_float64_numpy_lossless():
    rng = np.random.default_rng(2)
    codec = RecordCodec.from_fields({"key": np.int64, "value": np.float64})
    rec = {"key": rng.integers(0, 1 << 40, 6),
           "value": rng.random(6)}
    out = codec.decode(codec.encode(rec))
    assert out["value"].dtype == np.float64
    np.testing.assert_array_equal(out["value"], rec["value"])  # bit-exact
    np.testing.assert_array_equal(out["key"], rec["key"])


def test_codec_zero_records():
    """Empty segments/buckets are legal: pack/encode of n=0 must produce
    (0, nbytes) rows, and the round-trip must hold."""
    import jax.numpy as jnp
    codec = RecordCodec.from_fields({"k": np.int32, "v": (np.float32, (2,))})
    rec = {"k": np.zeros(0, np.int32), "v": np.zeros((0, 2), np.float32)}
    enc = codec.encode(rec)
    assert enc.shape == (0, codec.nbytes)
    packed = codec.pack(rec)
    assert packed.shape == (0, codec.nbytes)
    out = codec.decode(enc)
    assert out["v"].shape == (0, 2)
    out = codec.unpack(jnp.asarray(enc))
    assert np.asarray(out["k"]).shape == (0,)


def test_codec_64bit_requires_x64_on_jax_path():
    """With jax_enable_x64 off (the default), the jax pack/unpack of a
    64-bit codec must fail loudly instead of silently truncating; the numpy
    path stays fully functional."""
    import jax
    codec = RecordCodec.from_fields({"v": np.float64})
    rec = {"v": np.random.default_rng(0).random(4)}
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled in this environment")
    with pytest.raises(RuntimeError, match="x64"):
        codec.pack(rec)
    with pytest.raises(RuntimeError, match="x64"):
        codec.unpack(np.zeros((4, codec.nbytes), np.uint8))
    out = codec.decode(codec.encode(rec))           # numpy path unaffected
    np.testing.assert_array_equal(out["v"], rec["v"])


def test_codec_rejects_schema_mismatch():
    codec = RecordCodec.from_fields({"a": np.int32})
    with pytest.raises(ValueError):
        codec.pack({"a": np.zeros(3, np.float32)})
    with pytest.raises(ValueError):
        codec.unpack(np.zeros((3, codec.nbytes + 1), np.uint8))


@settings(max_examples=25, deadline=None)
@given(
    dtypes=st.lists(st.sampled_from(DTYPES), min_size=1, max_size=4),
    n=st.integers(min_value=0, max_value=17),
    trailing=st.integers(min_value=0, max_value=3),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_codec_roundtrip_property(dtypes, n, trailing, seed):
    """pack/unpack and encode/decode are exact inverses over mixed dtypes,
    and the two packings agree byte-for-byte."""
    rng = np.random.default_rng(seed)
    shape = (trailing,) if trailing else ()
    rec = {f"f{i}": _example(rng, dt, n, shape)
           for i, dt in enumerate(dtypes)}
    codec = RecordCodec.from_example(rec)
    packed, encoded = codec.pack(rec), codec.encode(rec)
    np.testing.assert_array_equal(np.asarray(packed), encoded)
    unpacked, decoded = codec.unpack(packed), codec.decode(encoded)
    for k in rec:
        np.testing.assert_array_equal(np.asarray(unpacked[k]), rec[k])
        np.testing.assert_array_equal(decoded[k], rec[k])
        assert decoded[k].dtype == rec[k].dtype


# -- reduce_by_key_sum truncation accounting -----------------------------------


def test_reduce_by_key_sum_reports_drops():
    from repro.core.mapreduce import reduce_by_key_sum
    keys = np.array([5, 1, 5, 2, 3, 4, 1, 9], np.int32)
    values = np.ones_like(keys)
    valid = np.ones(len(keys), bool)
    out_k, out_v, dropped = reduce_by_key_sum(keys, values, valid,
                                              max_unique=3)
    # 6 distinct keys, room for 3 -> 3 dropped, and it is REPORTED
    assert int(dropped) == 3
    kept = np.asarray(out_k)
    assert (kept >= 0).sum() == 3
    # no truncation -> zero drops, sums correct
    out_k, out_v, dropped = reduce_by_key_sum(keys, values, valid)
    assert int(dropped) == 0
    got = {int(k): int(v) for k, v in zip(out_k, out_v) if k >= 0}
    assert got == {1: 2, 2: 1, 3: 1, 4: 1, 5: 2, 9: 1}


# -- SphereProcess bucket regression -------------------------------------------


def test_engine_empty_bucket_keeps_dtype_and_shape(tmp_path):
    from repro.launch.train import make_sector
    from repro.sphere.engine import SphereProcess
    from repro.sphere.spe import SPE

    master, client, daemon = make_sector(str(tmp_path), num_slaves=3)
    rec = np.arange(24, dtype=np.uint8).reshape(12, 2)
    client.upload_dataset("/data/x", [rec.tobytes()])
    daemon.run_until_stable()
    spes = [SPE(i, master.slaves[i].address, master, client.session_id)
            for i in range(3)]
    proc = SphereProcess(master, client.session_id, spes)
    # bucket_fn routes EVERYTHING to bucket 0 and mentions no other bucket,
    # so buckets 1..3 stay empty
    res = proc.run(["/data/x.00000"], lambda r: r.reshape(-1, 2),
                   record_bytes=2, bucket_fn=lambda out: {0: out},
                   num_buckets=4)
    assert res.outputs[0].shape == (12, 2)
    for b in (1, 2, 3):
        empty = res.outputs[b]
        assert empty.shape == (0, 2), "empty bucket lost trailing dims"
        assert empty.dtype == np.uint8, "empty bucket lost dtype"


# -- cross-executor equivalence (SPMD vs Sector/SPE) ---------------------------


def test_cross_executor_inverted_index_equivalence():
    """The acceptance check: ONE Dataflow object, two executors, identical
    key -> count multiset (and both equal the ground-truth Counter)."""
    run_spmd("""
import collections, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.core.records import RecordCodec
from repro.launch.train import make_sector
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE

NB = 8
codec = RecordCodec.from_fields({"word": np.uint8, "page": np.uint8})
def emit(rec):
    return {"key": rec["word"].astype(jnp.int32),
            "value": jnp.ones_like(rec["word"], jnp.int32)}
def count(rec, valid):
    k, v, dropped = reduce_by_key_sum(rec["key"], rec["value"], valid)
    return {"key": k, "value": v}, k >= 0, dropped
df = (Dataflow.source(codec)
      .map(emit)
      .shuffle(by=lambda r: default_hash(r["key"], NB), num_buckets=NB)
      .reduce(count))

rng = np.random.default_rng(7)
pages = []
for i in range(4):
    p = rng.integers(0, 26, size=(40, 2), dtype=np.uint8)
    p[:, 1] = i
    pages.append(p)
allpages = np.concatenate(pages)
want = dict(collections.Counter(allpages[:, 0].tolist()))

def counts(res):
    rec = res.valid_records()
    return {int(k): int(v) for k, v in zip(rec["key"], rec["value"])}

# host executor: one SPE crashes mid-run; retry must absorb it
root = tempfile.mkdtemp()
master, client, daemon = make_sector(root, num_slaves=4)
client.upload_dataset("/web/page", [p.tobytes() for p in pages])
daemon.run_until_stable()
spes = [SPE(i, master.slaves[i].address, master, client.session_id,
            fail_after=0 if i == 0 else None) for i in range(4)]
host_res = HostExecutor(master, client, spes).run(
    df, [f"/web/page.{i:05d}" for i in range(4)])
assert not host_res.errors, host_res.errors
assert host_res.retries >= 1   # the crash was absorbed, not ignored

# SPMD executor: same pipeline object
mesh = jax.make_mesh((8,), ("data",))
spmd = SPMDExecutor(mesh)
with mesh:
    spmd_res = spmd.run(df, {"word": jnp.asarray(allpages[:, 0]),
                             "page": jnp.asarray(allpages[:, 1])})
assert int(spmd_res.dropped) == 0

hc, sc = counts(host_res), counts(spmd_res)
assert hc == want, (hc, want)
assert sc == want, (sc, want)
print("cross-executor multiset equal:", len(hc), "keys")
""")


def test_cross_executor_sort_equivalence():
    """Dataflow.sort: SPMD terasort and host bucket-file sort produce the
    same globally sorted key sequence."""
    run_spmd("""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core.records import RecordCodec
from repro.launch.train import make_sector
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE

N = 8 * 256
rng = np.random.default_rng(3)
keys = rng.integers(0, 2**31 - 2, size=N).astype(np.int32)
payload = np.arange(N, dtype=np.int32)
codec = RecordCodec.from_fields({"key": np.int32, "payload": np.int32})
df = Dataflow.source(codec).sort(key=lambda r: r["key"], num_buckets=8)

mesh = jax.make_mesh((8,), ("data",))
with mesh:
    sres = SPMDExecutor(mesh, use_pallas=True, sort_algo="bitonic").run(
        df, {"key": jnp.asarray(keys), "payload": jnp.asarray(payload)})
svr = sres.valid_records()
assert int(sres.dropped) == 0
assert (np.diff(svr["key"]) >= 0).all()
assert (keys[svr["payload"]] == svr["key"]).all()

root = tempfile.mkdtemp()
master, client, daemon = make_sector(root, num_slaves=4)
slices = np.split(codec.encode({"key": keys, "payload": payload}), 4)
client.upload_dataset("/ts/in", [s.tobytes() for s in slices])
daemon.run_until_stable()
spes = [SPE(i, master.slaves[i].address, master, client.session_id)
        for i in range(4)]
hres = HostExecutor(master, client, spes).run(
    df, [f"/ts/in.{i:05d}" for i in range(4)])
hvr = hres.valid_records()
assert (np.diff(hvr["key"]) >= 0).all()
np.testing.assert_array_equal(hvr["key"], svr["key"])
print("sort equivalence ok")
""")


def test_map_reduce_float64_values_lossless():
    """Acceptance: a non-int32 (float64-value) map_reduce round-trips
    losslessly through the codec-backed shuffle (the old entry point cast
    everything to int32)."""
    run_spmd("""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core.mapreduce import map_reduce, reduce_by_key_sum

mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
N = 8 * 128
weights = rng.random(N)                      # float64 record stream
data = jnp.asarray(weights)
assert data.dtype == jnp.float64
# the map UDF derives an int32 key from each float64 value and emits the
# value untouched; the shuffle must carry it at full precision
with mesh:
    k, v, valid, dropped = map_reduce(
        lambda seg: ((seg * 40).astype(jnp.int32), seg),
        reduce_by_key_sum, data, mesh)
k, v, valid = np.asarray(k), np.asarray(v), np.asarray(valid)
assert v.dtype == np.float64, v.dtype
assert int(dropped) == 0
got = {int(a): b for a, b, ok in zip(k, v, valid) if ok and a >= 0}
want = {}
for x in weights:
    want.setdefault(int(x * 40), []).append(x)
assert set(got) == set(want)
for key in want:
    assert abs(got[key] - sum(sorted(want[key]))) < 1e-9, key
print("float64 map_reduce lossless:", len(got), "keys")
""")


def test_cross_executor_equivalence_chunked_and_hierarchical():
    """Satellite acceptance: the inverted-index and sort pipelines produce
    identical results on HostExecutor vs SPMDExecutor with chunks ∈ {1, 4}
    and flat vs hierarchical plans (all on the fused one-wire-tensor
    framing)."""
    run_spmd("""
import collections, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core.mapreduce import default_hash, reduce_by_key_sum
from repro.core.records import RecordCodec
from repro.launch.train import make_sector
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE

NB = 8
N = 8 * 128
rng = np.random.default_rng(11)

# -- inverted index ----------------------------------------------------------
codec = RecordCodec.from_fields({"word": np.uint8, "page": np.uint8})
def emit(rec):
    return {"key": rec["word"].astype(jnp.int32),
            "value": jnp.ones_like(rec["word"], jnp.int32)}
def count(rec, valid):
    k, v, dropped = reduce_by_key_sum(rec["key"], rec["value"], valid)
    return {"key": k, "value": v}, k >= 0, dropped
ii = (Dataflow.source(codec)
      .map(emit)
      .shuffle(by=lambda r: default_hash(r["key"], NB), num_buckets=NB)
      .reduce(count))
pages = rng.integers(0, 26, size=(N, 2), dtype=np.uint8)
want = dict(collections.Counter(pages[:, 0].tolist()))

def counts(res):
    rec = res.valid_records()
    return {int(k): int(v) for k, v in zip(rec["key"], rec["value"])}

root = tempfile.mkdtemp()
master, client, daemon = make_sector(root, num_slaves=4)
client.upload_dataset("/ii/page", [p.tobytes() for p in np.split(pages, 4)])
daemon.run_until_stable()
spes = [SPE(i, master.slaves[i].address, master, client.session_id)
        for i in range(4)]
host = counts(HostExecutor(master, client, spes).run(
    ii, [f"/ii/page.{i:05d}" for i in range(4)]))
assert host == want

mesh1 = jax.make_mesh((8,), ("data",))
mesh2 = jax.make_mesh((2, 4), ("dc", "node"))
src = {"word": jnp.asarray(pages[:, 0]), "page": jnp.asarray(pages[:, 1])}
for mesh, axes in ((mesh1, ("data",)), (mesh2, ("dc", "node"))):
    for w in (1, 4):
        ex = SPMDExecutor(mesh, axes=axes, chunks=w)
        with mesh:
            res = ex.run(ii, src)
        assert int(res.dropped) == 0, (axes, w)
        assert counts(res) == want, (axes, w)

# -- sort --------------------------------------------------------------------
keys = rng.integers(0, 2**31 - 2, size=N).astype(np.int32)
payload = np.arange(N, dtype=np.int32)
scodec = RecordCodec.from_fields({"key": np.int32, "payload": np.int32})
# capacity_factor covers per-CHUNK skew at W=4 (capacity splits W ways, so
# each chunk's bins see W x the relative variance)
sdf = Dataflow.source(scodec).sort(key=lambda r: r["key"], num_buckets=8,
                                   capacity_factor=3.0)

slices = np.split(scodec.encode({"key": keys, "payload": payload}), 4)
client.upload_dataset("/ts/in", [s.tobytes() for s in slices])
daemon.run_until_stable()
hres = HostExecutor(master, client, spes).run(
    sdf, [f"/ts/in.{i:05d}" for i in range(4)])
hkeys = hres.valid_records()["key"]
assert (np.diff(hkeys) >= 0).all()

for mesh, axes in ((mesh1, ("data",)), (mesh2, ("dc", "node"))):
    for w in (1, 4):
        ex = SPMDExecutor(mesh, axes=axes, chunks=w)
        with mesh:
            sres = ex.run(sdf, {"key": jnp.asarray(keys),
                                "payload": jnp.asarray(payload)})
        svr = sres.valid_records()
        assert int(sres.dropped) == 0, (axes, w)
        np.testing.assert_array_equal(svr["key"], hkeys, err_msg=str((axes, w)))
        assert (keys[svr["payload"]] == svr["key"]).all(), (axes, w)
print("cross-executor chunked/hier equivalence ok")
""")


def test_spmd_executor_cache_eviction():
    """Satellite: the compile cache is a bounded LRU — it cannot grow past
    cache_size, evicts least-recently-used first, and an evicted pipeline
    retraces on its next run."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.sphere.dataflow import Dataflow, SPMDExecutor

mesh = jax.make_mesh((8,), ("data",))
data = {"key": jnp.arange(8 * 32, dtype=jnp.int32)}
trace_count = [0]

def make_df():
    def bump(rec):
        trace_count[0] += 1
        return rec
    return Dataflow.source().map(bump).shuffle(by=lambda r: r["key"] % 8,
                                               num_buckets=8)

ex = SPMDExecutor(mesh, cache_size=2)
df1, df2, df3 = make_df(), make_df(), make_df()
with mesh:
    ex.run(df1, data)
    ex.run(df2, data)
    assert len(ex._cache) == 2
    ex.run(df1, data)          # refresh df1 -> df2 becomes LRU
    n = trace_count[0]
    ex.run(df3, data)          # evicts df2
    assert len(ex._cache) == 2
    cached = [e[0] for e in ex._cache.values()]
    assert df1 in cached and df3 in cached and df2 not in cached
    ex.run(df1, data)          # still cached: no retrace
    assert trace_count[0] == n + 1   # only df3's trace happened
    ex.run(df2, data)          # evicted: must retrace
    assert trace_count[0] == n + 2
    assert len(ex._cache) == 2
# the cache_info counters must tell the same story as the trace counts:
# 4 lowers (df1, df2, df3, df2-again), 2 hits (both df1 reruns), and the
# two evictions (df2 then df3) are counted, not silent
info = ex.cache_info()
assert info.misses == 4, info
assert info.hits == 2, info
assert info.evictions == 2, info
assert info.currsize == 2 and info.maxsize == 2, info
print("lru eviction ok")
""")


def test_sort_key_max_sentinel_guard():
    """Satellite: a real key equal to the key dtype's maximum collides with
    the stage-2 padding sentinel. Under the unstable bitonic kernel the
    executor's debug guard raises; under any *stable* sort (the default
    autotuned path resolves to one here, and sort_algo='radix'/'oracle'
    pin one) padding stays behind real keys, so the record is delivered
    correctly and the guard never fires — the regression this test pins."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.sphere.dataflow import Dataflow, SPMDExecutor

mesh = jax.make_mesh((8,), ("data",))
N = 8 * 64
rng = np.random.default_rng(5)
keys = rng.integers(0, np.iinfo(np.int32).max, size=N).astype(np.int32)
keys[7] = np.iinfo(np.int32).max          # collides with the sort sentinel
payload = np.arange(N, dtype=np.int32)
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=8)
src = {"key": jnp.asarray(keys), "payload": jnp.asarray(payload)}

# unstable bitonic: the guard must still catch the collision
strict = SPMDExecutor(mesh, sort_algo="bitonic")
try:
    with mesh:
        strict.run(df, src)
    raise AssertionError("sentinel collision was not detected")
except ValueError as e:
    assert "bitonic" in str(e) and "sentinel" in str(e), e
print("guard raised ok")

# clean keys pass the guard (no false positive)
keys2 = keys.copy(); keys2[7] = 0
with mesh:
    strict.run(df, {"key": jnp.asarray(keys2),
                    "payload": jnp.asarray(payload)})

# stable sorts deliver the max-value key instead of raising: the record
# is present in the output with its payload, in its sorted position
for algo in (None, "radix", "oracle"):     # None -> autotuned (stable here)
    ex = SPMDExecutor(mesh, sort_algo=algo)
    with mesh:
        res = ex.run(df, src)
    out_k = np.asarray(res.records["key"])[np.asarray(res.valid)]
    out_p = np.asarray(res.records["payload"])[np.asarray(res.valid)]
    assert out_k.size == N and int(res.dropped) == 0, algo
    assert out_k[-1] == np.iinfo(np.int32).max, (algo, out_k[-8:])
    assert out_p[out_k == np.iinfo(np.int32).max][0] == 7, algo
print("stable delivery ok")

# opting out restores the old silent behaviour for bitonic too
loose = SPMDExecutor(mesh, sort_algo="bitonic", debug_checks=False)
with mesh:
    loose.run(df, src)    # no raise
print("sentinel guard ok")
""")


def test_spmd_executor_compile_cache():
    """Re-running the same pipeline object on same-shaped data must hit the
    executor's compile cache (one entry, one trace)."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.sphere.dataflow import Dataflow, SPMDExecutor

trace_count = [0]
def emit(rec):
    trace_count[0] += 1
    return {"key": rec["key"] % 16, "value": rec["value"]}
df = (Dataflow.source().map(emit)
      .shuffle(by=lambda r: r["key"] % 8, num_buckets=8))
mesh = jax.make_mesh((8,), ("data",))
ex = SPMDExecutor(mesh)
data = {"key": jnp.arange(8 * 32, dtype=jnp.int32),
        "value": jnp.ones(8 * 32, jnp.float32)}
with mesh:
    r1 = ex.run(df, data)
    n_after_first = trace_count[0]
    r2 = ex.run(df, data)
assert len(ex._cache) == 1
assert trace_count[0] == n_after_first, "second run retraced"
vr1, vr2 = r1.valid_records(), r2.valid_records()
np.testing.assert_array_equal(vr1["value"], vr2["value"])
# float32 values survived the byte shuffle
assert vr1["value"].dtype == np.float32
print("cache ok")
""")
