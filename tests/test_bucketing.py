"""Range bucketing of a sort: the default even splitters bucket a key by
one division, given splitters by ``searchsorted``, and both executors use
the same default splitters."""

import numpy as np
import pytest

from test_spmd import run_spmd

from repro.core.sort import bucket_width, uniform_bucket, uniform_splitters

I32 = np.iinfo(np.int32)


@pytest.mark.parametrize("nb", [1, 2, 3, 8, 4610, 2**16 + 1])
def test_uniform_bucket_equals_search(nb):
    """The division equals ``searchsorted(side="right")`` over the default
    splitters for every int32 key: at each splitter and either side of it,
    at 0, -1 and both ends of int32, and on a seeded uniform sample."""
    spl = uniform_splitters(nb)
    assert spl.dtype == np.int32 and spl.shape == (nb - 1,)
    assert (np.diff(spl.astype(np.int64)) > 0).all()
    assert spl.size == 0 or (spl[0] > 0 and int(spl[-1]) < 2**31)
    edges = spl.astype(np.int64)
    keys = np.concatenate([
        edges, edges - 1, edges + 1,
        [0, -1, I32.min, I32.min + 1, I32.max, I32.max - 1],
        np.random.default_rng(nb).integers(I32.min, I32.max, 50_000,
                                           endpoint=True),
    ]).clip(I32.min, I32.max).astype(np.int32)
    want = np.searchsorted(spl, keys, side="right")
    got = np.asarray(uniform_bucket(keys, nb))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # every bucket is non-empty: the last takes the remainder of [0, 2^31)
    assert 0 <= 2**31 - (nb - 1) * bucket_width(nb) - bucket_width(nb) < nb


def test_sort_buckets_by_arithmetic_with_default_splitters():
    """A sort with the default splitters buckets by arithmetic, one with
    given splitters by search (read from the program that ran); both
    deliver a stable NumPy sort of the keys, nothing dropped. Keys sit at
    and beside every default splitter, and 4 buckets a device exercise the
    regroup's device offset."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.sort import uniform_splitters
from repro.obs import layers
from repro.sphere.dataflow import Dataflow, SPMDExecutor

NB = 32
N = 8 * 512
rng = np.random.default_rng(11)
keys = rng.integers(0, 2**31 - 1, size=N).astype(np.int32)
spl = uniform_splitters(NB)
edge = np.concatenate([spl - 1, spl, spl + 1, [0, 2**31 - 2]])
keys[:edge.size] = edge
payload = np.arange(N, dtype=np.int32)
src = {"key": jnp.asarray(keys), "payload": jnp.asarray(payload)}
order = np.argsort(keys, kind="stable")

mesh = jax.make_mesh((8,), ("data",))
ex = SPMDExecutor(mesh, sort_algo="radix")
given = (spl + rng.integers(-1000, 1000, NB - 1)).astype(np.int32)
for splitters, path in ((None, "arithmetic"), (spl, "search"),
                        (given, "search")):
    df = Dataflow.source().sort(key=lambda r: r["key"], splitters=splitters,
                                num_buckets=NB)
    with mesh:
        res = ex.run(df, src)
    assert layers.last_bucketing() == (path,), (path, layers.last_bucketing())
    assert int(res.dropped) == 0
    vr = res.valid_records()
    np.testing.assert_array_equal(vr["key"], keys[order])
    # the payload is the row id: each record once, in stable order
    np.testing.assert_array_equal(vr["payload"], order)
    print(path, "ok")
""")


def test_executors_share_default_bucket_boundaries():
    """HostExecutor and SPMDExecutor put every key in the same bucket under
    the default splitters. With one bucket a device, a reduce after the
    sort sees exactly one bucket on either executor; it tags each record
    with its bucket's smallest key. Keys sit at and beside every default
    splitter, where two definitions of the splitters would disagree (at
    10 buckets float32 and float64 ``linspace`` both miss the exact
    splitters)."""
    run_spmd("""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core.records import RecordCodec
from repro.core.sort import uniform_splitters
from repro.launch.train import make_sector
from repro.sphere.dataflow import Dataflow, HostExecutor, SPMDExecutor
from repro.sphere.spe import SPE

NB = 10
N = NB * 128
rng = np.random.default_rng(13)
keys = rng.integers(0, 2**31 - 1, size=N).astype(np.int32)
spl = uniform_splitters(NB)
edge = np.concatenate([spl - 1, spl, spl + 1])
keys[:edge.size] = edge
payload = np.arange(N, dtype=np.int32)
codec = RecordCodec.from_fields({"key": np.int32, "payload": np.int32})

def lowest(r, v):
    lo = jnp.min(jnp.where(v, r["key"], np.iinfo(np.int32).max))
    return ({"key": r["key"], "payload": r["payload"],
             "lo": jnp.full_like(r["key"], lo)}, v)

df = (Dataflow.source(codec).sort(key=lambda r: r["key"], num_buckets=NB)
      .reduce(lowest))

mesh = jax.make_mesh((NB,), ("data",))
with mesh:
    sres = SPMDExecutor(mesh, sort_algo="radix").run(
        df, {"key": jnp.asarray(keys), "payload": jnp.asarray(payload)})
assert int(sres.dropped) == 0
svr = sres.valid_records()

root = tempfile.mkdtemp()
master, client, daemon = make_sector(root, num_slaves=4)
slices = np.split(codec.encode({"key": keys, "payload": payload}), 4)
client.upload_dataset("/ts/in", [s.tobytes() for s in slices])
daemon.run_until_stable()
spes = [SPE(i, master.slaves[i].address, master, client.session_id)
        for i in range(4)]
hvr = HostExecutor(master, client, spes).run(
    df, [f"/ts/in.{i:05d}" for i in range(4)]).valid_records()

def bucket_lo(vr):
    lo = np.empty(N, np.int64)
    lo[np.asarray(vr["payload"])] = np.asarray(vr["lo"])
    return lo

s_lo, h_lo = bucket_lo(svr), bucket_lo(hvr)
want = np.concatenate([[0], spl])[np.searchsorted(spl, keys, side="right")]
for lo in (s_lo, h_lo):
    # a bucket's smallest key is at or above its lower splitter
    assert (lo >= want).all() and (lo <= keys).all()
np.testing.assert_array_equal(s_lo, h_lo)
print("bucket boundaries equal")
""", devices=10)
