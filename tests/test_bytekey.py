"""Compound byte keys: Sphere's sort ordered by every byte of a ``(n, k)``
uint8 key (TeraSort's 10-byte order), on both executors and with every
stage-2 kernel, compared exactly with the plain numpy reference of the
benchmark's ``terasort_100b_key10`` deployment.

Keys are tie-heavy: the leading 8 bytes come from a handful of values, so
the last word (bytes 8-9) decides the order of most pairs.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_spmd import run_spmd

from repro.core.sort import is_byte_key, key_layout, key_words
from repro.kernels import autotune, ops

REFERENCE = (Path(__file__).resolve().parents[1] / "bench" / "references"
             / "terasort_100b_key10.py")
ALGOS = ("bitonic", "radix", "oracle")


def reference():
    spec = importlib.util.spec_from_file_location("terasort_key10_ref",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("k", [1, 3, 4, 8, 10, 13])
def test_key_words_compare_as_memcmp(k):
    """The words of a key, compared as unsigned integers word by word,
    order keys as their bytes do; numpy and jax give the same words."""
    rng = np.random.default_rng(k)
    keys = rng.integers(0, 4, (600, k), dtype=np.uint8)
    keys[0] = 0xFF
    keys[1] = 0
    words = key_words(keys)
    assert len(words) == -(-k // 4)
    assert all(w.dtype == np.uint32 for w in words)
    for w, j in zip(words, key_words(jnp.asarray(keys))):
        np.testing.assert_array_equal(w, np.asarray(j))
    by_words = [bytes(keys[i]) for i in np.lexsort(words[::-1])]
    assert by_words == sorted(bytes(r) for r in keys)
    assert is_byte_key(keys) and not is_byte_key(keys[:, 0])
    assert key_layout(keys) == f"bytes{k}/{len(words)}w"
    assert key_layout(keys[:, 0].astype(np.int32)) == "int32"


@pytest.mark.parametrize("words", [2, 3])
@pytest.mark.parametrize("algo", ALGOS)
def test_sort_kv_segments_compound_key(algo, words):
    """Every stage-2 kernel orders a compound key by all its words, equal
    to ``lax.sort(num_keys=words)``; the stable ones (radix, oracle) also
    keep the values of equal keys in input order, and bitonic moves each
    value with its key. Each word takes a handful of values, the int32
    extremes among them, so most pairs are decided by the last word."""
    rng = np.random.default_rng(words)
    choices = np.array([np.iinfo(np.int32).min, -1, 0, 7,
                        np.iinfo(np.int32).max], np.int32)
    shape = (6, 300)
    keys = [rng.choice(choices, shape) for _ in range(words)]
    if not autotune.is_stable(algo):
        # the network is unstable: a key equal to the padding sentinel on
        # every word could swap with padding (the executor's guard refuses
        # such keys), so none is
        at_max = np.all([k == choices[-1] for k in keys], axis=0)
        keys[-1] = np.where(at_max, 7, keys[-1])
    keys = tuple(jnp.asarray(k) for k in keys)
    vals = jnp.arange(shape[0] * shape[1], dtype=jnp.int32).reshape(shape)
    want = jax.lax.sort(keys + (vals,), dimension=-1, is_stable=True,
                        num_keys=words)
    got_k, got_v = ops.sort_kv_segments(keys, vals, algo=algo)
    assert isinstance(got_k, tuple) and len(got_k) == words
    for g, w in zip(got_k, want[:-1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if autotune.is_stable(algo):
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want[-1]))
    else:
        col = np.asarray(got_v) % shape[1]
        assert (np.sort(col, axis=1) == np.arange(shape[1])).all()
        for k, g in zip(keys, got_k):
            np.testing.assert_array_equal(
                np.take_along_axis(np.asarray(k), col, axis=1), np.asarray(g))


def test_autotuner_keys_a_compound_cell_apart():
    """A compound key's cell key counts its words, so a one-word pick is
    never replayed for it; the one-word key is unchanged."""
    one = autotune.cell_key(4, 1024, jnp.int32, True, interpret=True)
    three = autotune.cell_key(4, 1024, jnp.int32, True, interpret=True,
                              words=3)
    assert one.startswith("kv|int32|4x1024|")
    assert three.startswith("kv|int32x3|4x1024|")
    autotune.reset()
    try:
        autotune.load_table({one: "bitonic"})
        assert autotune.choose(4, 1024, jnp.int32, interpret=True
                               ) == autotune.Choice("bitonic", "table")
        assert autotune.choose(4, 1024, jnp.int32, interpret=True, words=3
                               ) == autotune.Choice("oracle", "static")
    finally:
        autotune.reset()


SPMD_BYTE_SORT = """
import importlib.util
import jax, jax.numpy as jnp, numpy as np
from repro.core.sort import key_words, word_bucket_key
from repro.obs import layers
from repro.sphere.dataflow import Dataflow, SPMDExecutor

spec = importlib.util.spec_from_file_location("ref", {reference!r})
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)

ALGO = {algo!r}
ND = len(jax.devices())
mesh = jax.make_mesh((ND,), ("data",))
N = ND * 384
rng = np.random.default_rng(ND * 7 + len(ALGO))

def tie_heavy(k):
    # the leading 8 bytes from five values, the rest uniform
    lead = rng.integers(0, 256, (5, 8), dtype=np.uint8)
    keys = np.concatenate([lead[rng.integers(0, 5, N)],
                           rng.integers(0, 256, (N, k - 8), dtype=np.uint8)],
                          axis=1)
    return keys

def records(keys):
    rowid = np.arange(N, dtype=np.uint32).view(np.uint8).reshape(N, 4)
    fill = rng.integers(0, 256, (N, 8), dtype=np.uint8)
    return {{"key": keys, "payload": np.concatenate([rowid, fill], axis=1)}}

def run(ex, df, src):
    res = ex.run(df, {{k: jnp.asarray(v) for k, v in src.items()}})
    out = res.valid_records()
    got = ref.compare(src["key"], src["payload"], out["key"], out["payload"])
    assert got == dict.fromkeys(got, 0), got
    assert int(res.dropped) == 0
    return out

keys = tie_heavy(10)
keys[3] = 0xFF                # all-0xFF keys: the greatest 10-byte key
keys[N - 1] = 0xFF
keys[5] = 0
src = records(keys)
ex = SPMDExecutor(mesh, use_pallas=True, sort_algo=ALGO)
NB = 8                        # a multiple of 1 and 4 devices

# the default even splitters over the leading 31 bits; the tie-heavy keys
# fill few buckets, so each may take every record
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=NB,
                            capacity_factor=float(NB))
run(ex, df, src)
assert layers.last_sort_keys() == ("bytes10/3w",), layers.last_sort_keys()
assert layers.last_bucketing() == ("arithmetic",)

# given splitters: one bucket per leading value (searched)
lead = np.unique(word_bucket_key(key_words(keys)[0]))
spl = np.concatenate([lead[1:], np.full(NB - len(lead),
                                        np.iinfo(np.int32).max)])
df = Dataflow.source().sort(key=lambda r: r["key"],
                            splitters=jnp.asarray(spl[:NB - 1], jnp.int32),
                            num_buckets=NB, capacity_factor=float(NB))
run(ex, df, src)
assert layers.last_bucketing() == ("search",)

# an 8-byte key's all-0xFF value equals the padding sentinel on both
# words: the stable kernels deliver it, the bitonic guard refuses it
keys8 = tie_heavy(8)
keys8[9] = 0xFF
src8 = records(keys8)
df = Dataflow.source().sort(key=lambda r: r["key"], num_buckets=NB,
                            capacity_factor=float(NB))
if ALGO == "bitonic":
    try:
        run(ex, df, src8)
        raise AssertionError("sentinel collision was not detected")
    except ValueError as e:
        assert "sentinel" in str(e), e
else:
    out = run(ex, df, src8)
    assert (out["key"][-1] == 0xFF).all()
print("byte-key sort ok")
"""


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("devices", [1, 4])
def test_spmd_byte_key_sort_matches_reference(devices, algo):
    """``SPMDExecutor`` (the fused path, Pallas rank kernel) sorts 100-byte
    style records by a 10-byte key, every check of the reference 0: with
    the default splitters, with given splitters, and with all-0xFF keys;
    an 8-byte all-0xFF key (the padding sentinel) is delivered by the
    stable kernels and refused by the bitonic guard."""
    run_spmd(SPMD_BYTE_SORT.format(reference=str(REFERENCE), algo=algo),
             devices=devices)


@pytest.mark.parametrize("given_splitters", [False, True])
def test_host_byte_key_sort_matches_reference(tmp_path, given_splitters):
    """``HostExecutor`` runs the same byte-key ``Dataflow`` over Sector
    files: bucketed on the leading 31 bits, each bucket file sorted by all
    10 bytes, every check of the reference 0."""
    from repro.core.records import RecordCodec
    from repro.core.sort import key_words, word_bucket_key
    from repro.launch.train import make_sector
    from repro.sphere.dataflow import Dataflow, HostExecutor
    from repro.sphere.spe import SPE

    ref = reference()
    rng = np.random.default_rng(17)
    n = 2048
    lead = rng.integers(0, 256, (5, 8), dtype=np.uint8)
    keys = np.concatenate([lead[rng.integers(0, 5, n)],
                           rng.integers(0, 256, (n, 2), dtype=np.uint8)],
                          axis=1)
    keys[11] = 0xFF
    payload = np.concatenate(
        [np.arange(n, dtype=np.uint32).view(np.uint8).reshape(n, 4),
         rng.integers(0, 256, (n, 6), dtype=np.uint8)], axis=1)
    codec = RecordCodec.from_fields({"key": (np.uint8, (10,)),
                                     "payload": (np.uint8, (10,))})
    if given_splitters:
        spl = np.unique(word_bucket_key(key_words(keys)[0]))[1:]
        df = Dataflow.source(codec).sort(key=lambda r: r["key"],
                                         splitters=spl.astype(np.int32))
    else:
        df = Dataflow.source(codec).sort(key=lambda r: r["key"],
                                         num_buckets=4)
    master, client, daemon = make_sector(str(tmp_path), num_slaves=4)
    rows = codec.encode({"key": keys, "payload": payload})
    client.upload_dataset("/ts/in", [s.tobytes()
                                     for s in np.split(rows, 4)])
    daemon.run_until_stable()
    spes = [SPE(i, master.slaves[i].address, master, client.session_id)
            for i in range(4)]
    res = HostExecutor(master, client, spes).run(
        df, [f"/ts/in.{i:05d}" for i in range(4)])
    assert not res.errors, res.errors
    out = res.valid_records()
    got = ref.compare(keys, payload, out["key"], out["payload"])
    assert got == dict.fromkeys(got, 0), got
