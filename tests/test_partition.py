"""Fused O(n) partition/pack kernel vs the stable-argsort oracle.

The acceptance contract of ISSUE 4: `partition_pack` (Pallas kernel and jnp
oracle alike) must reproduce the historical stable-argsort send layout
*exactly* — per-bucket stability, counts, drop accounting — across dtypes,
skewed/empty buckets and out-of-range destinations, so the shuffle send
path could drop its O(n log n) sort without changing a single delivered
byte. (Flat-vs-hierarchical delivery equivalence stays locked in by
tests/test_hier_shuffle.py, unchanged.)
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels import ops, ref
from repro.kernels.partition import partition_rank_pallas

RNG = np.random.default_rng(0)


def argsort_layout(columns, dest, num_dest, capacity):
    """The pre-kernel send path (stable argsort + histogram + gather),
    kept here as the oracle the fused kernel must match."""
    n = dest.shape[0]
    order = np.argsort(dest, kind="stable")
    ok = (dest >= 0) & (dest < num_dest)
    counts = np.bincount(dest[ok], minlength=num_dest)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    # argsort puts out-of-range ids (always >= num_dest in the shuffle, the
    # overflow destination) after all real ones; negatives would sort first,
    # so skip them explicitly the way the slot map does.
    order = order[np.argsort(~ok[order], kind="stable")]  # ok records first
    in_range = np.arange(capacity)[None, :] < counts[:, None]
    origin = np.full((num_dest, capacity), -1, np.int64)
    for d in range(num_dest):
        take = min(counts[d], capacity)
        origin[d, :take] = order[offsets[d]:offsets[d] + take]
    tiles = []
    for col in columns:
        t = np.zeros((num_dest, capacity) + col.shape[1:], col.dtype)
        t[in_range] = col[origin[in_range]]
        tiles.append(t)
    dropped = int(np.maximum(counts - capacity, 0).sum())
    return tiles, in_range, origin, dropped


def _check_equal(dest, columns, num_dest, capacity, use_pallas):
    got_t, got_ir, got_or, got_dr = ops.partition_pack(
        [jnp.asarray(c) for c in columns], jnp.asarray(dest),
        num_dest, capacity, use_pallas=use_pallas)
    want_t, want_ir, want_or, want_dr = argsort_layout(
        columns, dest, num_dest, capacity)
    got_ir = np.asarray(got_ir)
    np.testing.assert_array_equal(got_ir, want_ir)
    np.testing.assert_array_equal(np.asarray(got_or)[got_ir],
                                  want_or[want_ir])
    assert (np.asarray(got_or)[~got_ir] == -1).all()
    for g, w in zip(got_t, want_t):
        np.testing.assert_array_equal(np.asarray(g)[got_ir], w[want_ir])
    assert int(got_dr) == want_dr


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("n,num_dest,capacity", [
    (1, 1, 1), (7, 3, 2), (200, 8, 10), (1000, 8, 300),
    (513, 16, 40), (4096, 4, 4096),
])
def test_matches_argsort_oracle_shapes(n, num_dest, capacity, use_pallas):
    dest = RNG.integers(0, num_dest, size=n).astype(np.int32)
    cols = [RNG.integers(0, 1 << 30, size=(n, 3)).astype(np.int32),
            np.arange(n, dtype=np.int32)]
    _check_equal(dest, cols, num_dest, capacity, use_pallas)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("dtype", ["int32", "float32", "uint8", "bfloat16",
                                   "bool"])
def test_pack_preserves_dtypes(dtype, use_pallas):
    n, num_dest, cap = 300, 5, 80
    dest = RNG.integers(0, num_dest, size=n).astype(np.int32)
    if dtype == "bool":
        col = RNG.random((n, 2)) > 0.5
    elif dtype == "bfloat16":
        col = jnp.asarray(RNG.standard_normal((n, 2)), jnp.bfloat16)
    else:
        col = RNG.standard_normal((n, 2)).astype(dtype) \
            if np.dtype(dtype).kind == "f" \
            else RNG.integers(0, 200, size=(n, 2)).astype(dtype)
    (tile,), in_rng, origin, _ = ops.partition_pack(
        [jnp.asarray(col)], jnp.asarray(dest), num_dest, cap,
        use_pallas=use_pallas)
    assert tile.dtype == jnp.asarray(col).dtype
    got = np.asarray(tile)[np.asarray(in_rng)]
    want = np.asarray(jnp.asarray(col))[np.asarray(origin)[np.asarray(in_rng)]]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_skew_empty_and_overflow_destinations(use_pallas):
    n, num_dest, cap = 500, 8, 40
    # everything lands in bucket 3 (max skew), plus overflow ids num_dest
    # and -1 padding — none of which may be packed or counted
    dest = np.full(n, 3, np.int32)
    dest[::7] = num_dest
    dest[::11] = -1
    cols = [np.arange(n, dtype=np.int32)]
    _check_equal(dest, cols, num_dest, cap, use_pallas)
    (tile,), in_rng, origin, dropped = ops.partition_pack(
        [jnp.asarray(cols[0])], jnp.asarray(dest), num_dest, cap,
        use_pallas=use_pallas)
    in_rng = np.asarray(in_rng)
    n_valid = int((dest == 3).sum())
    assert in_rng[3].sum() == min(n_valid, cap)
    assert int(dropped) == n_valid - cap
    for d in range(num_dest):
        if d != 3:
            assert in_rng[d].sum() == 0          # empty buckets stay empty


@pytest.mark.parametrize("use_pallas", [False, True])
def test_capacity_drop_keeps_earliest_arrivals(use_pallas):
    """Bounded-skew contract: when a bucket overflows, the *first* arrivals
    (original order) are kept — exactly the records the argsort layout
    kept."""
    dest = np.array([0, 1, 0, 0, 1, 0, 0], np.int32)
    col = np.arange(7, dtype=np.int32)
    (tile,), in_rng, origin, dropped = ops.partition_pack(
        [jnp.asarray(col)], jnp.asarray(dest), 2, 3, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(tile)[0], [0, 2, 3])
    np.testing.assert_array_equal(np.asarray(tile)[1][:2], [1, 4])
    assert int(dropped) == 2                     # rows 5, 6 of bucket 0


@pytest.mark.parametrize("use_pallas", [False, True])
def test_zero_records(use_pallas):
    (tile,), in_rng, origin, dropped = ops.partition_pack(
        [jnp.zeros((0, 2), jnp.float32)], jnp.zeros((0,), jnp.int32), 4, 5,
        use_pallas=use_pallas)
    assert tile.shape == (4, 5, 2)
    assert not np.asarray(in_rng).any()
    assert (np.asarray(origin) == -1).all()
    assert int(dropped) == 0


def test_rank_kernel_matches_oracle():
    """The fused Pallas rank pass ≡ the jnp oracle, including across tile
    boundaries (n > tile forces multi-step base accumulation)."""
    for n, num_dest in [(10, 4), (1024, 8), (3000, 8), (2500, 130)]:
        dest = RNG.integers(0, num_dest, size=n).astype(np.int32)
        kr, kc = partition_rank_pallas(jnp.asarray(dest), num_dest,
                                       tile=1024, interpret=True)
        rr, rc = ref.partition_rank_ref(jnp.asarray(dest), num_dest)
        np.testing.assert_array_equal(np.asarray(kc), np.asarray(rc))
        ok = (dest >= 0) & (dest < num_dest)   # rank defined only in-range
        np.testing.assert_array_equal(np.asarray(kr)[ok], np.asarray(rr)[ok])


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(-1, 9), min_size=1, max_size=250),
       st.integers(1, 20))
def test_property_layout_equals_argsort_oracle(dests, capacity):
    """Randomized acceptance property: fused layout ≡ stable-argsort layout
    (ids above num_dest act as the shuffle's overflow destination; -1 as
    padding)."""
    dest = np.asarray(dests, np.int32)
    n = len(dests)
    cols = [np.arange(n, dtype=np.int32),
            (np.arange(n)[:, None] * np.ones((1, 2))).astype(np.float32)]
    _check_equal(dest, cols, 8, capacity, use_pallas=False)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=120),
       st.integers(1, 10))
def test_property_kernel_equals_oracle(dests, capacity):
    """Pallas kernel path ≡ jnp oracle path, bit-for-bit."""
    dest = np.asarray(dests, np.int32)
    cols = [np.arange(len(dests), dtype=np.int32)]
    for up in (False, True):
        _check_equal(dest, cols, 6, capacity, up)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_rank_inside_shard_map_on_default_explicit_mesh(use_pallas):
    """Regression: jax.make_mesh makes Explicit axes by default, and the
    jnp oracle's ``.at[:n].set`` padding failed inside an eager shard_map
    on such a mesh ("Unexpected XLA sharding override"). Four devices,
    because a one-device mesh never shows it."""
    from test_spmd import run_spmd
    run_spmd(f"""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.kernels import ops, ref
mesh = jax.make_mesh((4,), ("data",))
assert mesh.axis_types == (AxisType.Explicit,)
dest = np.random.default_rng(0).integers(0, 5, size=4 * 300).astype(np.int32)
dd = jax.device_put(jnp.asarray(dest), NamedSharding(mesh, P("data")))
f = shard_map(lambda d: ops.partition_rank(d, 4, use_pallas={use_pallas}),
              mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P("data")),
              check_vma=False)
with mesh:
    rank, counts = f(dd)
rank, counts = np.asarray(rank), np.asarray(counts).reshape(4, 4)
for s, shard in enumerate(dest.reshape(4, -1)):
    want_rank, want_counts = ref.partition_rank_ref(jnp.asarray(shard), 4)
    ok = shard < 4
    assert (rank.reshape(4, -1)[s][ok] == np.asarray(want_rank)[ok]).all()
    assert (counts[s] == np.asarray(want_counts)).all()
""", devices=4)


# -- segmented stage-2 sort ----------------------------------------------------


@pytest.mark.parametrize("rows,cols", [(1, 7), (8, 64), (20, 257), (13, 1)])
def test_multi_segment_sort_matches_per_row_oracle(rows, cols):
    """The upgraded bitonic kernel sorts many sublane-packed segments per
    grid step; every row must equal an independent sort of that row."""
    keys = RNG.integers(0, 1 << 30, size=(rows, cols)).astype(np.int32)
    vals = np.arange(rows * cols, dtype=np.int32).reshape(rows, cols)
    gk, gv = ops.sort_kv_segments(jnp.asarray(keys), jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(gk), np.sort(keys, axis=-1))
    for r in range(rows):
        assert (sorted(zip(np.asarray(gk)[r], np.asarray(gv)[r]))
                == sorted(zip(keys[r], vals[r])))


def test_segmented_sort_equals_single_segment_multiset():
    """Segmenting a bucket-major buffer must not lose or invent records:
    the concatenated sorted segments hold the same multiset as one giant
    sorted segment, and each segment is internally sorted."""
    n, bpd = 4096, 8
    keys = RNG.integers(0, 1 << 20, size=n).astype(np.int32)
    seg = keys.reshape(bpd, n // bpd)
    got = np.asarray(ops.sort_segments(jnp.asarray(seg)))
    assert (np.diff(got, axis=1) >= 0).all()
    single = np.asarray(ops.sort_segments(jnp.asarray(keys[None, :])))[0]
    np.testing.assert_array_equal(np.sort(got.reshape(-1)), single)


# -- sampled_splitters small-shard regression ----------------------------------


def test_sampled_splitters_shard_smaller_than_sample():
    """n < sample_per_shard used to slice out of bounds; now the sample is
    clamped to the shard size."""
    import jax
    from repro.core.sort import sampled_splitters

    mesh = jax.make_mesh((1,), ("data",))
    keys = jnp.asarray(np.arange(4, dtype=np.int32) * 1000)
    spl = sampled_splitters(keys, num_buckets=4, sample_per_shard=16,
                            mesh=mesh)
    spl = np.asarray(spl)
    assert spl.shape == (3,)
    assert (np.diff(spl) >= 0).all()
    assert set(spl).issubset(set(np.asarray(keys)))
