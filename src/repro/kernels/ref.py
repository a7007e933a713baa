"""Pure-jnp oracles for the Pallas kernels (used by the allclose test sweeps).

These are the semantics the kernels must match exactly; they are also the
fallback implementation path when Pallas is unavailable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bucket_histogram_ref(bucket_ids: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Count of records per bucket. ids outside [0, num_buckets) are ignored.

    Args:
      bucket_ids: int32 (n,)
      num_buckets: static python int
    Returns:
      int32 (num_buckets,)
    """
    ids = bucket_ids.astype(jnp.int32)
    onehot = (ids[:, None] == jnp.arange(num_buckets, dtype=jnp.int32)[None, :])
    return jnp.sum(onehot.astype(jnp.int32), axis=0)


def partition_rank_ref(dest: jnp.ndarray, num_dest: int, tile: int = 4096):
    """Fused (stable rank, histogram) of the destination vector.

    ``rank[i]`` counts earlier records with the same destination — the slot
    record i occupies within destination ``dest[i]``'s contiguous run, in
    arrival order (the exact layout a stable argsort by destination would
    produce). Out-of-range ids (< 0 or >= num_dest) count nothing and get
    an unspecified rank. O(n · num_dest) vectorized work, no sort — the
    one-hot cumsum runs as a scan over ``tile``-row chunks carrying the
    per-destination base (mirroring the Pallas kernel's grid), so transient
    memory is O(tile · num_dest) rather than O(n · num_dest).

    Args:
      dest: int32 (n,)
      num_dest: static python int
    Returns:
      (rank int32 (n,), counts int32 (num_dest,))
    """
    ids = dest.astype(jnp.int32).reshape(-1)
    n = ids.shape[0]
    if n == 0:
        return ids, jnp.zeros((num_dest,), jnp.int32)
    tile = min(tile, n)
    n_pad = (n + tile - 1) // tile * tile
    padded = jnp.pad(ids, (0, n_pad - n), constant_values=-1)
    cols = jnp.arange(num_dest, dtype=jnp.int32)[None, :]

    def step(base, chunk):
        oh = chunk[:, None] == cols
        cum = jnp.cumsum(oh.astype(jnp.int32), axis=0)
        rank = jnp.sum(jnp.where(oh, cum - 1 + base[None, :], 0), axis=1)
        return base + cum[-1], rank

    counts, ranks = jax.lax.scan(step, jnp.zeros((num_dest,), jnp.int32),
                                 padded.reshape(-1, tile))
    return ranks.reshape(-1)[:n], counts


def sort_segments_ref(keys: jnp.ndarray) -> jnp.ndarray:
    """Ascending sort of each segment (row) independently.

    Args:
      keys: (num_segments, segment_len) int32/uint32/float32
    Returns:
      sorted keys, same shape/dtype
    """
    return jnp.sort(keys, axis=-1)


def sort_kv_segments_ref(keys, values: jnp.ndarray):
    """Sort each segment of (key, value) rows by key (stable).

    Args:
      keys:   (num_segments, segment_len), or a tuple of such word arrays,
              most significant first: a compound key, ordered by its words
              lexicographically (one multi-operand ``lax.sort``)
      values: (num_segments, segment_len) payload (e.g. record index)
    Returns:
      (sorted_keys, permuted_values), ``sorted_keys`` shaped as ``keys``
    """
    if isinstance(keys, tuple):
        out = jax.lax.sort(keys + (values,), dimension=-1, is_stable=True,
                           num_keys=len(keys))
        return tuple(out[:-1]), out[-1]
    order = jnp.argsort(keys, axis=-1, stable=True)
    skeys = jnp.take_along_axis(keys, order, axis=-1)
    svals = jnp.take_along_axis(values, order, axis=-1)
    return skeys, svals
