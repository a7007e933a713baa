"""Public jit'd wrappers for the Pallas kernels.

Every kernel picks its mode through
:func:`repro.kernels.autotune.interpret_default`: compiled by Mosaic on a
TPU, Pallas ``interpret`` mode (the kernel body runs as traced JAX ops) on
a CPU host.

The segment-sort entry points (:func:`sort_segments`,
:func:`sort_kv_segments`) dispatch through the backend-aware autotuner
(:mod:`repro.kernels.autotune`): ``algo=None`` measures bitonic vs radix vs
the XLA oracle once per shape cell and replays the cached winner; ``algo``
may pin ``"bitonic"`` / ``"radix"`` / ``"oracle"`` explicitly, and
``REPRO_KERNEL_FORCE`` overrides everything. The historical ``use_pallas``
boolean is deprecated (it predates the radix kernel): ``True`` maps to
``"bitonic"``, ``False`` to ``"oracle"``.
"""

from __future__ import annotations

import os
import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.bucket_hist import bucket_histogram_pallas
from repro.kernels.bitonic_sort import (sort_kv_segments_pallas,
                                        sort_segments_pallas)
from repro.kernels.partition import partition_rank_pallas
from repro.kernels.radix_sort import (sort_kv_segments_radix,
                                      sort_segments_radix)

_UNSET = object()  # sentinel: "use_pallas not passed" (deprecation shim)

def pad_sentinel(dtype):
    """Greatest value of ``dtype`` — the padding key that sorts to the end
    of a segment (+inf for floats, the integer max otherwise), as a numpy
    scalar so it stays concrete inside traced code. Stable sorts keep real
    keys equal to the sentinel ahead of suffix padding; only the unstable
    bitonic network needs the collision guard."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return dtype.type(float("inf"))
    return dtype.type(jnp.iinfo(dtype).max)


def _legacy_algo(use_pallas, algo: Optional[str], where: str) -> Optional[str]:
    """Fold the deprecated ``use_pallas`` boolean into ``algo``."""
    if use_pallas is _UNSET:
        return algo
    warnings.warn(
        f"{where}(use_pallas=...) is deprecated: the kernel choice is now "
        f"autotuned per shape/backend; pass algo='bitonic'/'radix'/'oracle' "
        f"to pin one (use_pallas={bool(use_pallas)} maps to "
        f"algo={'bitonic' if use_pallas else 'oracle'!r}).",
        DeprecationWarning, stacklevel=3)
    if algo is not None:
        return algo
    return "bitonic" if use_pallas else "oracle"


def resolve_sort_algo(num_segments: int, segment_len: int, dtype,
                      algo: Optional[str] = None, kv: bool = True,
                      words: int = 1) -> str:
    """The algorithm :func:`sort_segments` / :func:`sort_kv_segments` will
    run for this cell: the forced/pinned/autotuned choice as a plain string,
    resolvable at trace time (callers use it to decide stability-dependent
    guards before the sort runs). ``words`` counts the key's word planes (a
    compound key has more than one; the autotuner keys its cell by it).
    ``REPRO_KERNEL_FORCE`` beats a pinned ``algo``."""
    if not os.environ.get(autotune.FORCE_ENV) and algo is not None:
        if algo not in autotune.ALGOS:
            raise ValueError(f"algo={algo!r}: expected one of "
                             f"{autotune.ALGOS} (or None to autotune)")
        return algo
    return autotune.choose(num_segments, segment_len, dtype, kv=kv,
                           words=words).algo


def bucket_histogram(bucket_ids: jnp.ndarray, num_buckets: int,
                     use_pallas: bool = True) -> jnp.ndarray:
    """int32 (num_buckets,) histogram; ids outside range are ignored."""
    if not use_pallas:
        return ref.bucket_histogram_ref(bucket_ids, num_buckets)
    return bucket_histogram_pallas(bucket_ids, num_buckets)


def partition_rank(dest: jnp.ndarray, num_dest: int,
                   use_pallas: bool = True):
    """Fused one-pass (stable rank, histogram) of a destination vector —
    see :func:`repro.kernels.partition.partition_rank_pallas`."""
    if not use_pallas:
        return ref.partition_rank_ref(dest, num_dest)
    return partition_rank_pallas(dest, num_dest)


def partition_pack(
    columns: Sequence[jnp.ndarray],
    dest: jnp.ndarray,
    num_dest: int,
    capacity: int,
    use_pallas: bool = True,
) -> Tuple[List[jnp.ndarray], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """O(n) fused partition/pack: lay records out contiguously per
    destination and pack fixed-size ``(num_dest, capacity, ...)`` tiles.

    This is the shuffle send-path primitive (every ``sphere_shuffle`` /
    ``hierarchical_shuffle`` stage, the MoE expert regroup, and the
    segmented stage-2 sort all go through it). It replaces the historical
    stable-argsort + histogram + gather with one fused rank pass (Pallas
    kernel or jnp oracle), an O(n) slot-map scatter, and one gather per
    column — reproducing the stable-argsort layout exactly: destination
    d's records occupy slots [0, counts[d]) of row d in arrival order, and
    records past ``capacity`` are dropped *from the tail* (later arrivals
    lose, exactly as the argsort layout dropped them).

    Args:
      columns: arrays sharing leading dim n; each is packed into its own
        tile stack (dtypes are preserved — records are moved, not summed).
      dest: (n,) int32; ids outside [0, num_dest) are never packed (callers
        use ``num_dest`` as the virtual overflow destination).
      capacity: slots per destination.
    Returns (tiles, in_range, origin, dropped_local):
      tiles[i]:  (num_dest, capacity, *columns[i].shape[1:])
      in_range:  (num_dest, capacity) bool — slot holds a real record
      origin:    (num_dest, capacity) int32 source row (-1 on empty slots;
                 meaningful only where ``in_range``)
      dropped_local: () int32 — records beyond capacity, this shard only.

    The rank pass runs in the named scope ``partition.rank``, the scatter
    and gathers in ``partition.move``, so a profile of any program that
    calls this can tell them apart (Sphere counts them in its partition/
    pack layer: :mod:`repro.obs.layers`).
    """
    dest = jnp.asarray(dest, jnp.int32).reshape(-1)
    n = dest.shape[0]
    if n == 0:
        tiles = [jnp.zeros((num_dest, capacity) + c.shape[1:], c.dtype)
                 for c in columns]
        return (tiles, jnp.zeros((num_dest, capacity), bool),
                jnp.full((num_dest, capacity), -1, jnp.int32),
                jnp.zeros((), jnp.int32))
    with jax.named_scope("partition.rank"):
        rank, counts = partition_rank(dest, num_dest, use_pallas=use_pallas)
    with jax.named_scope("partition.move"):
        ok = (dest >= 0) & (dest < num_dest) & (rank < capacity)
        slot = jnp.where(ok, dest * capacity + rank, num_dest * capacity)
        origin = (jnp.full((num_dest * capacity + 1,), -1, jnp.int32)
                  .at[slot].set(jnp.arange(n, dtype=jnp.int32))
                  [:num_dest * capacity].reshape(num_dest, capacity))
        cap_iota = jnp.arange(capacity, dtype=counts.dtype)[None, :]
        in_range = cap_iota < counts[:, None]
        gidx = jnp.clip(origin, 0, n - 1).reshape(-1)
        tiles = [jnp.take(col, gidx, axis=0)
                 .reshape((num_dest, capacity) + col.shape[1:])
                 for col in columns]
        dropped_local = jnp.sum(jnp.maximum(counts - capacity, 0)
                                ).astype(jnp.int32)
    return tiles, in_range, origin, dropped_local


def sort_segments(keys: jnp.ndarray, use_pallas=_UNSET, *,
                  algo: Optional[str] = None) -> jnp.ndarray:
    """Sort each row ascending.

    ``algo=None`` → autotuned per shape/backend (see module docstring);
    ``"bitonic"``/``"radix"``/``"oracle"`` pin an implementation.
    ``use_pallas`` is the deprecated boolean predecessor.
    """
    algo = _legacy_algo(use_pallas, algo, "sort_segments")
    n, s = keys.shape
    resolved = resolve_sort_algo(n, s, keys.dtype, algo, kv=False)
    if resolved == "oracle":
        return ref.sort_segments_ref(keys)
    if resolved == "radix":
        return sort_segments_radix(keys)
    return sort_segments_pallas(keys)


def sort_kv_segments(keys, values: jnp.ndarray,
                     use_pallas=_UNSET, *, algo: Optional[str] = None):
    """Sort each row of (keys, values) by key.

    ``keys`` is one ``(segments, segment)`` array, or a tuple of such word
    arrays, most significant first, for a compound key ordered by its words
    lexicographically (every algorithm compares all of them); the sorted
    keys come back in the same form. ``algo=None`` → autotuned per
    shape/backend and word count; ``"radix"`` and ``"oracle"`` are stable,
    ``"bitonic"`` is not (callers needing stability check
    :func:`repro.kernels.autotune.is_stable` on the
    :func:`resolve_sort_algo` result). ``use_pallas`` is deprecated.
    """
    algo = _legacy_algo(use_pallas, algo, "sort_kv_segments")
    words = keys if isinstance(keys, tuple) else (keys,)
    n, s = words[0].shape
    resolved = resolve_sort_algo(n, s, words[0].dtype, algo, kv=True,
                                 words=len(words))
    if resolved == "oracle":
        return ref.sort_kv_segments_ref(keys, values)
    if resolved == "radix":
        return sort_kv_segments_radix(keys, values)
    return sort_kv_segments_pallas(keys, values)
