"""Distributed Terasort (paper §4.2, Fig 3) and the Hadoop-style baseline.

``terasort`` is now a thin shim over the unified dataflow API — the whole
two-stage sort is one pipeline::

    Dataflow.source().sort(key=lambda r: r["key"], splitters=...,
                           num_buckets=...)

executed by :class:`repro.sphere.dataflow.SPMDExecutor` (or, over
Sector-stored records, by the host executor with bucket files).

Stage 1 ("hashing"): every record's key is range-partitioned into a bucket
(``searchsorted`` against splitters — the paper's T_0 < T_1 < ... thresholds;
with the default even splitters, :func:`uniform_bucket`'s division) and
shuffled to the device owning that bucket via
:class:`repro.core.shuffle.ShufflePlan` (flat or two-level wide-area).

Stage 2 ("sort each bucket"): each device sorts its received records — the
paper's point that "the SPE processes the *whole* data segment ... and does
not just process each record individually". The sort is the Pallas bitonic
kernel (TPU-native) or the XLA sort oracle.

After stage 2 the stream is globally sorted: all keys on device d precede all
keys on device d+1 (bucket ranges are contiguous per device).

``hadoop_style_sort`` is the comparison baseline (paper Table 1): a
block-store shuffle where every reducer reads the full map output — realized
as an ``all_gather`` followed by a local range filter + sort. It moves
``axis_size``× the bytes of the direct bucket shuffle; the roofline
collective term quantifies the paper's 2× claim on our hardware model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.shuffle import ShufflePlan
from repro.kernels import ops as kops

KEY_MAX = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass
class SortResult:
    """keys/payloads: (num_devices * capacity,) globally laid out so that the
    valid records on device d are ascending and all precede device d+1's."""
    keys: jax.Array
    payload: jax.Array
    valid: jax.Array
    dropped: jax.Array


def bucket_width(num_buckets: int) -> int:
    """Width of the even key ranges the default splitters cut the
    non-negative int32 keys ``[0, 2^31)`` into. Rounded down, so every
    splitter fits an int32 and no bucket is empty; the last bucket takes
    the remainder, fewer than ``num_buckets`` keys more than the others."""
    return 2**31 // num_buckets


def uniform_splitters(num_buckets: int) -> np.ndarray:
    """The default splitters: ``bucket_width * (1 .. num_buckets - 1)``,
    exact int32 (terasort keys are uniform). Every executor's default; the
    one definition both the arithmetic and the search path agree on."""
    w = bucket_width(num_buckets)
    return (w * np.arange(1, num_buckets, dtype=np.int64)).astype(np.int32)


def uniform_bucket(keys: jax.Array, num_buckets: int) -> jax.Array:
    """``searchsorted(uniform_splitters(num_buckets), keys, side="right")``
    for every int32 key, by one division instead of a search: negative keys
    go to bucket 0, keys past the last splitter (the int32 maximum among
    them) to ``num_buckets - 1``."""
    if num_buckets == 1:
        return jnp.zeros(keys.shape, jnp.int32)
    w = jnp.int32(bucket_width(num_buckets))
    b = jax.lax.div(jnp.maximum(keys.astype(jnp.int32), 0), w)
    return jnp.minimum(b, num_buckets - 1)


def is_byte_key(key) -> bool:
    """Whether a sort key is a compound byte key: ``(n, k)`` uint8, ordered
    by its ``k`` bytes lexicographically (memcmp order), as TeraSort's
    10-byte keys are. Every other key is one word (cast to int32)."""
    return key.ndim == 2 and key.dtype == np.uint8


def key_words(key) -> Tuple:
    """The ``ceil(k / 4)`` big-endian 32-bit words of ``(n, k)`` uint8 byte
    keys, most significant first, the last zero-padded: ``(n,)`` uint32
    each, numpy for a numpy key and jax otherwise. Comparing two keys' word
    tuples as unsigned integers, word by word, compares the keys by memcmp.
    Both executors order and bucket byte keys through it."""
    xp = np if isinstance(key, np.ndarray) else jnp
    k = key.shape[1]
    words = []
    for w in range(0, k, 4):
        word = key[:, w].astype(xp.uint32) << 24
        for j in range(1, min(4, k - w)):
            word = word | (key[:, w + j].astype(xp.uint32) << (24 - 8 * j))
        words.append(word)
    return tuple(words)


def word_bucket_key(first_word):
    """The int32 a byte key is range-bucketed on: its leading 31 bits (the
    first word of :func:`key_words` shifted right by one), in ``[0, 2^31)``
    like a terasort int32 key, so the default splitters cut it evenly and
    buckets stay monotone in the whole key."""
    return (first_word >> 1).astype(np.int32)


def key_layout(key) -> str:
    """How a sort stage orders ``key``: ``"int32"`` (one word) or
    ``"bytes<k>/<w>w"`` (``k`` key bytes in ``w`` words)."""
    if not is_byte_key(key):
        return "int32"
    k = key.shape[1]
    return f"bytes{k}/{-(-k // 4)}w"


def sampled_splitters(keys: jax.Array, num_buckets: int,
                      sample_per_shard: int, mesh: Mesh,
                      axis: str = "data") -> jnp.ndarray:
    """Sample-based splitters for non-uniform keys: every shard contributes a
    strided sample; quantiles of the gathered sample become the thresholds
    (the paper's 'more advanced hashing technique ... to more evenly
    distribute' remark, §3.6)."""

    def local_sample(k):
        n = k.shape[0]
        # clamp to the shard size: a shard smaller than sample_per_shard
        # contributes every record instead of slicing out of bounds
        take = min(sample_per_shard, n)
        stride = max(n // take, 1)
        samp = jax.lax.slice(k, (0,), (take * stride,), (stride,))
        return jax.lax.all_gather(samp, axis, tiled=True)

    gathered = shard_map(local_sample, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(), check_vma=False)(keys)
    ssorted = jnp.sort(gathered)
    m = ssorted.shape[0]
    idx = (jnp.arange(1, num_buckets) * m) // num_buckets
    return ssorted[idx]


def terasort(
    keys: jax.Array,
    payload: jax.Array,
    mesh: Mesh,
    axis: Union[str, Sequence[str]] = "data",
    splitters: Optional[jnp.ndarray] = None,
    capacity_factor: float = 2.0,
    use_pallas: bool = True,
    buckets_per_device: int = 1,
    plan: Optional[ShufflePlan] = None,
    chunks: Optional[int] = None,
    sort_algo: Optional[str] = None,
) -> SortResult:
    """Globally sort (keys, payload) sharded over ``axis``.

    keys: (N,) int32 >= 0; payload: (N,) int32 (e.g. record index into the
    90-byte values held in Sector).

    ``axis`` may be a single mesh axis (flat bucket shuffle) or a pair
    ``(dc_axis, node_axis)`` — then stage 1 runs the wide-area two-level
    shuffle of :mod:`repro.core.shuffle`, keeping cross-DC traffic to one
    dense tile per remote data center. An explicit ``plan`` overrides
    ``axis``/``buckets_per_device``/``capacity_factor``: its axes and bucket
    count drive the sharding specs and splitters. ``sort_algo`` pins the
    stage-2 segment-sort kernel (``"bitonic"``/``"radix"``/``"oracle"``);
    ``None`` defers to the backend-aware autotuner of
    :mod:`repro.kernels.autotune`. ``use_pallas`` runs the partition/pack
    send path and the stage-2 regroup through the Pallas rank kernel, as
    on :class:`~repro.sphere.dataflow.SPMDExecutor`. ``chunks`` sets the
    shuffle pipeline depth:
    W interleaved pack/exchange rounds per hop (see
    :func:`repro.core.shuffle.sphere_shuffle`); ``None`` defers to
    ``plan.chunks`` (or 1).

    .. deprecated:: thin shim — build the pipeline directly with
       ``Dataflow.source().sort(...)`` and an executor; a pipeline object
       reused across calls also reuses its compiled program.
    """
    from repro.sphere.dataflow import Dataflow, SPMDExecutor

    if plan is not None:
        axes = plan.axes
        num_buckets = plan.num_buckets
    else:
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        axis_size = math.prod(mesh.shape[a] for a in axes)
        num_buckets = axis_size * buckets_per_device
    if splitters is not None and splitters.shape[0] != num_buckets - 1:
        raise ValueError(f"{splitters.shape[0]} splitters for "
                         f"{num_buckets} buckets")

    df = Dataflow.source().sort(key=lambda r: r["key"], splitters=splitters,
                                num_buckets=num_buckets,
                                capacity_factor=capacity_factor)
    ex = SPMDExecutor(mesh, axes=axes, plan=plan, use_pallas=use_pallas,
                      chunks=chunks, sort_algo=sort_algo)
    res = ex.run(df, {"key": keys.astype(jnp.int32),
                      "payload": payload})
    return SortResult(keys=res.records["key"], payload=res.records["payload"],
                      valid=res.valid, dropped=res.dropped)


def hadoop_style_sort(
    keys: jax.Array,
    payload: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    splitters: Optional[jnp.ndarray] = None,
    use_pallas=kops._UNSET,
    algo: Optional[str] = None,
) -> SortResult:
    """Baseline: every reducer pulls the complete map output (block-store
    shuffle read amplification), then filters its own key range and sorts.
    Semantically identical to :func:`terasort`; moves D× the bytes.

    The local sort goes through the autotuned
    :func:`repro.kernels.ops.sort_kv_segments` entry point; ``algo`` pins
    ``"bitonic"``/``"radix"``/``"oracle"``, ``None`` autotunes.
    ``use_pallas`` is deprecated (``True`` → ``algo="bitonic"``, ``False``
    → ``algo="oracle"``)."""
    algo = kops._legacy_algo(use_pallas, algo, "hadoop_style_sort")
    axis_size = mesh.shape[axis]
    if splitters is None:
        splitters = uniform_splitters(axis_size)
    n_local = keys.shape[0] // axis_size

    def udf(k, p, spl):
        k = k.reshape(-1)
        p = p.reshape(-1)
        all_k = jax.lax.all_gather(k, axis, tiled=True)    # (N,) everywhere
        all_p = jax.lax.all_gather(p, axis, tiled=True)
        me = jax.lax.axis_index(axis)
        bucket = jnp.searchsorted(spl, all_k, side="right").astype(jnp.int32)
        mine = bucket == me
        # keep at most n_local * axis_size rows (full dataset upper bound);
        # realistic capacity: same as terasort's receive capacity.
        cap = k.shape[0] * 2
        skey = jnp.where(mine, all_k, KEY_MAX)
        pos = jnp.arange(skey.shape[0], dtype=jnp.int32)
        sk_row, order_row = kops.sort_kv_segments(skey[None, :],
                                                  pos[None, :], algo=algo)
        order, sk = order_row[0, :cap], sk_row[0, :cap]
        sp = jnp.take(all_p, order)
        sv = jnp.take(mine, order)
        return sk, sp, sv, jnp.zeros((), jnp.int32)

    sk, sp, sv, dropped = shard_map(
        udf, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis), P()),
        check_vma=False,
    )(keys, payload, splitters)
    return SortResult(keys=sk, payload=sp, valid=sv, dropped=dropped)


def is_globally_sorted(result: SortResult, num_devices: int) -> bool:
    """Host-side verification: valid keys ascend within each device block and
    block maxima never exceed the next block's minima."""
    keys = jax.device_get(result.keys)
    valid = jax.device_get(result.valid)
    per = keys.shape[0] // num_devices
    prev_max = -1
    for d in range(num_devices):
        k = keys[d * per:(d + 1) * per][valid[d * per:(d + 1) * per]]
        if k.size == 0:
            continue
        import numpy as np
        if not bool(np.all(np.diff(k) >= 0)):
            return False
        if k[0] < prev_max:
            return False
        prev_max = int(k[-1])
    return True
