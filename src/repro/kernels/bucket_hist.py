"""Pallas kernel: per-tile bucket histogram via an MXU one-hot matmul.

Terasort stage 1 (paper Fig 3) needs, per shard, the number of records
destined for every range bucket so the shuffle can lay records out
contiguously per destination. The CPU version is a table increment per
record; on TPU the idiomatic form is::

    counts = one_hot(ids, B)ᵀ @ ones(T, 128)    # an MXU matmul per tile

Grid iterates over tiles of the id vector; all grid steps map to the *same*
output block, which Pallas keeps resident in VMEM and we accumulate into
(initialized at step 0). Buckets sit on sublanes and records on lanes, so
the one-hot needs no relayout of the id vector; every lane of the
``(B, 128)`` result holds the same count. Bucket ids outside [0, B)
contribute nothing — the wrapper uses that to pad inputs to a whole number
of tiles.

The per-tile matmul runs in float32 (exact: a tile holds at most ``tile`` <
2^24 records), but the running accumulator is **int32** — a float32
accumulator silently loses +1 increments once a bucket's count passes 2^24
(≈16.7M records), which is well inside a production shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.autotune import interpret_default


def _hist_kernel(ids_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (1, tile) int32
    tile = ids.shape[-1]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], tile), 0)
    onehot = jnp.where(buckets == ids, 1.0, 0.0).astype(jnp.bfloat16)
    ones = jnp.ones((tile, out_ref.shape[1]), jnp.bfloat16)
    # MXU matmul: (B, tile) @ (tile, 128) -> (B, 128); per-tile counts <=
    # tile < 2^24 so the f32 matmul is exact — accumulate in int32 (exact
    # to 2^31)
    counts = jnp.dot(onehot, ones, preferred_element_type=jnp.float32)
    out_ref[...] += counts.astype(jnp.int32)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def bucket_histogram_pallas(
    bucket_ids: jnp.ndarray,
    num_buckets: int,
    tile: int = 2048,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """int32 (num_buckets,) histogram of ``bucket_ids`` (int32 (n,)).
    ``interpret=None`` resolves through
    :func:`repro.kernels.autotune.interpret_default`."""
    if interpret is None:
        interpret = interpret_default()
    return _bucket_histogram(bucket_ids, num_buckets, tile, interpret)


@functools.partial(jax.jit, static_argnames=("num_buckets", "tile",
                                             "interpret"))
def _bucket_histogram(bucket_ids, num_buckets: int, tile: int,
                      interpret: bool):
    n = bucket_ids.shape[0]
    n_pad = max(_round_up(n, tile), tile)
    # pad with an id guaranteed out of range -> lands in no bucket row
    ids = jnp.pad(bucket_ids.astype(jnp.int32), (0, n_pad - n),
                  constant_values=-1)
    b_pad = _round_up(max(num_buckets, 1), 16)
    out = pl.pallas_call(
        _hist_kernel,
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((b_pad, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, 128), jnp.int32),
        interpret=interpret,
    )(ids.reshape(1, n_pad))
    return out[:num_buckets, 0]
