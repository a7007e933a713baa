"""Pallas kernel: fused per-destination histogram + stable counting rank.

The shuffle send path (paper §3.2 "the output can be sent to multiple
locations") has to lay local records out contiguously per destination before
the capacity-bounded ``all_to_all``. The historical implementation paid a
full stable ``argsort`` over every local record on every send — O(n log² n)
compare-exchanges on TPU — even though the layout only needs, per record,

  ``rank[i]`` = how many earlier records share record i's destination,

and, per destination, the total count. Both fall out of ONE pass over the
destination vector (the paper's one-pass "hashing" stage of Fig 3):

- the destination one-hot of a tile (destinations on sublanes, records on
  lanes) times an upper-triangular ones matrix — one MXU matmul, the
  cumulative sum Mosaic has no primitive for — gives each record its
  *intra-tile* rank in its destination row and the tile's histogram in the
  last column; destinations go 512 at a time, so VMEM stays bounded for
  any ``num_dest``;
- a running per-destination base (the histogram of all earlier tiles) is
  kept resident in the revisited output block and added to the intra-tile
  rank, making ranks global and **stable by construction** — records keep
  their arrival order within a destination, exactly like the stable argsort
  they replace.

Counts accumulate in **int32** (exact to 2^31); only the per-tile matmul
runs in float32, exact because a tile holds far fewer than 2^24 records.

Downstream (:func:`repro.kernels.ops.partition_pack`) converts
``(rank, counts)`` into the packed ``(num_dest, capacity, ...)`` send tiles
with one O(n) slot-map scatter + one gather per column — no sort anywhere
on the send path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.autotune import interpret_default

#: destinations handled per inner step: bounds the (chunk, tile) one-hot and
#: running-count planes in VMEM whatever ``num_dest`` is.
_DEST_CHUNK = 512


def _rank_kernel(ids_ref, rank_ref, counts_ref, *, dest_chunk: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    ids = ids_ref[...]                       # (1, tile) int32
    tile = ids.shape[-1]
    # inclusive running count along the record axis is one MXU matmul with
    # an upper-triangular ones matrix (Mosaic has no cumsum): 0/1 operands
    # are exact in bf16 and the f32 accumulator is exact below 2^24 > tile
    upper = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                      <= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1),
                      1.0, 0.0).astype(jnp.bfloat16)
    dest_iota = jax.lax.broadcasted_iota(jnp.int32, (dest_chunk, tile), 0)

    def chunk(c, rank):
        lo = pl.multiple_of(c * dest_chunk, dest_chunk)
        # destination one-hot, destinations on sublanes: (dest_chunk, tile)
        oh = (dest_iota + lo) == ids
        cum = jnp.dot(jnp.where(oh, 1.0, 0.0).astype(jnp.bfloat16), upper,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        base = counts_ref[pl.ds(lo, dest_chunk), :][:, :1]  # earlier tiles
        # each record's global stable rank within its destination row
        rank = rank + jnp.sum(jnp.where(oh, cum - 1 + base, 0), axis=0,
                              keepdims=True)
        counts_ref[pl.ds(lo, dest_chunk), :] = (
            counts_ref[pl.ds(lo, dest_chunk), :] + cum[:, tile - 1:])
        return rank

    rank_ref[...] = jax.lax.fori_loop(
        0, counts_ref.shape[0] // dest_chunk, chunk,
        jnp.zeros((1, tile), jnp.int32))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def partition_rank_pallas(
    dest: jnp.ndarray,
    num_dest: int,
    tile: int = 256,
    interpret: Optional[bool] = None,
):
    """One-pass fused (stable rank, histogram) of ``dest`` (int32 (n,)).

    Returns ``(rank (n,) int32, counts (num_dest,) int32)``. ``rank[i]`` is
    meaningful only where ``dest[i]`` is in [0, num_dest); out-of-range ids
    (negative padding, the ``num_dest`` overflow destination) contribute to
    no count and get an unspecified rank. ``tile`` (a multiple of 128) is
    the records per grid step; ``interpret=None`` resolves through
    :func:`repro.kernels.autotune.interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    return _partition_rank(dest, num_dest, tile, interpret)


@functools.partial(jax.jit, static_argnames=("num_dest", "tile", "interpret"))
def _partition_rank(dest, num_dest: int, tile: int, interpret: bool):
    n = dest.shape[0]
    n_pad = max(_round_up(max(n, 1), tile), tile)
    # pad with -1: matches no destination row, counts nothing
    ids = jnp.pad(dest.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    dest_chunk = min(_DEST_CHUNK, _round_up(max(num_dest, 1), 16))
    d_pad = _round_up(max(num_dest, 1), dest_chunk)
    # counts live in a resident (d_pad, 128) block, broadcast along lanes
    rank, counts = pl.pallas_call(
        functools.partial(_rank_kernel, dest_chunk=dest_chunk),
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                   pl.BlockSpec((d_pad, 128), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((d_pad, 128), jnp.int32)],
        interpret=interpret,
    )(ids.reshape(1, n_pad))
    return rank[0, :n], counts[:num_dest, 0]
