"""Pallas kernel: stable LSD counting-radix sort of (key, payload) segments.

The bitonic network (:mod:`repro.kernels.bitonic_sort`) is O(S log² S)
compare-exchanges and **not stable** — ties (including the padding sentinel)
can swap. This kernel is the complementary design point: a least-significant
-digit counting radix sort, **stable by construction** (each pass preserves
the arrival order of equal digits), built from TPU-native primitives only —
Mosaic has neither per-element gather/scatter nor a cumulative sum:

- ``broadcasted_iota`` + compare to build one-hot digit planes, digits on
  sublanes and elements on lanes, 128 elements at a time;
- the stable counting rank — rank of element i within its digit bucket =
  # earlier elements with the same digit — as one **MXU matmul** per chunk
  with an upper-triangular ones matrix (the primitive
  :mod:`repro.kernels.partition` uses for the shuffle send path), carrying
  the running per-digit counts across chunks; the destination is that rank
  plus the number of elements with a smaller digit,
  ``pos = rank + Σ_{b < digit} hist[b]``, a sublane reduction;
- the permutation as chunked **one-hot MXU matmuls**: output lanes
  ``[c, c + 128)`` are ``X · Pᵀ`` with ``P[j, i] = (pos[i] == c + j)``. Each
  output column has exactly one nonzero term, so the f32 accumulate is
  exact once operands are split into 8-bit limbs (every limb < 2⁸ is exact
  in bf16).

The permutation is O(S²) work per segment and pass, so radix pays off only
on short segments; :func:`radix_supported` states the VMEM envelope and the
autotuner (:mod:`repro.kernels.autotune`) measures where it wins.

Keys are first mapped through an order-preserving bijection onto uint32
("sortable bits": int32 flips the sign bit, float32 flips sign-magnitude to
two's-complement-like order), sorted as unsigned digits, and mapped back —
one kernel body serves int32/uint32/float32. NaN keys are unsupported (as
with the bitonic kernel's ±inf sentinel); -0.0 orders before +0.0 (bit
order refines the numeric order at the one tie the bijection splits).

Padding (segment length to a lane multiple, segment count to whole blocks)
uses the transformed-domain maximum ``0xFFFFFFFF``: stability keeps real
rows ahead of padding even when a real key equals the sentinel, so — unlike
the bitonic kernel — no key value is reserved.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import interpret_default

#: supported digit widths (bits per pass); 32 must divide evenly.
SUPPORTED_BITS = (1, 2, 4, 8)

#: elements per rank chunk and output lanes per permutation matmul (one
#: lane tile, the MXU width).
_CHUNK = 128

#: soft VMEM budget (bytes) for the permutation's (128, S) one-hot plane;
#: bounds the segment-length envelope.
_VMEM_BUDGET = 4 << 20


# -- order-preserving key <-> uint32 bijections ------------------------------


def key_to_sortable_bits(keys: jnp.ndarray) -> jnp.ndarray:
    """Map int32/uint32/float32 keys onto uint32 so that unsigned byte order
    equals the key order (monotone bijection)."""
    dt = keys.dtype
    if dt == jnp.uint32:
        return keys
    if dt == jnp.int32:
        return (keys ^ jnp.int32(-2147483648)).astype(jnp.uint32)
    if dt == jnp.float32:
        bits = jax.lax.bitcast_convert_type(keys, jnp.uint32)
        sign = (bits >> jnp.uint32(31)) == jnp.uint32(1)
        return jnp.where(sign, ~bits, bits | jnp.uint32(0x80000000))
    raise TypeError(f"radix sort supports int32/uint32/float32 keys, "
                    f"got {dt}")


def sortable_bits_to_key(bits: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`key_to_sortable_bits`."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint32:
        return bits
    if dtype == jnp.int32:
        return bits.astype(jnp.int32) ^ jnp.int32(-2147483648)
    if dtype == jnp.float32:
        sign = (bits & jnp.uint32(0x80000000)) == jnp.uint32(0)
        raw = jnp.where(sign, ~bits, bits & jnp.uint32(0x7FFFFFFF))
        return jax.lax.bitcast_convert_type(raw, jnp.float32)
    raise TypeError(f"radix sort supports int32/uint32/float32 keys, "
                    f"got {dtype}")


# -- kernel ------------------------------------------------------------------


def _make_radix_kernel(bits: int, num_planes: int):
    nb = 1 << bits

    def kernel(*refs):
        in_refs = refs[:num_planes]
        out_refs = refs[num_planes:2 * num_planes]
        digit_ref, pos_ref, x_ref, y_ref = refs[2 * num_planes:]
        rows, s = in_refs[0].shape
        n_chunks = s // _CHUNK
        upper = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 1),
            1.0, 0.0).astype(jnp.bfloat16)
        bucket = jax.lax.broadcasted_iota(jnp.int32, (nb, _CHUNK), 0)
        target = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, s), 0)

        def rank_chunk(c, base):
            off = pl.multiple_of(c * _CHUNK, _CHUNK)
            oh = bucket == digit_ref[:, pl.ds(off, _CHUNK)]
            cum = jnp.dot(jnp.where(oh, 1.0, 0.0).astype(jnp.bfloat16), upper,
                          preferred_element_type=jnp.float32
                          ).astype(jnp.int32)
            pos_ref[:, pl.ds(off, _CHUNK)] = jnp.sum(
                jnp.where(oh, cum - 1 + base, 0), axis=0, keepdims=True)
            return base + cum[:, _CHUNK - 1:]

        def place_chunk(c, hist):
            off = pl.multiple_of(c * _CHUNK, _CHUNK)
            smaller = bucket < digit_ref[:, pl.ds(off, _CHUNK)]
            pos_ref[:, pl.ds(off, _CHUNK)] += jnp.sum(
                jnp.where(smaller, hist, 0), axis=0, keepdims=True)
            return hist

        def permute_chunk(c, x):
            off = pl.multiple_of(c * _CHUNK, _CHUNK)
            onehot = jnp.where(target + off == pos_ref[...], 1.0, 0.0)
            y_ref[:, pl.ds(off, _CHUNK)] = jax.lax.dot_general(
                x, onehot.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return x

        def one_row(r, carry):
            planes = [ref[pl.ds(r, 1), :] for ref in in_refs]    # (1, S)
            for shift in range(0, 32, bits):
                digit_ref[...] = (jax.lax.shift_right_logical(planes[0], shift)
                                  & (nb - 1))
                hist = jax.lax.fori_loop(0, n_chunks, rank_chunk,
                                         jnp.zeros((nb, 1), jnp.int32))
                jax.lax.fori_loop(0, n_chunks, place_chunk, hist)
                for p, plane in enumerate(planes):
                    for limb in range(4):
                        row = 4 * p + limb
                        x_ref[row:row + 1, :] = (
                            jax.lax.shift_right_logical(plane, 8 * limb)
                            & 0xFF).astype(jnp.float32)
                jax.lax.fori_loop(0, n_chunks, permute_chunk,
                                  x_ref[...].astype(jnp.bfloat16))
                planes = []
                for p in range(num_planes):
                    word = y_ref[4 * p:4 * p + 1, :].astype(jnp.int32)
                    for limb in range(1, 4):
                        row = 4 * p + limb
                        word = word | jax.lax.shift_left(
                            y_ref[row:row + 1, :].astype(jnp.int32), 8 * limb)
                    planes.append(word)
            for ref, plane in zip(out_refs, planes):
                ref[pl.ds(r, 1), :] = plane
            return carry

        jax.lax.fori_loop(0, rows, one_row, 0)

    return kernel


def default_bits(segment_len: int) -> int:
    """Digit width by segment length: 8 halves the pass count of 4; drop to
    4 once an (S, 256) int32 plane would exceed the VMEM budget."""
    return 8 if segment_len * 256 * 4 <= _VMEM_BUDGET else 4


def radix_supported(segment_len: int, bits: Optional[int] = None
                    ) -> Optional[str]:
    """Return None when the kernel envelope covers ``segment_len``, else a
    human-readable reason (callers log it — never a silent skip)."""
    b = bits if bits is not None else default_bits(segment_len)
    if b not in SUPPORTED_BITS:
        return f"bits={b} not in {SUPPORTED_BITS}"
    s_pad = _lane_pad(segment_len)
    if _CHUNK * s_pad * 4 > _VMEM_BUDGET:
        return (f"permutation plane 128·S_pad·4 = {_CHUNK * s_pad * 4} "
                f"bytes exceeds the {_VMEM_BUDGET}-byte VMEM budget "
                f"(S={segment_len}, bits={b})")
    return None


def _lane_pad(s: int) -> int:
    return -(-max(s, 1) // _CHUNK) * _CHUNK


def _radix(planes, bits: Optional[int], rows_per_step: int,
           interpret: Optional[bool]):
    """Sort rows of int32 sortable-bit ``planes[0]`` (payload planes ride
    along); returns the permuted planes, same shapes."""
    n, s = planes[0].shape
    b = bits if bits is not None else default_bits(s)
    reason = radix_supported(s, b)
    if reason is not None:
        raise ValueError(f"radix kernel unsupported here: {reason}")
    if interpret is None:
        interpret = interpret_default()
    return _radix_call(tuple(planes), b, rows_per_step, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "rows_per_step",
                                             "interpret"))
def _radix_call(planes, bits: int, rows_per_step: int, interpret: bool):
    n, s = planes[0].shape
    # lane-align the segment axis; transformed-domain max (-1 as int32)
    # pads sort to the suffix and stability keeps them behind real
    # 0xFFFFFFFF keys. Padding rows are whole segments of zeros.
    s_pad = _lane_pad(s)
    rb = n if n <= rows_per_step else rows_per_step
    n_pad = -(-n // rb) * rb
    padded = [jnp.pad(jnp.pad(p, ((0, 0), (0, s_pad - s)),
                              constant_values=-1 if i == 0 else 0),
                      ((0, n_pad - n), (0, 0)))
              for i, p in enumerate(planes)]
    spec = pl.BlockSpec((rb, s_pad), lambda i: (i, 0))
    limbs = 4 * len(planes)
    out = pl.pallas_call(
        _make_radix_kernel(bits, num_planes=len(planes)),
        grid=(n_pad // rb,),
        in_specs=[spec] * len(planes),
        out_specs=[spec] * len(planes),
        out_shape=[jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32)
                   for _ in planes],
        scratch_shapes=[pltpu.VMEM((1, s_pad), jnp.int32),     # digits
                        pltpu.VMEM((1, s_pad), jnp.int32),     # positions
                        pltpu.VMEM((limbs, s_pad), jnp.float32),
                        pltpu.VMEM((limbs, s_pad), jnp.float32)],
        interpret=interpret,
    )(*padded)
    return [o[:n, :s] for o in out]


def _to_i32(bits_u32: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(bits_u32, jnp.int32)


def _from_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def sort_kv_segments_radix(keys, values: jnp.ndarray,
                           bits: Optional[int] = None,
                           rows_per_step: int = 8,
                           interpret: Optional[bool] = None):
    """Stable-sort each row of ``keys`` ascending, permuting ``values``
    alongside.

    keys/values: (num_segments, segment_len); keys int32/uint32/float32
    (NaN unsupported), values any 32-bit dtype (moved bit-exactly). Rows of
    equal keys keep their input order — the property the stage-2 segmented
    sort relies on to keep suffix padding behind real max-value keys.
    ``keys`` may be a tuple of word planes, most significant first (a
    compound key): one LSD kernel call per word, least significant first,
    each carrying every word plane and the values, orders the rows by the
    words lexicographically (each call is stable). ``interpret=None``
    resolves through :func:`repro.kernels.autotune.interpret_default`.
    """
    val_dtype = values.dtype
    if val_dtype.itemsize != 4:
        raise TypeError(f"radix payload must be a 32-bit dtype, "
                        f"got {val_dtype}")
    words = keys if isinstance(keys, tuple) else (keys,)
    planes = [_to_i32(key_to_sortable_bits(k)) for k in words]
    planes.append(values if val_dtype == jnp.int32
                  else jax.lax.bitcast_convert_type(values, jnp.int32))
    for w in reversed(range(len(words))):
        # the word sorted on goes first; the other planes ride along
        rest = planes[:w] + planes[w + 1:]
        out = _radix([planes[w]] + rest, bits, rows_per_step, interpret)
        planes = out[1:w + 1] + [out[0]] + out[w + 1:]
    out_k = [sortable_bits_to_key(_from_i32(p), k.dtype)
             for p, k in zip(planes, words)]
    out_v = planes[-1]
    if val_dtype != jnp.int32:
        out_v = jax.lax.bitcast_convert_type(out_v, val_dtype)
    return (tuple(out_k) if isinstance(keys, tuple) else out_k[0]), out_v


def sort_segments_radix(keys: jnp.ndarray,
                        bits: Optional[int] = None,
                        rows_per_step: int = 8,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Keys-only row sort (single-plane kernel — no payload matmuls)."""
    kbits = _to_i32(key_to_sortable_bits(keys))
    (out_k,) = _radix([kbits], bits, rows_per_step, interpret)
    return sortable_bits_to_key(_from_i32(out_k), keys.dtype)
