"""Pallas kernel: in-VMEM bitonic sort of (key, payload) segments.

Terasort stage 2 (paper Fig 3) sorts each bucket locally; the paper's SPEs
call a CPU quicksort on the whole segment. Quicksort is branch/scatter bound
and has no TPU analogue, so we adapt the insight ("sort whole segments where
they live") to the TPU's vector units with a **bitonic sorting network**:

- the compare-exchange partner of element ``i`` at distance ``j`` (a power
  of two) is ``i ^ j``: two lane rotations (``pltpu.roll`` by ``j`` and by
  ``S - j``) and a select on bit ``j`` of the lane index bring every
  partner into place with *no gather/scatter* and no relayout;
- the ascending/descending direction of stage ``k`` depends only on bit
  ``k`` of the lane index, so it is a broadcasted-iota predicate;
- the whole network is O(S log² S) fully-vectorized compare-exchanges on
  segments resident in VMEM; the distances of one merge level run as a
  ``fori_loop`` with a dynamic rotation, so the program stays small
  however long the segment is.

One grid step sorts a **block of segments** at once: the operands stay 2-D
``(rows, S)`` — segments along the sublane axis, elements along the lane
axis — and every compare-exchange is a sublane×lane-shaped select over all
rows of the block simultaneously, amortizing one grid step over ``rows``
segments (the multi-segment layout the segmented terasort stage 2 feeds
it, where sorting ``bpd`` rows of ``R/bpd`` cuts the network from
O(R log² R) to O(R log² (R/bpd))). Segments are padded to at least one
lane tile (128) and to a power of two; :func:`bitonic_supported` states the
VMEM envelope.

The payload array is permuted alongside the keys (used to carry record
indices through the sort).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import interpret_default

#: VMEM budget (bytes) for one (rows, S) int32 operand block: the network
#: keeps keys, values, both rotations of each and the masks live, and
#: Pallas double-buffers the four in/out blocks.
_BLOCK_BUDGET = 1 << 20

_LANES = 128


def _lex(a, b, cmp):
    """``cmp`` (a strict order) of the key tuples ``a`` and ``b``, planes
    most significant first: decided by the first plane where they differ."""
    out = cmp(a[-1], b[-1])
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        out = cmp(x, y) | ((x == y) & out)
    return out


def _bitonic_kernel(*refs):
    # refs: the key planes (most significant first) and the values, then
    # their outputs in the same order
    nk = len(refs) // 2 - 1
    keys_refs, vals_ref = refs[:nk], refs[nk]
    out_k_refs, out_v_ref = refs[nk + 1:2 * nk + 1], refs[2 * nk + 1]
    rows, s = vals_ref.shape
    m = s.bit_length() - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
    keys = tuple(r[...] for r in keys_refs)   # (rows, S): a block of segments
    vals = vals_ref[...]
    for k_exp in range(1, m + 1):
        # 0 where the element's block sorts ascending (bit k_exp clear)
        down = (lane >> k_exp) & 1

        def stage(t, kv, down=down, k_exp=k_exp):
            keys, vals = kv
            j = jax.lax.shift_left(jnp.int32(1), k_exp - 1 - t)
            # 1 where the element is the upper of its pair: partner i ^ j
            # sits at i - j (roll by j), else at i + j (roll by s - j)
            upper = jnp.where((lane & j) == 0, 0, 1)
            pk = tuple(jnp.where(upper == 0, pltpu.roll(k, s - j, 1),
                                 pltpu.roll(k, j, 1)) for k in keys)
            pv = jnp.where(upper == 0, pltpu.roll(vals, s - j, 1),
                           pltpu.roll(vals, j, 1))
            # the lower slot of an ascending pair (and the upper slot of a
            # descending one) keeps the minimum
            take_min = upper == down
            swap = ((take_min & _lex(pk, keys, jnp.less))
                    | (jnp.logical_not(take_min)
                       & _lex(pk, keys, jnp.greater)))
            return (tuple(jnp.where(swap, p, k) for p, k in zip(pk, keys)),
                    jnp.where(swap, pv, vals))

        keys, vals = jax.lax.fori_loop(0, k_exp, stage, (keys, vals))
    for r, k in zip(out_k_refs, keys):
        r[...] = k
    out_v_ref[...] = vals


def _segment_pad(s: int) -> int:
    """Padded segment length: a power of two, at least one lane tile."""
    return max(_LANES, 1 << max(0, (s - 1).bit_length()))


def bitonic_supported(segment_len: int, rows_per_step: int = 8,
                      planes: int = 2) -> Optional[str]:
    """None when one ``(rows_per_step, S)`` block of each of ``planes``
    operands (key planes and values) fits the kernel's VMEM budget, else
    the reason (callers record it — never a silent skip)."""
    s_pad = _segment_pad(segment_len)
    nbytes = rows_per_step * s_pad * 4
    if nbytes * planes > 2 * _BLOCK_BUDGET:
        return (f"bitonic block rows·S_pad·4 = {nbytes} bytes for each of "
                f"{planes} planes exceeds the {2 * _BLOCK_BUDGET}-byte VMEM "
                f"budget (S={segment_len} padded to {s_pad}, "
                f"{rows_per_step} rows per step)")
    return None


def _max_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def sort_kv_segments_pallas(keys, values: jnp.ndarray,
                            rows_per_step: int = 8,
                            interpret: Optional[bool] = None):
    """Sort each row of ``keys`` ascending, permuting ``values`` alongside.

    keys/values: (num_segments, segment_len); ``keys`` may be a tuple of
    such int32 word planes, most significant first, compared
    lexicographically (a compound key). Each grid step sorts
    ``rows_per_step`` segments at once (sublane-packed). Padding — segment
    length to a power of two of at least 128, segment count to a whole
    number of blocks — uses a max sentinel (on every key plane) so padded
    slots sort to the end and are sliced off. Not stable — callers needing
    stability pack a unique tiebreak into keys. ``interpret=None`` resolves
    through :func:`repro.kernels.autotune.interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    return _sort_kv(keys, values, rows_per_step, interpret)


@functools.partial(jax.jit, static_argnames=("rows_per_step", "interpret"))
def _sort_kv(keys, values, rows_per_step: int, interpret: bool):
    planes = keys if isinstance(keys, tuple) else (keys,)
    n, s = values.shape
    s_pad = _segment_pad(s)
    rb = n if n <= rows_per_step else rows_per_step
    n_pad = -(-n // rb) * rb
    planes = tuple(jnp.pad(k, ((0, n_pad - n), (0, s_pad - s)),
                           constant_values=_max_sentinel(k.dtype))
                   for k in planes)
    values = jnp.pad(values, ((0, n_pad - n), (0, s_pad - s)))
    spec = pl.BlockSpec((rb, s_pad), lambda i: (i, 0))
    out = pl.pallas_call(
        _bitonic_kernel,
        grid=(n_pad // rb,),
        in_specs=[spec] * (len(planes) + 1),
        out_specs=[spec] * (len(planes) + 1),
        out_shape=[jax.ShapeDtypeStruct((n_pad, s_pad), a.dtype)
                   for a in planes + (values,)],
        interpret=interpret,
    )(*planes, values)
    out = [o[:n, :s] for o in out]
    return (tuple(out[:-1]) if isinstance(keys, tuple) else out[0]), out[-1]


def sort_segments_pallas(keys: jnp.ndarray,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Keys-only row sort (payload = dummy)."""
    dummy = jnp.zeros_like(keys, dtype=jnp.int32)
    out_k, _ = sort_kv_segments_pallas(keys, dummy, interpret=interpret)
    return out_k
