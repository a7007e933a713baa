"""Layer scopes of the fused SPMD program, and the op->layer map of the
program that ran last.

Each layer of :class:`repro.sphere.dataflow.SPMDExecutor`'s fused program
wraps its work in one ``jax.named_scope``. Scopes change only the metadata
of the compiled program: XLA carries the name stack into every optimised
instruction's ``metadata={op_name="jit(...)/.../sphere.<layer>/..."}``,
and the innermost scope of :data:`SCOPES` on that path names the op's
layer (:func:`repro.core.introspect.layer_of`).

- ``sphere.bucket``: range bucketing, the splitters and both bucketings
  of a sort (a division with the default splitters, else ``searchsorted``);
- ``sphere.key``: a byte key's conversion to its 32-bit words
  (:func:`repro.core.sort.key_words`), in the bucketing and in stage 2;
- ``sphere.rank``: ``kernels.ops.partition_rank`` (Pallas kernel or jnp
  oracle);
- ``sphere.move``: row moves, partition/pack's slot-map scatter and
  gathers, ``RecordCodec.pack``/``unpack``, the sort's final gather;
- ``sphere.exchange``: the shuffle hop, framing, ``WireFrame.seal``/
  ``open`` and the ``all_to_all``;
- ``sphere.segsort``: the stage-2 segment sort and the sentinel keys that
  feed it;
- ``sphere.udf``: map, reduce and shuffle-key UDF calls and the streaming
  carry.

``kernels.ops.partition_pack`` also serves programs that are not Sphere's
(expert dispatch), so it names its two passes in its own terms,
``partition.rank`` and ``partition.move``; :data:`SCOPES` counts them in
Sphere's layers.

Every run of the executor notes the executable's HLO module name and its
map ``{instruction name: layer}``, read once when it was compiled, so a
reader of a ``jax.profiler`` trace (whose op events are named by their
instructions) can sum device time per layer without reading HLO by hand.
With it goes the range-bucketing path of each sort stage of that program
(``"arithmetic"`` or ``"search"``, :func:`last_bucketing`), and the key
layout each sorts (:func:`last_sort_keys`).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

BUCKET = "sphere.bucket"
KEY = "sphere.key"
RANK = "sphere.rank"
MOVE = "sphere.move"
EXCHANGE = "sphere.exchange"
SEGSORT = "sphere.segsort"
UDF = "sphere.udf"

#: every scope the program names, with the layer it counts in.
SCOPES: Dict[str, str] = {
    BUCKET: BUCKET, KEY: KEY, RANK: RANK, MOVE: MOVE, EXCHANGE: EXCHANGE,
    SEGSORT: SEGSORT, UDF: UDF,
    "partition.rank": RANK, "partition.move": MOVE,
}

_last: Optional[Tuple[str, Dict[str, str]]] = None
_last_bucketing: Tuple[str, ...] = ()
_last_sort_keys: Tuple[str, ...] = ()


def ran(module: str, op_layers: Dict[str, str],
        bucketing: Sequence[str] = (), sort_keys: Sequence[str] = ()) -> None:
    """Note that the executable of ``module`` with this map, whose sort
    stages bucket by ``bucketing`` and order keys laid out as
    ``sort_keys``, ran last."""
    global _last, _last_bucketing, _last_sort_keys
    _last = (module, op_layers)
    _last_bucketing = tuple(bucketing)
    _last_sort_keys = tuple(sort_keys)


def last_run() -> Optional[Tuple[str, Dict[str, str]]]:
    """``(module name, op->layer map)`` of the program that ran last, or
    None before any run."""
    return _last


def last_bucketing() -> Tuple[str, ...]:
    """The range-bucketing path of each sort stage, in stage order, of the
    program that ran last: ``"arithmetic"`` (the default even splitters,
    one division) or ``"search"`` (given splitters, ``searchsorted``)."""
    return _last_bucketing


def last_sort_keys() -> Tuple[str, ...]:
    """The key layout of each sort stage, in stage order, of the program
    that ran last: ``"int32"`` (one word) or ``"bytes<k>/<w>w"`` (a
    ``k``-byte key ordered by its ``w`` big-endian words,
    :func:`repro.core.sort.key_layout`)."""
    return _last_sort_keys
