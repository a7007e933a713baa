"""sort.row_move_ms: device time of row moves per call.

Layer: partition/pack (the ``sphere.move`` scope: ``kernels/ops.
partition_pack``'s slot-map scatter and row gathers, ``RecordCodec.pack``
and ``unpack`` around the exchange, and the sort's final gather). The self
time of the ops the program's op->layer map puts in that scope, per call,
mean over the chips (:mod:`bench.layers`). The rank kernel is not in it.
"""

from bench import layers


def read(ctx):
    ms = layers.per_call_ms(ctx)
    return ms.get("sphere.move") if ms else None
