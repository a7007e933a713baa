"""sort.bucket_ms: device time of range bucketing per call.

Layer: range bucketing (``sphere/dataflow.py`` ``SPMDExecutor._sort``: the
splitters and both ``searchsorted`` calls, in the ``sphere.bucket`` scope).
The self time of the ops the program's op->layer map puts in that scope,
per call, mean over the chips (:mod:`bench.layers`).
"""

from bench import layers


def read(ctx):
    ms = layers.per_call_ms(ctx)
    return ms.get("sphere.bucket") if ms else None
