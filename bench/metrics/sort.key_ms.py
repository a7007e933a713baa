"""sort.key_ms: device time of a byte key's conversion to words per call.

Layer: key words (the ``sphere.key`` scope of ``sphere/dataflow.py``
``SPMDExecutor._sort``: ``core/sort.key_words`` turning each ``uint8[k]``
key into its big-endian 32-bit words and the leading 31 bits it is bucketed
on, in the bucketing and again in stage 2). The self time of the ops the
program's op->layer map puts in that scope, per call, mean over the chips
(:mod:`bench.layers`). A program without the scope gives nothing.
"""

from bench import layers


def read(ctx):
    ms = layers.per_call_ms(ctx)
    return ms.get("sphere.key") if ms else None
