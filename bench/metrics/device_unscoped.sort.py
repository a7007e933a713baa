"""device_unscoped.sort: share of the chips' busy time no layer claims.

Layer: device. The self time of the ops the program's op->layer map names
no layer for (:mod:`bench.layers`), over the device's busy time (the union
of its op intervals in the traced window), in percent, mean over the
chips. It says how much of the device time the per-layer metrics cannot
see.
"""

from bench import layers


def read(ctx):
    ms = layers.per_call_ms(ctx)
    if ms is None:
        return None
    busy_ms = ctx.trace.busy_s() * 1e3 / ctx.calls
    if busy_ms <= 0:
        return None
    return 100.0 * ms.get(layers.UNSCOPED, 0.0) / busy_ms
