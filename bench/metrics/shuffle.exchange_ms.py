"""shuffle.exchange_ms: device time of the shuffle hop per call.

Layer: shuffle (the ``sphere.exchange`` scope around the shuffle hop in
``SPMDExecutor._exchange``: framing, ``WireFrame.seal``/``open``, the
``all_to_all``, and the loops the compiler writes to lay the wire out; the
partition/pack inside it keeps its own layers). The self time of the ops
the program's op->layer map puts in that scope, per call, mean over the
chips (:mod:`bench.layers`). No cell reports it yet: a one-chip program
has no all-to-all.
"""

from bench import layers


def read(ctx):
    ms = layers.per_call_ms(ctx)
    return ms.get("sphere.exchange") if ms else None
