"""shuffle.all_to_all_ms: device time of the shuffle's all-to-all per call.

Layer: shuffle (``core/shuffle.py``). The summed duration of the ops whose
opcode is ``all-to-all`` on each chip for one call, as the mean over the
chips (seen in a TPU v5e trace of terasort-4chip-flat as
``%all_to_all.3 = u8[4,786434,100]{...} all-to-all(...)``, one a call).
On one chip the program elides the exchange and there is nothing to read.
"""

EVENTS = [r" all-to-all\("]


def read(ctx):
    if ctx.trace is None or not ctx.calls:
        return None
    seconds = ctx.trace.kernel_s(EVENTS) / ctx.calls
    return seconds * 1e3 if seconds > 0 else None
