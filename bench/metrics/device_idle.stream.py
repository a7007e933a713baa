"""device_idle.stream: the chip's idle share while the stream serves.

Layer: device (TPU v5e). 1 - (union of the device's op intervals) / (the
traced window), averaged over the chips, in percent. The window is the
``bench.traced`` host span around the traced seconds.
"""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
