"""device_idle.sort: the chips' idle share while terasort runs.

Layer: device (TPU v5e). 1 - (union of the device's op intervals) / (the
traced window), averaged over the chips, in percent. The window is the
``bench.traced`` host span around the traced calls.
"""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
