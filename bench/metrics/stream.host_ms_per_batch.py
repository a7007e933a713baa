"""stream.host_ms_per_batch: host time of a micro-batch outside the executor.

Layer: stream admission and checkpointing (``sphere/streaming.py``). The
wall time of ``StreamExecutor.step()`` (the benchmark's clock around each
call) less the executor's own time of the same steps (``run_seconds`` of
``StreamExecutor.stats()``), as the mean per step over the window:
admission, ``StreamCheckpoint.seal``, the Sector upload and batch assembly.
"""


def read(ctx):
    return ctx.host.get("host_ms_per_batch")
