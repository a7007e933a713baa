"""stream.executor_ms_per_batch: the executor's time of a micro-batch.

Layer: dataflow executor (``sphere/dataflow.py`` ``SPMDExecutor.run``). The
growth of ``StreamExecutor.stats()["run_seconds"]`` (the program's own clock
around each ``inner.run``, synced by ``int(res.dropped)``) over the window,
as the mean per step.
"""


def read(ctx):
    return ctx.host.get("executor_ms_per_batch")
