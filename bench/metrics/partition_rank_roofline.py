"""partition_rank_roofline: the Pallas rank kernel's share of its roofline.

Layer: partition/pack (``kernels/partition.py`` through
``kernels/ops.partition_rank``). The kernel's time is the summed device time
of its ops for one call: the ``tpu_custom_call`` named after its jitted
entry ``_partition_rank`` (seen in a TPU v5e trace of terasort-1chip:
``%_partition_rank.2`` on the send, 2^23 ids, and ``%_partition_rank.3`` on
the stage-2 regroup, 12,582,913 ids over 4,610 buckets). The bytes are what
the algorithm must move per call: each id read (4 B), each rank written
(4 B) and each destination's count written (4 B), for every call of the
kernel (the send and the regroup). The share is (bytes / HBM peak) / time:
the rank is byte-bound, one pass over the ids.
"""

EVENTS = [r"^%_partition_rank(\.\d+)? = "]


def bytes_per_call(rank_calls) -> int:
    return sum(8 * n + 4 * num_dest for n, num_dest in rank_calls)


def read(ctx):
    if ctx.trace is None or "rank_calls" not in ctx.geometry:
        return None
    seconds = ctx.trace.kernel_s(EVENTS) / ctx.calls
    if seconds <= 0:
        return None
    least = bytes_per_call(ctx.geometry["rank_calls"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
