"""Pallas TPU kernels for the Terasort hot spots (paper §4.2, Fig 3).

The paper's compute-critical path is the two-stage distributed sort:
stage 1 hashes every record into a range bucket; stage 2 sorts each bucket
locally. On commodity CPUs those are a table-driven scatter and quicksort; on
TPU there is no efficient per-element scatter, so we adapt:

- ``bucket_hist``   — per-tile one-hot histogram, computed as an MXU matmul
                      (int32 accumulation).
- ``partition``     — fused histogram + stable counting rank in one pass
                      (the running count is an MXU matmul):
                      the O(n) shuffle send path (replaces the stable argsort
                      every send used to pay).
- ``bitonic_sort``  — in-VMEM bitonic network over (key, payload) pairs;
                      XOR partners arrive by lane rotations (``pltpu.roll``,
                      no gather/scatter), the TPU-native sort; one grid step
                      sorts a sublane-packed block of segments. Not stable.
- ``radix_sort``    — stable LSD counting-radix sort: one-hot digit rank as
                      chunked MXU matmuls (the ``partition`` primitive, one
                      pass per key digit) and the permutation as one-hot MXU
                      matmuls — no gather/scatter at all.
- ``autotune``      — backend-aware dispatch: measures bitonic vs radix vs
                      the XLA oracle once per segment-geometry cell, caches
                      the winner, persists the table into BENCH_kernels.json.

``ops`` exposes jit'd wrappers (including ``partition_pack``, the full
rank → slot-map → gather send-tile builder); the sort entry points dispatch
through the autotuner. ``ref`` holds the pure-jnp oracles used by the
tests' allclose sweeps.
"""

from repro.kernels.ops import (
    bucket_histogram,
    pad_sentinel,
    partition_pack,
    partition_rank,
    resolve_sort_algo,
    sort_segments,
    sort_kv_segments,
)

__all__ = ["bucket_histogram", "pad_sentinel", "partition_pack",
           "partition_rank", "resolve_sort_algo", "sort_segments",
           "sort_kv_segments"]
