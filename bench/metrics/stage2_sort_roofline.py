"""stage2_sort_roofline: the stage-2 segment sort's share of its roofline.

Layer: stage-2 segment sort (``kernels/autotune.py`` picks among
``kernels/bitonic_sort.py``, ``kernels/radix_sort.py`` and XLA's sort).
Whichever ran, the work is the same: ``buckets_per_device x segment`` keys
and their values (int32 each) read once and written once, 16 bytes an
element. The time is the device time of the ops of whichever kernel ran,
for one call:

- bitonic: the ``tpu_custom_call`` of its jitted entry ``_sort_kv`` (seen
  in a TPU v5e trace of terasort-1chip: ``%_sort_kv.1`` on s32[4616,4096],
  the 4610x4095 cell padded);
- radix: the ``tpu_custom_call`` of its jitted entry ``_radix_call``;
- the XLA oracle: the ``sort`` op over the cell's own shape
  (s32[buckets_per_device, segment]).
"""

PALLAS_EVENTS = [r"^%_sort_kv(\.\d+)? = ", r"^%_radix_call(\.\d+)? = "]


def events(bpd: int, segment: int):
    return PALLAS_EVENTS + [rf"^%sort(\.\d+)? = \(s32\[{bpd},{segment}\]"]


def bytes_per_call(bpd: int, segment: int) -> int:
    return 16 * bpd * segment


def read(ctx):
    if ctx.trace is None or "stage2" not in ctx.geometry:
        return None
    bpd, segment = ctx.geometry["stage2"]
    seconds = ctx.trace.kernel_s(events(bpd, segment)) / ctx.calls
    if seconds <= 0:
        return None
    least = bytes_per_call(bpd, segment) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
