"""stage2_key10_sort_roofline: the stage-2 compound-key sort's share of its
byte roofline.

Layer: stage-2 segment sort (``kernels/autotune.py`` picks, for a key of
``words`` 32-bit words, among ``kernels/bitonic_sort.py``'s lexicographic
network, ``kernels/radix_sort.py``'s LSD passes over the words and XLA's
multi-operand sort). Whichever ran, the work is the same:
``buckets_per_device x segment`` elements, each ``words`` key words and
one int32 value, read once and written once: ``2 x (4 words + 4)`` bytes an
element (``geometry["stage2_words"]``). The time is the device time of the
ops of whichever kernel ran, for one call: the ``tpu_custom_call`` of
bitonic's jitted entry ``_sort_kv`` or of radix's ``_radix_call`` (one per
word), or XLA's ``sort`` over the cell's own shape (its operands are the
word planes and the values, s32[buckets_per_device, segment] each).
"""

PALLAS_EVENTS = [r"^%_sort_kv(\.\d+)? = ", r"^%_radix_call(\.\d+)? = "]


def events(bpd: int, segment: int):
    return PALLAS_EVENTS + [rf"^%sort(\.\d+)? = \(s32\[{bpd},{segment}\]"]


def bytes_per_call(bpd: int, segment: int, words: int) -> int:
    return 2 * (4 * words + 4) * bpd * segment


def read(ctx):
    if (ctx.trace is None or "stage2" not in ctx.geometry
            or "stage2_words" not in ctx.geometry):
        return None
    bpd, segment = ctx.geometry["stage2"]
    seconds = ctx.trace.kernel_s(events(bpd, segment)) / ctx.calls
    if seconds <= 0:
        return None
    least = (bytes_per_call(bpd, segment, ctx.geometry["stage2_words"])
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
