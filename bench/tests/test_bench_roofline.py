"""The roofline readers' byte counts and their arithmetic."""

from types import SimpleNamespace

import pytest

from bench import spec

PEAKS = {"hbm_bytes_per_s": 819e9}


class FakeTrace:
    def __init__(self, seconds):
        self.seconds = seconds
        self.devices = ["/device:TPU:0"]
        self.asked = None

    def kernel_s(self, patterns):
        self.asked = patterns
        return self.seconds


def test_rank_bytes_are_ids_ranks_and_counts():
    rank = spec.metric_reader("partition_rank_roofline")
    # terasort-1chip: the send (2^23 ids, 1 chip) and the regroup
    # (12,582,913 ids over 4,610 buckets)
    calls = [[8388608, 1], [12582913, 4610]]
    assert rank.bytes_per_call(calls) == (8 * 8388608 + 4
                                          + 8 * 12582913 + 4 * 4610)


def test_stage2_bytes_are_keys_and_values_read_and_written():
    s2 = spec.metric_reader("stage2_sort_roofline")
    assert s2.bytes_per_call(4610, 4095) == 16 * 4610 * 4095  # 302 MB


def test_stage2_reads_whichever_kernel_ran():
    s2 = spec.metric_reader("stage2_sort_roofline")
    names = s2.events(4610, 4095)
    import re
    for op in ["%_sort_kv.1 = (s32[4616,4096]{1,0}) custom-call(",
               "%_radix_call.1 = (s32[4616,4096]{1,0}) custom-call(",
               "%sort.0 = (s32[4610,4095]{1,0:T(8,128)}, s32[4610,4095]) "
               "sort("]:
        assert any(re.search(p, op) for p in names), op
    # the stream's one-segment sort and other cells' sorts are not it
    assert not any(re.search(p, "%sort.0 = (s32[1,4244305]{1,0}) sort(")
                   for p in names)


def test_roofline_share_is_least_time_over_kernel_time():
    rank = spec.metric_reader("partition_rank_roofline")
    calls = [[1 << 20, 4]]
    trace = FakeTrace(seconds=2 * 0.01)           # two calls, 10 ms each
    ctx = SimpleNamespace(trace=trace, calls=2, peaks=PEAKS,
                          geometry={"rank_calls": calls})
    least = rank.bytes_per_call(calls) / 819e9
    assert rank.read(ctx) == pytest.approx(100 * least / 0.01)
    assert trace.asked == rank.EVENTS


def test_a_reader_with_nothing_to_read_returns_none():
    for name in ("partition_rank_roofline", "stage2_sort_roofline",
                 "shuffle.all_to_all_ms"):
        reader = spec.metric_reader(name)
        ctx = SimpleNamespace(trace=FakeTrace(0.0), calls=2, peaks=PEAKS,
                              geometry={"rank_calls": [[8, 1]],
                                        "stage2": [1, 8]})
        assert reader.read(ctx) is None
        assert reader.read(SimpleNamespace(trace=None, calls=2, peaks=PEAKS,
                                           geometry={})) is None


def test_all_to_all_reads_the_exchange_by_its_opcode():
    import re
    a2a = spec.metric_reader("shuffle.all_to_all_ms")
    seen = ("%all_to_all.3 = u8[4,786434,100]{1,2,0:T(8,128)(4,1)} "
            "all-to-all(u8[4,786434,100]{1,2,0:T(8,128)(4,1)} %copy.34), "
            "channel_id=1, replica_groups={{0,1,2,3}}")
    assert any(re.search(p, seen) for p in a2a.EVENTS)
    other = ("%all-reduce.4 = (s32[]{:T(128)}) all-reduce(s32[]{:T(128)} "
             "%add.141)")
    assert not any(re.search(p, other) for p in a2a.EVENTS)
    ctx = SimpleNamespace(trace=FakeTrace(2 * 0.00376), calls=2,
                          peaks=PEAKS, geometry={})
    assert a2a.read(ctx) == pytest.approx(3.76)
