"""The terasort-key10-1chip cell at CPU sizes: a sound run gives every check
0, and the byte-key driver's faults, on tie-heavy keys, come out not
correct through the reference's compare; its two readers."""

import re
import time
from types import SimpleNamespace

import pytest

from bench import layers, run, spec
from bench import traceread as T
from bench.tests.test_bench_faults import CPU, SEED

CELL = "terasort-key10-1chip"
FAULTS = spec.driver("terasort_bytekey").FAULTS


def small(distribution="uniform"):
    def adjust(cell):
        dep = cell["deployment"]
        dep.update(records_per_chip=2048, segment=256,
                   key_distribution=distribution)
    return adjust


def one_run(adjust, fault=None, trace=False):
    return run.run_cell(CELL, SEED, 0.5, trace, time.perf_counter(), CPU,
                        adjust=adjust, fault=fault)


@pytest.mark.parametrize("distribution,trace", [
    ("uniform", False), ("uniform", True), ("tie_heavy", False)])
def test_a_sound_run_is_correct(distribution, trace):
    res = one_run(small(distribution), trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sorted(res["checks"]) == sorted([
        "records_lost_or_extra", "keys_out_of_order", "rowids_not_once",
        "records_not_intact", "dropped"])
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    if not trace:
        assert set(res["metrics"]) == {"sort_records_per_s", "setup_s"}


@pytest.mark.parametrize("fault,check", [
    ("last_word_ignored", "keys_out_of_order"),
    ("control", "records_not_intact"),
    ("record_lost", "records_lost_or_extra")])
def test_fault_is_caught_on_tie_heavy_keys(fault, check):
    """A sort that ignores the key's last word (bytes 8-9), values left in
    place, one record lost: each is a nonzero count of the reference."""
    assert fault in FAULTS
    res = one_run(small("tie_heavy"), fault=fault)
    assert not res["correct"]
    assert res["checks"][check]["value"] > 0, res["checks"]


def test_stage2_key10_bytes_are_words_and_values_read_and_written():
    s2 = spec.metric_reader("stage2_key10_sort_roofline")
    # terasort-key10-1chip: 4,610 segments of 4,095 slots, 3 key words
    assert s2.bytes_per_call(4610, 4095, 3) == 2 * 16 * 4610 * 4095
    ops = ["%_sort_kv.1 = (s32[4616,4096]{1,0}, s32[4616,4096]{1,0}, "
           "s32[4616,4096]{1,0}, s32[4616,4096]{1,0}) custom-call(",
           "%_radix_call.2 = (s32[4616,4096]{1,0}) custom-call(",
           "%sort.3 = (s32[4610,4095]{1,0}, s32[4610,4095]{1,0}, "
           "s32[4610,4095]{1,0}, s32[4610,4095]{1,0}) sort("]
    for op in ops:
        assert any(re.search(p, op) for p in s2.events(4610, 4095)), op


def test_stage2_key10_share_is_least_time_over_kernel_time():
    from bench.tests.test_bench_roofline import PEAKS, FakeTrace
    s2 = spec.metric_reader("stage2_key10_sort_roofline")
    trace = FakeTrace(seconds=2 * 0.05)           # two calls, 50 ms each
    geometry = {"stage2": [4610, 4095], "stage2_words": 3}
    ctx = SimpleNamespace(trace=trace, calls=2, peaks=PEAKS,
                          geometry=geometry)
    least = s2.bytes_per_call(4610, 4095, 3) / PEAKS["hbm_bytes_per_s"]
    assert s2.read(ctx) == pytest.approx(100 * least / 0.05)
    # a program that publishes no word count (the int32 terasort) reads none
    ctx.geometry = {"stage2": [4610, 4095]}
    assert s2.read(ctx) is None


def test_key_ms_is_the_key_scope_per_call(monkeypatch):
    dev = "/device:TPU:0"
    trace = T.Trace([
        T.Event(T.HOST_PLANE, "python", "bench.traced", 0.0, 1e6),
        T.Event(dev, T.OPS_LINE, "%fusion.1 = u32[8]{0} fusion(u8[8,10] %a)",
                0.0, 3e5),
        T.Event(dev, T.OPS_LINE, "%fusion.2 = s32[8]{0} fusion(s32[8] %b)",
                4e5, 1e5)])
    ctx = SimpleNamespace(trace=trace, calls=2, geometry={}, host={},
                          peaks={})
    reader = spec.metric_reader("sort.key_ms")
    monkeypatch.setattr(layers, "program_map",
                        lambda: {"fusion.1": "sphere.key",
                                 "fusion.2": "sphere.bucket"})
    assert reader.read(ctx) == pytest.approx(0.15)
    # the parent's program has no sphere.key scope: nothing to read
    monkeypatch.setattr(layers, "program_map",
                        lambda: {"fusion.2": "sphere.bucket"})
    assert reader.read(ctx) is None
