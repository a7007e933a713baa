"""The trace reduction, on small traces: hand-made, and recorded on a TPU
v5e (two terasort-1chip calls and three stream steps, trimmed to the ops
line of the device and the benchmark's host spans)."""

from pathlib import Path

import pytest

from bench import traceread as T

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"


def ev(name, start, dur, plane=DEV, line=T.OPS_LINE):
    return T.Event(plane, line, name, float(start), float(dur))


def span(name, start, dur):
    return ev(name, start, dur, plane=T.HOST_PLANE, line="python")


@pytest.fixture
def handmade():
    # window [0, 100); ops [10, 30) holding a nested op [12, 20),
    # [25, 40) overlapping it, and [60, 70); one op outside the window
    return T.Trace([
        span("bench.traced", 0, 100),
        span("bench.x.call", 0, 50), span("bench.x.wait", 50, 50),
        ev("%while.1 = s32[] while(s32[] %a)", 10, 20),
        ev("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %b)", 12, 8),
        ev("%_partition_rank.3 = s32[8] custom-call(s32[8] %c)", 25, 15),
        ev("%_partition_rank.4 = s32[8] custom-call(s32[8] %d)", 60, 10),
        ev("%late = s32[] copy(s32[] %e)", 150, 10),
    ])


def test_busy_is_the_union_of_op_intervals(handmade):
    assert handmade.window_s == pytest.approx(100e-9)
    # [10, 40) and [60, 70)
    assert handmade.busy_s() == pytest.approx(40e-9)
    assert handmade.idle_share() == pytest.approx(0.6)


def test_kernel_time_sums_the_listed_names(handmade):
    assert handmade.kernel_s([r"^%_partition_rank(\.\d+)? = "]) \
        == pytest.approx(25e-9)
    assert handmade.kernel_s([r"^%nothing"]) == 0.0


def test_idle_gaps_are_labelled_by_host_span(handmade):
    # gaps [0, 10), [40, 60) and [70, 100), labelled at their middles
    gaps = handmade.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.x.wait", "bench.x.wait",
                                    "bench.x.call"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])


def test_top_ops_count_self_time(handmade):
    top = dict(handmade.top_ops())
    assert top["while.1 while s32[]"] == pytest.approx(12e-9)
    assert top["fusion.2 fusion s32[8]"] == pytest.approx(8e-9)


def test_short_name():
    assert T.short_name("%while.21 = (s32[]{:T(128)}, s32[12582913]{0:T(1024)"
                        "S(1)}) while((s32[]{:T(128)}) %tuple.71)") \
        == "while.21 while (s32[], s32[12582913])"
    assert T.short_name("plain") == "plain"


def test_a_trace_needs_its_window_span():
    with pytest.raises(ValueError):
        T.Trace([ev("%a = s32[] copy(s32[] %b)", 0, 1)])


def test_recorded_terasort_trace():
    tr = T.Trace(T.load_events(str(DATA / "terasort_trace.json.gz")))
    assert tr.devices == [DEV]
    # two back-to-back calls of about 3.34 s: the chip idles under 1 %
    assert 6.6 < tr.window_s < 6.8
    assert 0 < tr.idle_share() < 0.01
    # the rank kernel runs twice a call (send and stage-2 regroup)
    ranks = [e for e in tr._ops[DEV] if e.name.startswith("%_partition_rank")]
    assert len(ranks) == 4
    assert tr.kernel_s([r"^%_partition_rank(\.\d+)? = "]) == pytest.approx(
        sum(e.dur_ns for e in ranks) / 1e9)
    assert tr.kernel_s([r"^%_sort_kv(\.\d+)? = "]) > 0
    # every gap falls inside a call's host span
    assert {label for label, _ in tr.idle_gaps()} == {"bench.terasort.call"}
    top = tr.top_ops()
    assert len(top) == 10 and top[0][0].startswith("fusion.42 fusion")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_recorded_stream_trace():
    tr = T.Trace(T.load_events(str(DATA / "stream_trace.json.gz")))
    labels = {label for label, _ in tr.idle_gaps()}
    assert "bench.stream.step" in labels
    assert 0 < tr.busy_s() < tr.window_s


def test_events_round_trip(tmp_path):
    events = T.load_events(str(DATA / "terasort_trace.json.gz"))[:50]
    T.save_events(events, str(tmp_path / "t.json.gz"))
    assert T.load_events(str(tmp_path / "t.json.gz")) == events
