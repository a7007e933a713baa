"""Device time per layer: a trace's op self times summed through the
program's op->layer map (bench/layers.py and its readers)."""

from types import SimpleNamespace

import pytest

from bench import layers, spec
from bench import traceread as T

DEV = "/device:TPU:0"
READERS = ("sort.bucket_ms", "sort.row_move_ms", "shuffle.exchange_ms",
           "device_unscoped.sort")


def ev(name, start, dur):
    return T.Event(DEV, T.OPS_LINE, name, float(start), float(dur))


@pytest.fixture
def handmade():
    # window [0, 1e6) ns, two calls' worth of ops: a while [0, 4e5) holding
    # a bucketing fusion [1e5, 3e5); a gather [4e5, 6e5); an all-to-all
    # [6e5, 7e5); a copy the map does not name [7e5, 8e5)
    return T.Trace([
        T.Event(T.HOST_PLANE, "python", "bench.traced", 0.0, 1e6),
        ev("%while.21 = (s32[]) while(s32[] %a)", 0, 4e5),
        ev("%fusion.42 = s32[8]{0} fusion(s32[8] %b)", 1e5, 2e5),
        ev("%fusion.6 = u8[8,96]{1,0} fusion(u8[8,96] %c)", 4e5, 2e5),
        ev("%all-to-all.3 = u8[4,8,100] all-to-all(u8[4,8,100] %d)",
           6e5, 1e5),
        ev("%copy.9 = s32[8]{0} copy(s32[8] %e)", 7e5, 1e5),
    ])


MAP = {"while.21": "sphere.bucket", "fusion.42": "sphere.bucket",
       "fusion.6": "sphere.move", "all-to-all.3": "sphere.exchange"}


def _ctx(trace, calls=2):
    return SimpleNamespace(trace=trace, calls=calls, geometry={}, host={},
                           peaks={})


def test_layer_time_is_op_self_time_through_the_map(handmade):
    got = layers.layer_s(handmade, MAP)
    # the while keeps 2e5 ns of its own, its body's fusion 2e5
    assert got == pytest.approx({"sphere.bucket": 4e-4, "sphere.move": 2e-4,
                                 "sphere.exchange": 1e-4,
                                 layers.UNSCOPED: 1e-4})
    assert sum(got.values()) == pytest.approx(handmade.busy_s())


def test_readers_give_ms_per_call_and_the_unscoped_share(handmade,
                                                          monkeypatch):
    monkeypatch.setattr(layers, "program_map", lambda: MAP)
    ctx = _ctx(handmade)
    got = {name: spec.metric_reader(name).read(ctx) for name in READERS}
    assert got == pytest.approx({"sort.bucket_ms": 0.2,
                                 "sort.row_move_ms": 0.1,
                                 "shuffle.exchange_ms": 0.05,
                                 "device_unscoped.sort": 12.5})


def test_readers_read_nothing_without_a_program_map(handmade, monkeypatch):
    """A program built before the layer scopes publishes no map: every
    reader then returns None, and none raises."""
    monkeypatch.setattr(layers, "program_map", lambda: None)
    for name in READERS:
        reader = spec.metric_reader(name)
        assert reader.read(_ctx(handmade)) is None
        assert reader.read(_ctx(None)) is None


def test_program_map_is_the_last_run_of_the_program():
    from repro.obs import layers as obs_layers
    obs_layers.ran("jit_sphere_spmd", dict(MAP))
    assert layers.program_map() == MAP
    obs_layers.ran("jit_sphere_spmd", {})
    assert layers.program_map() is None


# -- a terasort-1chip trace recorded on a TPU v5e with its program map -------
# (bench/record_trace.py: two traced calls, the device's XLA Ops line and the
# bench.* host spans, and the op->layer map jit_sphere_spmd noted then, kept
# to the ops in the trace)


@pytest.fixture(scope="module")
def recorded():
    import json
    data = spec.BENCH / "tests" / "data"
    trace = T.Trace(T.load_events(str(data
                                      / "terasort_scoped_trace.json.gz")))
    with open(data / "terasort_scoped_layers.json") as f:
        program = json.load(f)
    assert program["module"] == "jit_sphere_spmd"
    return trace, program["layers"]


def test_recorded_trace_readers_give_their_values(recorded, monkeypatch):
    trace, op_map = recorded
    monkeypatch.setattr(layers, "program_map", lambda: op_map)
    ctx = _ctx(trace)
    got = {name: spec.metric_reader(name).read(ctx)
           for name in READERS}
    # what the traced run printed on the chip
    assert got == pytest.approx({"sort.bucket_ms": 2015.5705225000004,
                                 "sort.row_move_ms": 1002.0044359999994,
                                 "shuffle.exchange_ms": 13.797685500000002,
                                 "device_unscoped.sort":
                                     0.11515185531030762}, rel=1e-9)


def test_recorded_layer_times_add_up_to_busy_time(recorded):
    trace, op_map = recorded
    total = sum(layers.layer_s(trace, op_map).values())
    assert total == pytest.approx(trace.busy_s(), rel=1e-3)


@pytest.mark.parametrize("reader,layer", [
    ("partition_rank_roofline", "sphere.rank"),
    ("stage2_sort_roofline", "sphere.segsort")])
def test_recorded_kernels_the_rooflines_read_are_their_layers(
        recorded, reader, layer):
    """The ops a roofline reader matches are exactly the kernels (Pallas
    calls and sorts) the map puts in its layer, and they take nearly all
    of that layer's time."""
    import re
    trace, op_map = recorded
    mod = spec.metric_reader(reader)
    patterns = mod.EVENTS if reader.startswith("partition") \
        else mod.events(4610, 4095)
    ops = {e.name for e in trace._ops["/device:TPU:0"]}
    matched = {T.short_name(n).split(" ")[0] for n in ops
               if any(re.search(p, n) for p in patterns)}
    kernels = {T.short_name(n).split(" ")[0] for n in ops
               if op_map.get(T.short_name(n).split(" ")[0]) == layer
               and ('"tpu_custom_call"' in n
                    or T.short_name(n).split(" ")[1] == "sort")}
    assert matched and matched == kernels
    self_s = dict((n.split(" ")[0], s) for n, s in trace.top_ops(top=None))
    layer_s = layers.layer_s(trace, op_map)[layer]
    assert sum(self_s[n] for n in matched) > 0.95 * layer_s
