"""The harness drives a run with the timed path broken underneath, and
``correct`` comes out false: once for the control and for each fault a cell
can have (:mod:`bench.faults`). Small sizes on the CPU; the look for a chip
is skipped (``run_cell``), everything else is a run."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench import faults, run, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 3


def small(cell):
    dep, mix = cell["deployment"], cell["mix"]
    if dep["kind"] == "terasort":
        dep["records_per_chip"] = 2048 // cell["chips"]
        dep["segment"] = 256
    else:
        dep.update(micro_batch=2048, vocab=300, warmup_batches=1)
        mix.update(request_events=128, rate_events_per_s=20000)


def one_run(name, fault, trace=False):
    return run.run_cell(name, SEED, 0.5, trace, time.perf_counter(), CPU,
                        adjust=small, fault=fault)


@pytest.mark.parametrize("name", ["terasort-1chip",
                                  "stream-wordcount-0.8knee"])
@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_run_is_correct(name, trace):
    res = one_run(name, None, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    if not trace:
        assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("fault", [f for f in faults.TERASORT
                                   if f != "no_exchange"])
def test_terasort_fault_is_caught(fault):
    res = one_run("terasort-1chip", fault)
    assert not res["correct"]


@pytest.mark.parametrize("fault", faults.STREAM)
def test_stream_fault_is_caught(fault):
    res = one_run("stream-wordcount-0.8knee", fault)
    assert not res["correct"]


SCRIPT = """
import json, sys, time
from bench import run
from bench.tests.test_bench_faults import CPU, SEED, small
out = {}
for fault in (None, "no_exchange"):
    res = run.run_cell("terasort-4chip-flat", SEED, 0.5, False,
                       time.perf_counter(), dict(CPU, count=4),
                       adjust=small, fault=fault)
    out[str(fault)] = res["correct"]
print(json.dumps(out))
"""


def test_exchange_left_out_is_caught_across_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT),
                                           str(spec.ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         cwd=spec.ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "None": True, "no_exchange": False}
