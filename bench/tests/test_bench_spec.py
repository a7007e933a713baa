"""The benchmark's files: found by name, and within the contract's
limits."""

import re
import subprocess
import sys

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_every_file_a_workload_names_is_found(bench):
    for w in bench["workloads"]:
        cell = spec.workload(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert spec.driver(cell["deployment"]["kind"]).Cell
        assert spec.reference(cell["deployment"]["reference"]).compare
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def test_a_missing_file_is_an_error():
    with pytest.raises(FileNotFoundError):
        spec.workload("no-such-cell")


def test_configs_are_the_files_benchmark_names(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    sources = [c["source"] for c in bench["configs"]]
    assert len(sources) == len(set(sources))
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        dep = spec._json(spec.ROOT / c["file"])
        assert sorted(dep["reduced"]) == sorted(c["reduced"])
        assert all(k in dep for k in c["reduced"])


def test_names_and_lines_keep_the_limits(bench):
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and 0 < len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in ends


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    ends, layers = bench["end_to_end"], bench["per_layer"]
    for w in bench["workloads"]:
        got = {m["name"] for m in ends if spec.applies(m, w["name"], ends)}
        assert "setup_s" in got and len(got) >= 2
        assert any(spec.applies(m, w["name"], ends) for m in layers)


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "terasort-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=spec.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(spec.ROOT / "src")})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
