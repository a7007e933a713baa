"""Compile rehearsals for TPU v5e: the main path, compiled by the TPU
compiler for a described (not attached) ``v5e:2x2`` topology.

Nothing here runs on a chip. The compiles catch what interpret mode cannot:
a kernel Mosaic refuses, a block that overflows VMEM, a program that does
not fit the chip's memory, a collective the partitioner added. The kernels
compile at the shapes ``chip_smoke.py`` runs (its one-chip terasort cell);
the four-chip programs at a smaller per-chip count (see
``FOUR_CHIP_TEST_N``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this
file.
"""

import base64
import contextlib
import hashlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.kernels.bitonic_sort import sort_kv_segments_pallas  # noqa: E402
from repro.kernels.partition import partition_rank_pallas  # noqa: E402
from repro.kernels.radix_sort import sort_kv_segments_radix  # noqa: E402

N_PER_CHIP = 1 << chip_smoke.MIN_LOG2_N
#: per-chip records of the four-chip program compiles. Their host memory
#: and time grow with it (~23 GB and ~5 min at chip_smoke's 2^21), so the
#: rehearsal checks the structure at a size a test worker can afford.
FOUR_CHIP_TEST_N = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            described = topologies.get_topology_desc(platform="tpu",
                                                     topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe v5e here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described


@pytest.fixture(scope="module")
def one_chip_terasort(topo):
    """The one-chip terasort deployment chip_smoke.py sizes first."""
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    return chip_smoke.Terasort(mesh, ("data",), N_PER_CHIP)


def _kernel_case(name, ts, chip):
    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if name == "partition_send":
        return (lambda d: partition_rank_pallas(d, ts.mesh.size,
                                                interpret=False),
                (arg((ts.n_per_device,)),))
    if name == "partition_regroup":
        return (lambda d: partition_rank_pallas(d, ts.bpd, interpret=False),
                (arg((ts.recv,)),))
    algo, _, words = name.partition("_key")
    kernel = {"bitonic": sort_kv_segments_pallas,
              "radix": sort_kv_segments_radix}[algo]
    shape = (ts.bpd, ts.seg_cap)
    if words:
        # a compound key of ``words`` int32 word planes (a 10-byte key: 3)
        return (lambda *kv: kernel(kv[:-1], kv[-1], interpret=False),
                tuple(arg(shape) for _ in range(int(words) + 1)))
    return (lambda k, v: kernel(k, v, interpret=False),
            (arg(shape), arg(shape)))


@pytest.mark.parametrize("name", ["partition_send", "partition_regroup",
                                  "bitonic", "radix", "bitonic_key3",
                                  "radix_key3"])
def test_kernel_compiles_at_terasort_cell(name, topo, one_chip_terasort):
    fn, args = _kernel_case(name, one_chip_terasort,
                            SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total <= chip_smoke.MEMORY_LIMIT


@pytest.mark.parametrize("layout", ["flat", "hierarchical"])
def test_terasort_program_compiles_on_four_chips(layout, topo, monkeypatch):
    """The fused SPMDExecutor terasort on the four described chips: Pallas
    kernels inside, one all_to_all per flat hop and two per hierarchical
    hop, every chip within the memory bound."""
    # default_backend() is the CPU here; the program is compiled for the TPU
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    devices = np.array(topo.devices)
    if layout == "flat":
        ts = chip_smoke.Terasort(Mesh(devices, ("data",)), ("data",),
                                 FOUR_CHIP_TEST_N, sort_algo="bitonic")
    else:
        ts = chip_smoke.Terasort(Mesh(devices.reshape(2, 2), ("dc", "node")),
                                 ("dc", "node"), FOUR_CHIP_TEST_N,
                                 hierarchical=True, sort_algo="bitonic")
    compiled, _, mem = ts.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert mem["total"] <= chip_smoke.MEMORY_LIMIT
    want = 2 if layout == "hierarchical" else 1
    assert ts.all_to_all() == want
    assert "all-to-all" in text


_METADATA = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_KERNEL_BODY = re.compile(r'"body":"([^"]+)"')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _kernel_ir(body_b64: str) -> str:
    """A Mosaic kernel's serialized IR with its debug locations stripped:
    the body embeds the source paths, lines and name stack it was traced
    with."""
    from jax._src.lib.mlir import ir, passmanager
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body_b64))
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)").run(module.operation)
        return hashlib.sha256(str(module).encode()).hexdigest()


def hlo_without_debug_info(text: str) -> str:
    """Optimised HLO text less what only debugging reads: every
    ``metadata={...}``, the stack-frame tables, and the debug locations
    inside each Mosaic kernel's body."""
    out, skipping = [], False
    for line in text.splitlines():
        if line in _TABLES:
            skipping = True
        elif skipping and not line[:1].isdigit() and line.strip():
            skipping = False
        if skipping or not line.strip():
            continue
        line = _METADATA.sub("", line)
        out.append(_KERNEL_BODY.sub(
            lambda m: '"body":"' + _kernel_ir(m.group(1)) + '"', line))
    return "\n".join(out)


def test_layer_scopes_change_only_metadata(topo, monkeypatch):
    """The one-chip terasort program compiled for the chip with its layer
    scopes and with ``jax.named_scope`` made a no-op: the same program,
    instruction for instruction, once debug info is set aside."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def compiled_text():
        ts = chip_smoke.Terasort(mesh, ("data",), FOUR_CHIP_TEST_N,
                                 sort_algo="bitonic")
        return ts.compile()[0].as_text()

    scoped = compiled_text()
    assert "sphere.bucket" in scoped and "sphere.segsort" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_text()
    assert "sphere." not in plain
    assert hlo_without_debug_info(scoped) == hlo_without_debug_info(plain)


def test_loops_the_compiler_writes_take_a_layer(topo, monkeypatch):
    """At 2^20 records a chip the compiler relays out the exchange's byte
    rows in ``while`` loops of its own (none at the smaller test size),
    two before the all-to-all and two after it: they, and every
    instruction of their bodies, are the exchange's. They are the only
    loops: range bucketing with the default splitters is a division, not a
    ``searchsorted`` loop. Every kernel, sort, gather, scatter and
    all-to-all of the four-chip program maps to a layer too."""
    from repro.core.introspect import op_layers
    from repro.obs.layers import EXCHANGE, SCOPES
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ts = chip_smoke.Terasort(Mesh(np.array(topo.devices), ("data",)),
                             ("data",), 1 << 20, sort_algo="bitonic")
    text = ts.compile()[0].as_text()
    got = op_layers(text)
    body_of = dict(re.findall(r"%([\w.\-]+) = [^\n]*? while\([^\n]*?"
                              r"body=%([\w.\-]+)", text))
    wire = [w for w, _ in re.findall(
        r"%([\w.\-]+) = \([^\n]*u8\[[^\n]*\) while\((?![^\n]*op_name)"
        r"([^\n]*)", text)]
    assert len(wire) == 4 and len(body_of) == 4
    wire_bodies = {body_of[w] for w in wire}
    computation, missing = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$", line)
        if head:
            computation = head.group(1)
            continue
        inst = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*? "
                        r"([a-z][\w\-]*)\(", line)
        if inst is None:
            continue
        name, opcode = inst.groups()
        if name in wire or computation in wire_bodies:
            assert got.get(name) == EXCHANGE, line[:120]
        elif (computation in body_of.values() or opcode in (
                "while", "all-to-all", "gather", "scatter", "sort")
              or "tpu_custom_call" in line) \
                and got.get(name) not in SCOPES.values():
            missing.append(line[:120])
    assert missing == []
