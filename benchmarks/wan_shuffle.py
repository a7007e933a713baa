"""Wide-area shuffle benchmark: flat vs hierarchical bytes-over-WAN.

The paper's headline differentiator (§1, §2.2) is that Sector/Sphere manages
data *across* geographically distributed data centers. This benchmark prices
the bucket shuffle (§3.2) on the paper's Open Cloud Testbed model — 4
locations × 30 nodes, 1 GE in rack, a shared 10 GE uplink per site with
~30 ms one-way WAN latency — comparing

  flat  — one all_to_all over all 120 devices: every node ships a
          fixed-capacity tile to each of the 90 remote devices, i.e. 90
          sparse WAN flows per node per round;
  hier  — the two-level :class:`repro.core.shuffle.ShufflePlan`: stage A
          aggregates intra-DC, stage B ships ONE dense tile per remote DC
          (3 WAN flows per node), stage C is free.

Three byte accountings per round (one §3.5.1 segment of records in flight
per node), worst-case zero-drop capacities drawn from a multinomial model:

  useful    records that genuinely change DC — identical by construction
            (a record crosses the WAN exactly once either way);
  slot      what the capacity-padded all_to_all physically ships: tiles ×
            capacity slots. Hierarchical wins modestly — aggregated tiles
            concentrate around their mean, per-pair tiles pay the max-of-
            14400-pairs tail;
  wire      WAN-effective bytes with each flow rounded up to the transfer
            quantum a long fat pipe needs to sustain throughput (the
            bandwidth-delay product of the 10 GE / 30 ms link — the paper's
            UDT argument, §2.4; sub-BDP flows waste the pipe). Per-DC-pair
            payloads sit far below one quantum here, so the ratio collapses
            to the flow-count ratio: (dcs-1) / ((dcs-1) * nodes) =
            1/nodes_per_dc.

Also priced: the one-wire-tensor frame layout (``wan_frame_bytes`` — fused
payload rows + one count-header row per tile, ``wire_meta="min"``) against
the retired multi-collective layout (``wan_legacy_bytes`` — separate
capacity-padded data/valid/bucket/src tensors), for both paths.

Also reported: per-round WAN time (flow setup RTTs + payload over the shared
uplink, UDT vs TCP via :class:`repro.sector.transport.TransferSimulator`)
and a *measured* 8-virtual-device run checking the two paths deliver the
identical record multiset.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
from typing import Dict, List

import numpy as np

from repro.core.shuffle import ShufflePlan
from repro.sector.topology import NodeAddress, Topology
from repro.sector.transport import PAPER_LINKS, TransferSimulator

REC_BYTES = 100                 # paper terasort record: 10 B key + 90 B value
SEGMENT_RECORDS = 32768         # one §3.5.1 segment in flight per node


def zero_drop_capacities(dcs: int, nodes: int, n_local: int, seed: int = 0):
    """Worst-observed tile occupancies for one round of uniform bucket
    traffic (multinomial draw): the smallest capacities that drop nothing.

    Returns (c_flat, c_a, c_b): flat per-(src, dst-device) tile, stage-A
    per-(node, node) tile, stage-B per-(staging-node, dst-DC) tile.
    """
    rng = np.random.default_rng(seed)
    d = dcs * nodes
    counts = rng.multinomial(n_local, np.full(d, 1.0 / d), size=d)  # (src, dst)
    c_flat = int(counts.max())
    # stage A (intra-DC): tile (d1,n1)->(d1,n2) carries everything n1 holds
    # for node-row n2, any destination DC
    per_node_row = counts.reshape(d, dcs, nodes).sum(axis=1)        # (src, n2)
    c_a = int(per_node_row.max())
    # stage B: staged at (d1,n2), one tile per destination DC g
    staged = counts.reshape(dcs, nodes, dcs, nodes).sum(axis=1)     # (d1,g,n2)
    c_b = int(staged.max())
    return c_flat, c_a, c_b


def model_wan_round(
    dcs: int = 4,
    nodes: int = 30,
    n_local: int = SEGMENT_RECORDS,
    rec_bytes: int = REC_BYTES,
    wire_quantum_records: int | None = None,
    seed: int = 0,
) -> Dict[str, float]:
    """Per-device cross-DC traffic and per-round WAN time for one shuffle
    round on a ``dcs × nodes`` testbed (defaults: the paper's 4×30)."""
    if dcs < 2:
        raise ValueError("wide-area model needs >= 2 data centers "
                         "(a single-DC shuffle has no WAN traffic)")
    topo = Topology(pods=dcs, racks=1, nodes_per_rack=nodes)
    c_flat, c_a, c_b = zero_drop_capacities(dcs, nodes, n_local, seed)
    d = topo.num_nodes
    flat = ShufflePlan(num_buckets=d, axes=("wan",), shape=(d,),
                       capacities=(c_flat,))
    hier = dataclasses.replace(
        ShufflePlan.from_topology(topo, num_buckets=d, n_local=n_local),
        capacities=(c_a, c_b))

    wan = PAPER_LINKS[3]  # cross-pod: 10 GE, 30 ms one-way
    if wire_quantum_records is None:
        bdp = wan.bandwidth * 2 * wan.latency          # one RTT of the pipe
        wire_quantum_records = max(int(bdp / rec_bytes), 1)

    pf = flat.wan_profile(dcs, nodes, rec_bytes, wire_quantum_records)
    ph = hier.wan_profile(dcs, nodes, rec_bytes, wire_quantum_records)
    # fused one-tensor frame vs the retired multi-collective layout, at the
    # executor's wire_meta="min" (pure payload + per-tile count header)
    pf_min = flat.wan_profile(dcs, nodes, rec_bytes, wire_quantum_records,
                              wire_meta="min")
    ph_min = hier.wan_profile(dcs, nodes, rec_bytes, wire_quantum_records,
                              wire_meta="min")
    useful = int(n_local * (dcs - 1) / dcs * rec_bytes)  # either path

    def wan_time(profile, protocol: str) -> float:
        sim = TransferSimulator(links=PAPER_LINKS, protocol=protocol)
        bw = sim.effective_bandwidth(NodeAddress(0, 0, 0),
                                     NodeAddress(1, 0, 0)) / nodes
        setup = profile["wan_tiles"] * 2 * wan.latency   # rendezvous per flow
        return setup + profile["wan_slot_bytes"] / bw

    return {
        "dcs": dcs, "nodes": nodes, "n_local": n_local,
        "capacities": {"flat": c_flat, "stage_a": c_a, "stage_b": c_b},
        "wire_quantum_records": wire_quantum_records,
        "useful_bytes": useful,
        "flat": pf, "hier": ph,
        "flow_ratio": ph["wan_tiles"] / pf["wan_tiles"],
        "slot_ratio": ph["wan_slot_bytes"] / pf["wan_slot_bytes"],
        "wire_ratio": ph["wan_wire_bytes"] / pf["wan_wire_bytes"],
        # one-wire-tensor framing: bytes of the fused frame (wire_meta="min",
        # the dataflow executor's setting) over the retired 4/5-tensor layout
        "frame_ratio_flat": (pf_min["wan_frame_bytes"]
                             / pf["wan_legacy_bytes"]),
        "frame_ratio_hier": (ph_min["wan_frame_bytes"]
                             / ph["wan_legacy_bytes"]),
        "flat_min": pf_min, "hier_min": ph_min,
        "time_flat_udt": wan_time(pf, "udt"),
        "time_hier_udt": wan_time(ph, "udt"),
        "time_flat_tcp": wan_time(pf, "tcp"),
        "time_hier_tcp": wan_time(ph, "tcp"),
    }


_MEASURE_CODE = """
import time, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core.shuffle import ShufflePlan, sphere_shuffle
mesh1 = jax.make_mesh((8,), ("data",))
mesh2 = jax.make_mesh((2, 4), ("dc", "node"))
rng = np.random.default_rng(0)
N = 8 * 8192
data = rng.integers(0, 1 << 20, size=(N, 3)).astype(np.int32)
buckets = rng.integers(0, 16, size=N).astype(np.int32)
n_local = N // 8

flat_plan = ShufflePlan.for_mesh(mesh1, 16, n_local, 2.5, ("data",))
hier_plan = ShufflePlan.for_mesh(mesh2, 16, n_local, 2.5, ("dc", "node"))

def run_one(mesh, spec, plan):
    dd = jax.device_put(jnp.asarray(data), NamedSharding(mesh, spec))
    bd = jax.device_put(jnp.asarray(buckets), NamedSharding(mesh, spec))
    def udf(d, b):
        r = plan.shuffle(d, b.reshape(-1))
        return (r.data.reshape(-1, 3), r.valid.reshape(-1),
                r.bucket.reshape(-1), r.dropped)
    f = shard_map(udf, mesh=mesh, in_specs=(spec, spec),
                  out_specs=(spec, spec, spec, P()), check_vma=False)
    with mesh:
        out = f(dd, bd)
        jax.block_until_ready(out[0])
        t0 = time.time(); iters = 5
        for _ in range(iters):
            out = f(dd, bd)
            jax.block_until_ready(out[0])
        dt = (time.time() - t0) / iters
    return out, dt

(fd, fv, fb, fdrop), t_flat = run_one(mesh1, P("data"), flat_plan)
(hd, hv, hb, hdrop), t_hier = run_one(mesh2, P(("dc", "node")), hier_plan)
assert int(fdrop) == 0 and int(hdrop) == 0
fd, fv, fb, hd, hv, hb = map(np.asarray, (fd, fv, fb, hd, hv, hb))
flat_set = sorted(map(tuple, np.concatenate([fb[fv][:, None], fd[fv]], 1)))
hier_set = sorted(map(tuple, np.concatenate([hb[hv][:, None], hd[hv]], 1)))
assert flat_set == hier_set, "delivery multisets differ"
rb = 3 * 4
pf = flat_plan.wan_profile(2, 4, rb)
ph = hier_plan.wan_profile(2, 4, rb)
print(f"RESULT flat {t_flat * 1e6:.1f} wan_tiles={pf['wan_tiles']} "
      f"wan_slot_bytes={pf['wan_slot_bytes']}")
print(f"RESULT hier {t_hier * 1e6:.1f} wan_tiles={ph['wan_tiles']} "
      f"wan_slot_bytes={ph['wan_slot_bytes']} equivalent=yes")
"""


def measured_8dev() -> List[str]:
    env = dict(os.environ)
    # semantics on 8 virtual CPU devices: the child never needs the chip,
    # which a parent that has touched JAX may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MEASURE_CODE], env=env,
                          capture_output=True, text=True, timeout=520)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]


def run(csv: bool = True) -> List[str]:
    lines = []
    m = model_wan_round()
    mb = 1.0 / 1e6
    lines.append(
        f"wan_shuffle_model_flat,{m['time_flat_udt'] * 1e6:.0f},"
        f"flows={m['flat']['wan_tiles']} "
        f"slotMB={m['flat']['wan_slot_bytes'] * mb:.2f} "
        f"wireMB={m['flat']['wan_wire_bytes'] * mb:.1f} "
        f"usefulMB={m['useful_bytes'] * mb:.2f} "
        f"udt={m['time_flat_udt']:.2f}s tcp={m['time_flat_tcp']:.2f}s")
    lines.append(
        f"wan_shuffle_model_hier,{m['time_hier_udt'] * 1e6:.0f},"
        f"flows={m['hier']['wan_tiles']} "
        f"slotMB={m['hier']['wan_slot_bytes'] * mb:.2f} "
        f"wireMB={m['hier']['wan_wire_bytes'] * mb:.1f} "
        f"usefulMB={m['useful_bytes'] * mb:.2f} "
        f"udt={m['time_hier_udt']:.2f}s tcp={m['time_hier_tcp']:.2f}s")
    lines.append(
        f"wan_shuffle_model_ratio,0,"
        f"wire={m['wire_ratio']:.4f} slot={m['slot_ratio']:.3f} "
        f"flows={m['flow_ratio']:.4f} "
        f"target<=1/{m['nodes']}={1.0 / m['nodes']:.4f} "
        f"({m['dcs']}x{m['nodes']} testbed, segment={m['n_local']} recs)")
    lines.append(
        f"wan_shuffle_model_frame,0,"
        f"fused_vs_legacy_flat={m['frame_ratio_flat']:.3f} "
        f"fused_vs_legacy_hier={m['frame_ratio_hier']:.3f} "
        f"frameMB_hier={m['hier_min']['wan_frame_bytes'] * mb:.2f} "
        f"legacyMB_hier={m['hier']['wan_legacy_bytes'] * mb:.2f} "
        f"(one wire tensor/hop, wire_meta=min, {REC_BYTES}B records)")
    for r in measured_8dev():
        parts = r.split()
        lines.append(f"wan_shuffle_measured_{parts[1]},{parts[2]},"
                     f"{' '.join(parts[3:])}")
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
