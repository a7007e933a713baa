"""sphere_shuffle: the bucket shuffle (paper §3.2 "Shuffling input streams").

"the output can be sent to multiple locations ... a user-defined function can
specify a bucket ID (that refers to a destination file on either a local or
on a remote node) for each record in the output, and Sphere will send this
record to the specified destination."

TPU adaptation: a per-element network send does not exist; the SPMD-native
form is a **capacity-bounded all_to_all**. Buckets are assigned contiguously
to devices along a mesh axis; each device

1. frames every local record into one byte row (payload + the metadata this
   hop needs — :class:`repro.core.records.WireFrame`),
2. runs the fused O(n) partition pass
   (:func:`repro.kernels.ops.partition_pack`) over the framed rows —
   per-destination histogram + stable counting rank in ONE sweep, no sort
   anywhere on the send path,
3. exchanges exactly **one** fixed-size ``(devices, capacity+1, row_bytes)``
   uint8 tensor with ``jax.lax.all_to_all`` per hop — the Sector/UDT lesson
   (§2.3): one large framed transfer instead of several small ones. The
   historical implementation shipped four collectives per hop (``data``,
   ``valid``, ``bucket``, ``src_pos``); per-slot validity now travels as one
   int32 count per tile (real records occupy prefix slots by the partition's
   construction) and the remaining metadata rides in the same byte row as
   the payload.

With ``chunks > 1`` the local record stream splits into W chunks whose
per-chunk partition/pack interleaves with the previous chunk's
``all_to_all`` in one unrolled loop — XLA's latency-hiding scheduler can
overlap send-side compute with the exchange (the paper's overlap of SPE
processing with UDT data transfer, §2.3/§5), and peak send-buffer memory
drops by ~W×. Capacity splits across chunks (``ceil(capacity / W)`` slots
per destination per chunk), so each chunk's bins see W× the relative
traffic variance — size ``capacity_factor`` for the per-chunk clamp, not
the aggregate one, when running chunked under skew.

Capacity bounding is the paper's segment-size clamp (S_min/S_max, §3.5.1)
reborn: bounded skew in exchange for a static, compilable communication
pattern. Records beyond capacity are dropped and *counted* (``dropped``), the
same contract MoE capacity-factor dispatch uses — and indeed
:mod:`repro.models.moe` calls this exact function for expert dispatch.

Wide-area (two-level) form — paper §2.2: Sector "can manage data not only
within a data center, but also across geographically distributed data
centers". Over a 2-D ``(dc, node)`` mesh the flat all_to_all is wasteful on
the WAN: every device ships a fixed-capacity tile to each of the
``(dcs-1)*nodes`` remote devices, so each cross-DC link carries ``nodes``×
sparse tiles per destination DC. :func:`hierarchical_shuffle` instead runs

  Stage A  intra-DC all_to_all along the ``node`` axis that aggregates
           records by destination DC and pre-places them on the node-row of
           their final owner — after this, everything bound for DC ``g``
           sits densely packed on the staging nodes;
  Stage B  inter-DC all_to_all along the ``dc`` axis: one dense tile per
           remote DC per device crosses the WAN (1/nodes of the flat tile
           count);
  Stage C  fan-out to the final bucket owner inside the destination DC —
           free by construction, because stage A already staged each record
           on its owner's node-row, so arrival *is* delivery (consumers do
           the same local regroup-by-bucket they do after a flat shuffle).

Both paths share the fused partition/pack/capacity machinery
(:func:`repro.kernels.ops.partition_pack`) and are selected via
:class:`ShufflePlan`, which is built from a mesh or a
:class:`repro.sector.topology.Topology`.

Collective counts per call (``all_to_all``, at ``chunks=1``): flat shuffle
1 (was 4), hierarchical shuffle 2 (was 9), flat combine 1 (was 3),
hierarchical combine 2 (was 6). ``chunks=W`` multiplies the shuffle counts
by W, each collective carrying ~1/W of the bytes.

All shuffle functions here run **inside** ``shard_map`` and communicate via
``axis_name`` collectives.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.records import WireFrame
from repro.kernels import ops as kops

#: wire_meta modes: which per-record metadata rides in the frame rows.
#: "full"   — bucket + src (+ stage-A pos on the hierarchical path): the
#:            complete ShuffleResult contract incl. combine support.
#: "bucket" — bucket only: delivery grouping without a return trip.
#: "min"    — nothing beyond routing necessities (the hierarchical stage A
#:            still carries the bucket to route stage B): for consumers
#:            that recompute grouping from the records themselves (the
#:            dataflow executor does), the wire carries pure payload.
WIRE_META_MODES = ("full", "bucket", "min")

#: wire_meta mode -> int32 meta fields in the frame row, per hop kind. The
#: hierarchical stage B additionally carries the stage-A position so
#: :func:`hierarchical_combine` can invert the route. ``wan_profile`` prices
#: frames from these same tables, so the cost model cannot drift from the
#: bytes the hop actually ships.
_WIRE_META_FLAT = {"full": ("bucket", "src"), "bucket": ("bucket",),
                   "min": ()}
_WIRE_META_HIER = {"full": ("bucket", "src", "pos"), "bucket": ("bucket",),
                   "min": ()}


@dataclasses.dataclass
class ShuffleResult:
    """Per-device local view of a completed shuffle.

    data:    (num_src, slots, *rec) records received, grouped by source
             device (row s = records sent by source s). With ``chunks=W``,
             ``slots = W * ceil(capacity / W)`` (chunk receive tiles
             concatenated).
    valid:   (num_src, slots) bool — real record vs padding.
    bucket:  (num_src, slots) int32 global bucket id of each record, or
             ``None`` when the hop ran with ``wire_meta="min"``.
    src_pos: (num_src, slots) int32 original local row index at the source
             (needed by :func:`sphere_combine` to route results back), or
             ``None`` unless ``wire_meta="full"``.
    dropped: () int32 — records dropped across the whole axis this step
             (capacity overflow), psum'd.
    """

    data: jax.Array
    valid: jax.Array
    bucket: Optional[jax.Array]
    src_pos: Optional[jax.Array]
    dropped: jax.Array


@dataclasses.dataclass
class HierShuffleResult(ShuffleResult):
    """Result of :func:`hierarchical_shuffle`.

    The public fields keep the :class:`ShuffleResult` contract with
    ``num_src = dcs``: row g holds the records relayed through DC g's staging
    node on this device's node-row; ``src_pos`` is still the record's
    original row at its *origin* node. The private fields thread the
    two-stage route back for :func:`hierarchical_combine` (``None`` unless
    ``wire_meta="full"``).
    """

    a_valid: jax.Array = None   # (nodes, slots_a) stage-A receive validity
    a_src: jax.Array = None     # (nodes, slots_a) stage-A origin rows
    b_pos: jax.Array = None     # (dcs, slots_b) row into stage-A recv layout


def _a2a(x: jax.Array, axis_name: str) -> jax.Array:
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


#: host-side hop-geometry sink. Shuffle hops run inside traced code, so a
#: per-run byte counter is impossible without shipping extra scalars; the
#: geometry, however, is static. Wrapping *lowering* in :func:`record_hops`
#: captures, exactly once per compile, every hop the program will execute
#: (wire bytes per device, chunk rounds, destinations) — the SPMD executor
#: stores the list with the compiled program and replays it per run.
_HOP_SINK: Optional[List[dict]] = None


@contextlib.contextmanager
def record_hops(sink: List[dict]):
    """Collect one dict per shuffle hop traced inside the ``with`` block."""
    global _HOP_SINK
    prev = _HOP_SINK
    _HOP_SINK = sink
    try:
        yield sink
    finally:
        _HOP_SINK = prev


def _wire_exchange(
    frame: WireFrame,
    payload: jax.Array,
    meta: Dict[str, jax.Array],
    dest: jax.Array,
    num_dest: int,
    capacity: int,
    chunks: int,
    axis_name: str,
    use_pallas: bool,
):
    """One shuffle hop: frame -> chunked partition/pack -> ONE all_to_all
    per chunk -> open. Returns (payload, valid, metas, dropped_local) with
    receive leading shape ``(num_dest, chunks * ceil(capacity / chunks))``.

    The chunk loop is intentionally unrolled (no ``lax.scan``): chunk k+1's
    partition/pack has no data dependency on chunk k's ``all_to_all``, so
    the XLA latency-hiding scheduler can overlap them.
    """
    framed = frame.frame_rows(payload, **meta)
    n = framed.shape[0]
    w = max(int(chunks), 1)
    cap_c = -(-capacity // w)
    if _HOP_SINK is not None:
        _HOP_SINK.append({
            "axis": axis_name, "num_dest": num_dest, "capacity": capacity,
            "chunks": w, "row_nbytes": frame.row_nbytes,
            "tile_nbytes": frame.tile_nbytes(cap_c),
            "wire_bytes_per_device": w * num_dest * frame.tile_nbytes(cap_c),
            "meta": list(frame.meta),
        })
    nc = -(-n // w) if n else 0
    if w * nc != n:  # pad the stream so chunks are equal-shaped; padding
        pad = w * nc - n  # rows route to the virtual overflow destination
        framed = jnp.concatenate(
            [framed, jnp.zeros((pad, frame.row_nbytes), jnp.uint8)])
        dest = jnp.concatenate(
            [dest, jnp.full((pad,), num_dest, jnp.int32)])
    parts = []
    dropped = jnp.zeros((), jnp.int32)
    for k in range(w):
        rows = jax.lax.slice_in_dim(framed, k * nc, (k + 1) * nc, axis=0)
        dk = jax.lax.slice_in_dim(dest, k * nc, (k + 1) * nc, axis=0)
        (tile,), in_rng, _, drop_k = kops.partition_pack(
            [rows], dk, num_dest, cap_c, use_pallas=use_pallas)
        # empty slots hold a duplicated row-0 gather — zero them so the wire
        # is deterministic and no local bytes leak across devices
        tile = tile * in_rng[..., None].astype(jnp.uint8)
        counts = jnp.sum(in_rng, axis=1, dtype=jnp.int32)
        parts.append(frame.open(_a2a(frame.seal(tile, counts), axis_name)))
        dropped = dropped + drop_k
    if w == 1:
        pay, val, metas = parts[0]
    else:
        pay = jnp.concatenate([p[0] for p in parts], axis=1)
        val = jnp.concatenate([p[1] for p in parts], axis=1)
        metas = {name: jnp.concatenate([p[2][name] for p in parts], axis=1)
                 for name in frame.meta}
    return pay, val, metas, dropped


def _masked(metas: Dict[str, jax.Array], name: str,
            valid: jax.Array) -> Optional[jax.Array]:
    if name not in metas:
        return None
    return jnp.where(valid, metas[name], -1)


def sphere_shuffle(
    data: jax.Array,
    bucket_ids: jax.Array,
    num_buckets: int,
    capacity: int,
    axis_name: str,
    valid: Optional[jax.Array] = None,
    use_pallas: bool = False,
    chunks: int = 1,
    wire_meta: str = "full",
) -> ShuffleResult:
    """Send each local record to the device owning its bucket (flat path).

    Must be called inside ``shard_map``. ``num_buckets`` must be a multiple of
    the axis size; bucket b lives on device ``b // (num_buckets // D)``.

    Args:
      data: (n, *rec) local records.
      bucket_ids: (n,) int32 in [0, num_buckets); records with out-of-range
        ids (e.g. -1 for padding) are not sent.
      capacity: max records any source sends to any one destination
        (split ~evenly across ``chunks``).
      valid: optional (n,) bool marking real input records.
      use_pallas: compute the per-destination partition with the Pallas
        kernel instead of its jnp oracle.
      chunks: pipeline depth W — the hop runs as W interleaved
        pack/exchange rounds of capacity ``ceil(capacity / W)`` each.
      wire_meta: which metadata to ship per record (see WIRE_META_MODES).
    """
    axis_size = jax.lax.axis_size(axis_name)
    if num_buckets % axis_size != 0:
        raise ValueError(f"num_buckets={num_buckets} not divisible by "
                         f"axis size {axis_size}")
    if wire_meta not in WIRE_META_MODES:
        raise ValueError(f"wire_meta={wire_meta!r} not in {WIRE_META_MODES}")
    bpd = num_buckets // axis_size

    ids = bucket_ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < num_buckets)
    if valid is not None:
        ok = ok & valid
    # invalid records get dest = axis_size (a virtual overflow destination)
    dest = jnp.where(ok, ids // bpd, axis_size)

    names = _WIRE_META_FLAT[wire_meta]
    frame = WireFrame.for_payload(data, meta=names)
    meta = {}
    if "bucket" in names:
        meta["bucket"] = ids
    if "src" in names:
        meta["src"] = jnp.arange(data.shape[0], dtype=jnp.int32)
    pay, val, metas, drop_local = _wire_exchange(
        frame, data, meta, dest, axis_size, capacity, chunks, axis_name,
        use_pallas)
    return ShuffleResult(
        data=pay, valid=val,
        bucket=_masked(metas, "bucket", val),
        src_pos=_masked(metas, "src", val),
        dropped=jax.lax.psum(drop_local, axis_name),
    )


def hierarchical_shuffle(
    data: jax.Array,
    bucket_ids: jax.Array,
    num_buckets: int,
    capacity_a: int,
    capacity_b: int,
    dc_axis: str,
    node_axis: str,
    valid: Optional[jax.Array] = None,
    use_pallas: bool = False,
    chunks: int = 1,
    wire_meta: str = "full",
) -> HierShuffleResult:
    """Two-level wide-area shuffle over a ``(dc, node)`` mesh (see module
    docstring). Must be called inside ``shard_map`` over both axes.

    Bucket ownership matches the flat layout on the row-major flattened
    device order: bucket b lives on global device ``b // bpd`` =
    ``(dc, node) = (b // bpd // nodes, b // bpd % nodes)``.

    Args:
      capacity_a: stage-A tile size — max records one node sends to one
        sibling node inside its DC (≈ n_local / nodes × capacity_factor).
      capacity_b: stage-B (WAN) tile size — max staged records one node
        sends to one remote DC (≈ n_local / dcs × capacity_factor).
      chunks / wire_meta: as for :func:`sphere_shuffle` (both stages chunk;
        stage A always carries the bucket — stage B routes by it).
    """
    dcs = jax.lax.axis_size(dc_axis)
    nodes = jax.lax.axis_size(node_axis)
    num_devices = dcs * nodes
    if num_buckets % num_devices != 0:
        raise ValueError(f"num_buckets={num_buckets} not divisible by "
                         f"mesh size {dcs}x{nodes}")
    if wire_meta not in WIRE_META_MODES:
        raise ValueError(f"wire_meta={wire_meta!r} not in {WIRE_META_MODES}")
    bpd = num_buckets // num_devices

    ids = bucket_ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < num_buckets)
    if valid is not None:
        ok = ok & valid
    owner = jnp.where(ok, ids // bpd, 0)

    # Stage A: intra-DC exchange, keyed by the owner's node-row. This both
    # aggregates by destination DC (all records for DC g end up contiguous on
    # the staging nodes) and pre-places records so stage C is a no-op. The
    # bucket always rides along — stage B routes by it.
    names_b = _WIRE_META_HIER[wire_meta]
    names_a = ("bucket",) + (("src",) if "src" in names_b else ())
    frame_a = WireFrame.for_payload(data, meta=names_a)
    meta_a = {"bucket": ids}
    if "src" in names_a:
        meta_a["src"] = jnp.arange(data.shape[0], dtype=jnp.int32)
    dest_a = jnp.where(ok, owner % nodes, nodes)
    pay_a, val_a, metas_a, drop_a = _wire_exchange(
        frame_a, data, meta_a, dest_a, nodes, capacity_a, chunks, node_axis,
        use_pallas)

    # Stage B: inter-DC exchange along the dc axis — the only WAN traffic.
    # One dense (slots_b, row_bytes) tile per remote DC per device.
    n_staged = val_a.size
    f_pay = pay_a.reshape((n_staged,) + data.shape[1:])
    f_valid = val_a.reshape(-1)
    f_bucket = metas_a["bucket"].reshape(-1)
    owner_b = jnp.where(f_valid, f_bucket, 0) // bpd
    dest_b = jnp.where(f_valid, owner_b // nodes, dcs)
    frame_b = WireFrame.for_payload(data, meta=names_b)
    meta_b = {}
    if "bucket" in names_b:
        meta_b["bucket"] = f_bucket
    if "src" in names_b:
        meta_b["src"] = metas_a["src"].reshape(-1)
    if "pos" in names_b:
        meta_b["pos"] = jnp.arange(n_staged, dtype=jnp.int32)
    pay_b, val_b, metas_b, drop_b = _wire_exchange(
        frame_b, f_pay, meta_b, dest_b, dcs, capacity_b, chunks, dc_axis,
        use_pallas)

    # Stage C (fan-out inside the destination DC) is free: stage A staged
    # every record on its final owner's node-row, so stage B delivered it.
    dropped = jax.lax.psum(jax.lax.psum(drop_a + drop_b, dc_axis), node_axis)
    return HierShuffleResult(
        data=pay_b, valid=val_b,
        bucket=_masked(metas_b, "bucket", val_b),
        src_pos=_masked(metas_b, "src", val_b),
        dropped=dropped,
        a_valid=val_a, a_src=_masked(metas_a, "src", val_a),
        b_pos=_masked(metas_b, "pos", val_b),
    )


def sphere_combine(
    processed: jax.Array,
    shuffle: ShuffleResult,
    axis_name: str,
    num_local_out: int,
) -> Tuple[jax.Array, jax.Array]:
    """Route per-record results back to their source devices and original rows
    (the inverse shuffle) — ONE all_to_all: results, validity, and return
    rows travel in one explicit-valid wire frame (return-tile valid slots
    are not a prefix after drops, so validity is a per-row byte here).
    ``processed`` must be (num_src, slots, *out) aligned with
    ``shuffle.data``, and the shuffle must have run with
    ``wire_meta="full"``. Results for the same source row are summed (this
    is exactly the MoE top-k combine contract).

    Returns (combined (num_local_out, *out), hit_count (num_local_out,)).
    """
    if shuffle.src_pos is None:
        raise ValueError("combine needs a shuffle run with wire_meta='full' "
                         "(src_pos was not shipped)")
    num_src, cap = processed.shape[:2]
    out_tail = processed.shape[2:]
    flat_p = processed.reshape((num_src * cap,) + out_tail)
    frame = WireFrame.for_payload(flat_p, meta=("src",), explicit_valid=True)
    rows = frame.frame_rows(flat_p, valid=shuffle.valid.reshape(-1),
                            src=shuffle.src_pos.reshape(-1))
    back = _a2a(rows.reshape(num_src, cap, frame.row_nbytes), axis_name)
    pay, bvalid, metas = frame.open_rows(back)

    flat = pay.reshape((-1,) + out_tail)
    fvalid = bvalid.reshape(-1)
    fsrc = jnp.where(fvalid, metas["src"].reshape(-1), num_local_out)  # OOB
    out_shape = (num_local_out,) + out_tail
    zeros = jnp.zeros(out_shape, dtype=processed.dtype)
    masked = flat * fvalid.reshape((-1,) + (1,) * (flat.ndim - 1)).astype(flat.dtype)
    combined = zeros.at[fsrc].add(masked, mode="drop")
    hits = jnp.zeros((num_local_out,), jnp.int32).at[fsrc].add(
        fvalid.astype(jnp.int32), mode="drop")
    return combined, hits


def hierarchical_combine(
    processed: jax.Array,
    shuffle: HierShuffleResult,
    dc_axis: str,
    node_axis: str,
    num_local_out: int,
) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`hierarchical_shuffle`: results ride the WAN back to
    their staging node (reverse stage B, ONE all_to_all), are scattered into
    the stage-A receive layout, then :func:`sphere_combine` reverses stage A
    back to the origin rows (one more). ``processed`` must be
    (dcs, slots_b, *out) aligned with ``shuffle.data``."""
    if shuffle.b_pos is None:
        raise ValueError("combine needs a shuffle run with wire_meta='full' "
                         "(b_pos was not shipped)")
    num_src, cap = processed.shape[:2]
    out_tail = processed.shape[2:]
    flat_p = processed.reshape((num_src * cap,) + out_tail)
    frame = WireFrame.for_payload(flat_p, meta=("pos",), explicit_valid=True)
    rows = frame.frame_rows(flat_p, valid=shuffle.valid.reshape(-1),
                            pos=shuffle.b_pos.reshape(-1))
    back = _a2a(rows.reshape(num_src, cap, frame.row_nbytes), dc_axis)
    pay, bvalid, metas = frame.open_rows(back)

    flat = pay.reshape((-1,) + out_tail)
    fvalid = bvalid.reshape(-1)
    n_staged = shuffle.a_valid.size
    fpos = jnp.where(fvalid, metas["pos"].reshape(-1), n_staged)   # OOB drop
    masked = flat * fvalid.reshape((-1,) + (1,) * (flat.ndim - 1)).astype(flat.dtype)
    buf = jnp.zeros((n_staged + 1,) + out_tail, processed.dtype)
    buf = buf.at[fpos].add(masked, mode="drop")[:n_staged]
    buf = buf.reshape(shuffle.a_valid.shape + out_tail)
    # records that survived stage A but were dropped at stage B got no result
    # back — mask them out so hit_count keeps the flat-path contract
    # (hits == 0 for undelivered records)
    delivered = jnp.zeros((n_staged + 1,), bool).at[fpos].set(
        True, mode="drop")[:n_staged]
    a_valid = shuffle.a_valid & delivered.reshape(shuffle.a_valid.shape)
    synth = ShuffleResult(data=buf, valid=a_valid, bucket=None,
                          src_pos=shuffle.a_src,
                          dropped=jnp.zeros((), jnp.int32))
    return sphere_combine(buf, synth, node_axis, num_local_out)


# -- topology-parameterized plan ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShufflePlan:
    """A compiled-shape shuffle strategy: which mesh axes to exchange over,
    with what per-tile capacities. One axis → flat all_to_all; two axes
    (dc, node) → the two-level hierarchical path. ``chunks`` sets the
    pipeline depth W of every hop (see :func:`sphere_shuffle`).

    Built host-side (shapes must be static), used inside ``shard_map``.
    """

    num_buckets: int
    axes: Tuple[str, ...]        # ("data",) flat, or (dc_axis, node_axis)
    shape: Tuple[int, ...]       # mesh extent of each axis
    capacities: Tuple[int, ...]  # (capacity,) or (capacity_a, capacity_b)
    use_pallas: bool = False
    chunks: int = 1

    def __post_init__(self):
        if len(self.axes) not in (1, 2) or len(self.axes) != len(self.shape):
            raise ValueError(f"bad plan axes={self.axes} shape={self.shape}")
        if len(self.capacities) != len(self.axes):
            raise ValueError("need one capacity per shuffle stage")
        if self.num_buckets % self.num_devices != 0:
            raise ValueError(f"num_buckets={self.num_buckets} not divisible "
                             f"by {self.num_devices} devices")
        if self.chunks < 1:
            raise ValueError(f"chunks={self.chunks} must be >= 1")

    # -- static geometry ----------------------------------------------------
    @property
    def hierarchical(self) -> bool:
        return len(self.axes) == 2

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @property
    def buckets_per_device(self) -> int:
        return self.num_buckets // self.num_devices

    def stage_slots(self, stage: int) -> int:
        """Receive slots per source for shuffle stage ``stage``:
        ``chunks * ceil(capacity / chunks)``."""
        cap = self.capacities[stage]
        return self.chunks * (-(-cap // self.chunks))

    @property
    def recv_slots(self) -> int:
        """Rows of the local receive buffer (= num_src * slots of the
        delivering stage)."""
        if self.hierarchical:
            return self.shape[0] * self.stage_slots(1)
        return self.shape[0] * self.stage_slots(0)

    # -- constructors -------------------------------------------------------
    @classmethod
    def for_mesh(cls, mesh, num_buckets: int, n_local: int,
                 capacity_factor: float = 2.0,
                 axes: Sequence[str] = ("data",),
                 use_pallas: bool = False,
                 chunks: int = 1) -> "ShufflePlan":
        """Capacities sized for ``n_local`` records/device at uniform load,
        padded by ``capacity_factor`` (the §3.5.1 segment clamp)."""
        axes = tuple(axes)
        shape = tuple(mesh.shape[a] for a in axes)
        if len(axes) == 1:
            caps = (int(n_local / shape[0] * capacity_factor) + 1,)
        else:
            dcs, nodes = shape
            caps = (int(n_local / nodes * capacity_factor) + 1,
                    int(n_local / dcs * capacity_factor) + 1)
        return cls(num_buckets, axes, shape, caps, use_pallas, chunks)

    @classmethod
    def from_topology(cls, topo, num_buckets: int, n_local: int,
                      capacity_factor: float = 2.0,
                      dc_axis: str = "dc", node_axis: str = "node",
                      use_pallas: bool = False,
                      chunks: int = 1) -> "ShufflePlan":
        """Map a :class:`repro.sector.topology.Topology` onto a plan: pods
        become the WAN axis, racks × nodes_per_rack the intra-DC axis. A
        single-pod topology degenerates to the flat path."""
        nodes = topo.racks * topo.nodes_per_rack
        if topo.pods == 1:
            caps = (int(n_local / nodes * capacity_factor) + 1,)
            return cls(num_buckets, (node_axis,), (nodes,), caps, use_pallas,
                       chunks)
        caps = (int(n_local / nodes * capacity_factor) + 1,
                int(n_local / topo.pods * capacity_factor) + 1)
        return cls(num_buckets, (dc_axis, node_axis), (topo.pods, nodes),
                   caps, use_pallas, chunks)

    # -- shard_map-side ops -------------------------------------------------
    def device_index(self) -> jax.Array:
        """Global device index in bucket-ownership order (inside shard_map)."""
        if self.hierarchical:
            return (jax.lax.axis_index(self.axes[0]) * self.shape[1]
                    + jax.lax.axis_index(self.axes[1]))
        return jax.lax.axis_index(self.axes[0])

    def pmean_axes(self) -> Tuple[str, ...]:
        return self.axes

    def shuffle(self, data: jax.Array, bucket_ids: jax.Array,
                valid: Optional[jax.Array] = None,
                wire_meta: str = "full") -> ShuffleResult:
        if self.hierarchical:
            return hierarchical_shuffle(
                data, bucket_ids, self.num_buckets,
                self.capacities[0], self.capacities[1],
                self.axes[0], self.axes[1], valid=valid,
                use_pallas=self.use_pallas, chunks=self.chunks,
                wire_meta=wire_meta)
        return sphere_shuffle(data, bucket_ids, self.num_buckets,
                              self.capacities[0], self.axes[0], valid=valid,
                              use_pallas=self.use_pallas, chunks=self.chunks,
                              wire_meta=wire_meta)

    def combine(self, processed: jax.Array, result: ShuffleResult,
                num_local_out: int) -> Tuple[jax.Array, jax.Array]:
        if self.hierarchical:
            return hierarchical_combine(processed, result, self.axes[0],
                                        self.axes[1], num_local_out)
        return sphere_combine(processed, result, self.axes[0], num_local_out)

    # -- WAN cost model (host-side, used by benchmarks/wan_shuffle.py) ------
    def wan_profile(self, dcs: int, nodes: int, rec_bytes: int,
                    wire_segment_records: Optional[int] = None,
                    wire_meta: str = "full") -> dict:
        """Per-device, per-round cross-DC traffic of this plan mapped onto a
        ``dcs × nodes`` wide-area layout (flat plans flatten it row-major).

        wan_tiles: fixed-capacity tiles shipped across a DC boundary —
          flat: one per remote *device*; hierarchical: one per remote *DC*.
        wan_rounds: chunked exchange rounds (= ``chunks``); each WAN tile is
          shipped once per round at 1/rounds capacity.
        wan_slot_bytes: payload bytes the all_to_all ships over the WAN
          (tiles × capacity slots × rec_bytes, full even when half-empty).
        wan_frame_bytes: bytes of the fused one-tensor wire layout actually
          shipped — framed rows (payload + the ``wire_meta`` metadata ints)
          plus one count-header row per tile per round.
        wan_legacy_bytes: the retired multi-collective layout — separate
          capacity-padded data/valid/bucket/src((+pos)) tensors per hop.
        wan_wire_bytes: with transfers quantized to ``wire_segment_records``
          (the §3.5.1 S_min clamp — UDT needs big transfers to fill a long
          fat pipe), each tile's payload rounds up to whole wire segments.
        """
        if self.num_devices != dcs * nodes:
            raise ValueError(f"plan covers {self.num_devices} devices, "
                             f"topology has {dcs * nodes}")
        if wire_meta not in WIRE_META_MODES:
            raise ValueError(f"wire_meta={wire_meta!r} not in "
                             f"{WIRE_META_MODES}")
        if self.hierarchical:
            tiles, cap = dcs - 1, self.capacities[1]
            meta = _WIRE_META_HIER[wire_meta]
            legacy_tensors = rec_bytes + 1 + 4 + 4 + 4  # +valid,bucket,src,pos
        else:
            tiles, cap = (dcs - 1) * nodes, self.capacities[0]
            meta = _WIRE_META_FLAT[wire_meta]
            legacy_tensors = rec_bytes + 1 + 4 + 4      # +valid,bucket,src
        # the exact frame the WAN hop ships (rec_bytes-wide payload rows)
        frame = WireFrame("uint8", (rec_bytes,), meta=meta)
        w = self.chunks
        cap_c = -(-cap // w)
        frame_rows = cap_c + 1                          # + count header row
        out = {
            "wan_tiles": tiles,
            "wan_rounds": w,
            "wan_slot_bytes": tiles * cap * rec_bytes,
            "wan_frame_bytes": tiles * w * frame.tile_nbytes(cap_c),
            "wan_legacy_bytes": tiles * cap * legacy_tensors,
        }
        if wire_segment_records:
            q = wire_segment_records
            out["wan_wire_bytes"] = tiles * (-(-cap // q) * q) * rec_bytes
            out["wan_frame_wire_bytes"] = (
                tiles * w * (-(-frame_rows // q) * q) * frame.row_nbytes)
        return out
