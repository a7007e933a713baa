"""Unified Sphere dataflow: one pipeline description, two executors.

The paper's whole pitch is a *single* simple client API (§3.1):

    SphereStream sdss;  sdss.init(<slices>);
    SphereProcess myproc;  myproc.run(sdss, "myFunc");

This module is that API for the repo. A :class:`Dataflow` is a declarative,
executor-independent chain of stages over *records* (any fixed-shape pytree
of arrays sharing a leading record axis, see
:class:`repro.core.records.RecordCodec`):

    df = (Dataflow.source(codec)
          .map(extract)                      # record-wise UDF
          .shuffle(by=hash_fn, num_buckets=B)  # paper §3.2 bucket shuffle
          .reduce(aggregate))                # per-bucket-group UDF
    # or:  Dataflow.source().sort(key=..., splitters=...)   # paper §4.2

The same pipeline object runs on two executors with identical results:

- :class:`SPMDExecutor` fuses every stage into ONE ``jit(shard_map(...))``
  program: maps/reduces inline per device, shuffles become capacity-bounded
  ``all_to_all`` via :class:`repro.core.shuffle.ShufflePlan` (flat or
  two-level wide-area, all sends through the fused O(n) partition/pack),
  sort stage 2 regroups bucket-major and runs one autotuned multi-segment
  sort (Pallas bitonic or radix kernel, or XLA's sort). Compiled programs
  are cached keyed on (pipeline, plan, input shapes/dtypes).
- :class:`HostExecutor` lowers the same graph onto
  :class:`repro.sphere.engine.SphereProcess` / SPEs over Sector-stored
  files: maps run at the SPEs with locality scheduling and retry, shuffle
  stages materialize **bucket files** back into Sector (the paper's bucket
  handlers), and post-shuffle stages run as the next Sphere stage over
  those buckets.

UDF contracts (shared by both executors — write them once with
``jax.numpy``; on the host path numpy arrays go in and the outputs are
converted back):

- ``map(fn)``: ``fn(records) -> records``. Record-wise / vectorized. On the
  SPMD path padding rows may be present, so the function must be
  padding-oblivious (pure row-wise transforms are). If the leading dimension
  changes (static re-emission), validity resets to all-true; encode
  "emit nothing" by keying the following ``shuffle`` with a negative bucket.
- ``shuffle(by, ...)``: ``by(records) -> (n,) int`` bucket ids; negative or
  out-of-range ids mean "emit nothing".
- ``reduce(fn)``: ``fn(records, valid) -> (records, valid)`` or
  ``(records, valid, dropped)`` — a whole-group UDF (the paper's "the SPE
  processes the whole data segment"). The group is one device's received
  records (SPMD) or one bucket file (host); per-key aggregations see every
  record of a key either way, because the shuffle co-located them.
- ``sort(key, splitters, ...)``: range-partition by ``key`` then sort each
  partition locally — the two-stage terasort of §4.2.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import os
import time
from collections import OrderedDict, namedtuple
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.introspect import collective_counts, module_name, op_layers
from repro.core.records import RecordCodec
from repro.core.sort import (is_byte_key, key_layout, key_words,
                             uniform_bucket, uniform_splitters,
                             word_bucket_key)
from repro.core.shuffle import ShufflePlan, record_hops
from repro.kernels import autotune
from repro.kernels import ops as kops
from repro.obs import layers as obs_layers
from repro.kernels.radix_sort import sortable_bits_to_key
from repro.obs.layers import BUCKET, KEY, MOVE, EXCHANGE, SEGSORT, UDF
from repro.obs.metrics import REGISTRY
from repro.obs.trace import NULL_TRACER


# -- pipeline description ----------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class MapStage:
    fn: Callable


@dataclasses.dataclass(frozen=True, eq=False)
class ShuffleStage:
    by: Callable
    num_buckets: Optional[int] = None
    capacity_factor: float = 4.0
    chunks: Optional[int] = None          # None -> executor default


@dataclasses.dataclass(frozen=True, eq=False)
class ReduceStage:
    fn: Callable


@dataclasses.dataclass(frozen=True, eq=False)
class SortStage:
    key: Callable                         # -> (n,) int32, or (n, k) uint8 bytes
    splitters: Optional[Any] = None       # (num_buckets - 1,) int32 thresholds
    num_buckets: Optional[int] = None
    capacity_factor: float = 2.0
    chunks: Optional[int] = None          # None -> executor default


@dataclasses.dataclass(frozen=True, eq=False)
class Dataflow:
    """An immutable, chainable pipeline of stages (see module docstring).

    ``codec`` is the *source* record schema. Executors that read raw bytes
    (the host executor over Sector files) require it; the SPMD executor
    infers schemas from the arrays it is handed, so it is optional there.
    """

    stages: Tuple[Any, ...] = ()
    codec: Optional[RecordCodec] = None
    #: declared as a *streaming* source (``stream_source``): the stage graph
    #: is meant to run continuously over micro-batches via
    #: :class:`repro.sphere.streaming.StreamExecutor`. Batch executors run
    #: it unchanged (one micro-batch == one batch).
    stream: bool = False

    @classmethod
    def source(cls, codec: Optional[RecordCodec] = None) -> "Dataflow":
        return cls(stages=(), codec=codec)

    @classmethod
    def stream_source(cls, codec: Optional[RecordCodec] = None) -> "Dataflow":
        """A continuous micro-batch source (paper §3.2: "Sphere takes
        streams as inputs and produces streams as outputs"). The same stage
        verbs apply; :class:`repro.sphere.streaming.StreamExecutor` runs the
        graph over an unbounded sequence of fixed-shape micro-batches,
        compiled once."""
        return cls(stages=(), codec=codec, stream=True)

    def _with(self, stage) -> "Dataflow":
        return Dataflow(stages=self.stages + (stage,), codec=self.codec,
                        stream=self.stream)

    def map(self, fn: Callable) -> "Dataflow":
        return self._with(MapStage(fn))

    def shuffle(self, by: Callable, num_buckets: Optional[int] = None,
                capacity_factor: float = 4.0,
                chunks: Optional[int] = None) -> "Dataflow":
        return self._with(ShuffleStage(by, num_buckets, capacity_factor,
                                       chunks))

    def reduce(self, fn: Callable) -> "Dataflow":
        return self._with(ReduceStage(fn))

    def sort(self, key: Callable, splitters: Optional[Any] = None,
             num_buckets: Optional[int] = None,
             capacity_factor: float = 2.0,
             chunks: Optional[int] = None) -> "Dataflow":
        return self._with(SortStage(key, splitters, num_buckets,
                                    capacity_factor, chunks))

    def describe(self) -> str:
        parts = ["stream-source" if self.stream else "source"]
        for st in self.stages:
            if isinstance(st, MapStage):
                parts.append(f"map[{getattr(st.fn, '__name__', '<fn>')}]")
            elif isinstance(st, ShuffleStage):
                parts.append(f"shuffle[{st.num_buckets or 'auto'}]")
            elif isinstance(st, ReduceStage):
                parts.append(f"reduce[{getattr(st.fn, '__name__', '<fn>')}]")
            elif isinstance(st, SortStage):
                parts.append(f"sort[{st.num_buckets or 'auto'}]")
        return " |> ".join(parts)

    def run(self, executor: Any, data: Any, **kwargs: Any) -> "DataflowResult":
        """The paper's §3.1 client call, executor-polymorphic:
        ``df.run(spmd_executor, records, trace=tracer)`` or
        ``df.run(host_executor, sector_paths)``. All keyword arguments
        (``trace=``, ``chaos=``, ``valid=``, ...) pass through to the
        executor's ``run``; the result's ``trace`` handle carries the
        tracer back (``result.trace.to_perfetto("trace.json")``)."""
        return executor.run(self, data, **kwargs)

    def run_stream(self, inner: "SPMDExecutor", micro_batch: int,
                   **kwargs: Any) -> Any:
        """Wrap this ``stream_source`` pipeline in a
        :class:`repro.sphere.streaming.StreamExecutor` (accepts
        ``carry_capacity=``, ``queue=``, ``clock=``, ``trace=``)."""
        from repro.sphere.streaming import StreamExecutor
        return StreamExecutor(inner, self, micro_batch, **kwargs)


@dataclasses.dataclass
class DataflowResult:
    """Executor-independent result.

    records: output pytree. SPMD: padded, globally sharded arrays — mask
             with ``valid``. Host: dense numpy arrays, ``valid`` all-true.
    dropped: records lost to capacity bounds (SPMD shuffles) plus drops
             reported by reduce UDFs, summed over the whole run.
    errors/retries: host-executor fault accounting (empty/0 on SPMD).
    """

    records: Any
    valid: Any
    dropped: Any
    errors: Dict[Any, str] = dataclasses.field(default_factory=dict)
    retries: int = 0
    #: mid-job recoveries fault tolerance performed: Sector re-replications
    #: of lost bucket files (host) or hop-checkpoint resumes (SPMD)
    recoveries: int = 0
    #: segments that permanently failed and are MISSING from ``records``
    #: (every one also appears in ``errors`` with a ``DATA_ERROR:`` prefix)
    data_errors: int = 0
    #: streaming only: the ``(records, valid)`` cross-batch carry state the
    #: run produced (None on one-shot runs) — feed it back as the next
    #: micro-batch's ``carry``. See :mod:`repro.sphere.streaming`.
    carry: Optional[Tuple[Any, Any]] = None
    #: the tracer this run recorded into (None when untraced) — call
    #: ``result.trace.to_perfetto("trace.json")`` / ``result.trace.flame()``.
    trace: Optional[Any] = None
    #: host executor: one dict per phase with wall-clock accounting
    #: (``seconds``, ``engine_s``, ``materialize_s``, segments, retries,
    #: recoveries) — populated even without a tracer, so
    #: ``benchmarks/make_report.py`` can print a phase table.
    phase_times: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)

    def valid_records(self) -> Any:
        """Dense numpy view: only real records, in device/bucket order."""
        v = np.asarray(self.valid)
        return jax.tree.map(lambda a: np.asarray(a)[v], self.records)


def _split_reduce_out(out):
    if not isinstance(out, tuple) or len(out) not in (2, 3):
        raise ValueError("reduce UDF must return (records, valid) or "
                         "(records, valid, dropped)")
    records, valid = out[0], out[1]
    dropped = out[2] if len(out) == 3 else None
    return records, valid, dropped


def _leading(records) -> int:
    return jax.tree.leaves(records)[0].shape[0]


def _compact_carry(records, valid, cap: int):
    """Compress ``records[valid]`` into a fixed ``cap``-row carry buffer.

    Valid rows move (stably) to the prefix; rows past ``cap`` are dropped and
    counted — the carry is *bounded* state, the same §3.5.1 capacity contract
    as the shuffle. Returns ``(carry_records, carry_valid, dropped)``."""
    valid = valid.reshape(-1)
    n = valid.shape[0]
    if n < cap:
        records = jax.tree.map(
            lambda a: jnp.pad(a, ((0, cap - n),) + ((0, 0),) * (a.ndim - 1)),
            records)
        valid = jnp.pad(valid, (0, cap - n))
    order = jnp.argsort(jnp.logical_not(valid), stable=True)
    top = order[:cap].astype(jnp.int32)
    carry = jax.tree.map(lambda a: jnp.take(a, top, axis=0), records)
    nvalid = jnp.sum(valid.astype(jnp.int32))
    cvalid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(nvalid, cap)
    dropped = jnp.maximum(nvalid - cap, 0)
    return carry, cvalid, dropped


def _last_reduce_index(df: Dataflow) -> int:
    idx = [i for i, s in enumerate(df.stages) if isinstance(s, ReduceStage)]
    if not idx:
        raise ValueError(
            "cross-batch carry state needs a reduce stage to merge into — "
            f"pipeline is {df.describe()}")
    return idx[-1]


#: ``SPMDExecutor.cache_info()`` result, ``functools.lru_cache`` style plus
#: an eviction counter: steady-state streaming asserts ``misses`` stops
#: growing after warm-up (zero recompiles per micro-batch).
CacheInfo = namedtuple("CacheInfo",
                       ["hits", "misses", "evictions", "currsize", "maxsize"])


class SortCell(NamedTuple):
    """Stage-2 geometry of a sort stage (see :meth:`SPMDExecutor.sort_cell`)."""
    recv: int                 # receive-buffer slots per device
    buckets_per_device: int   # segments per device
    segment: int              # slots per segment


def _segment_capacity(recv: int, bpd: int, capacity_factor: float) -> int:
    """Slots per stage-2 segment: ``capacity_factor`` headroom over the
    uniform share of the receive buffer (the whole buffer for one bucket)."""
    return recv if bpd == 1 else min(recv, int(recv / bpd * capacity_factor)
                                     + 1)


class _CacheEntry(NamedTuple):
    """One compiled program. ``fn`` is the AOT-compiled executable
    (``jit(...).lower(args).compile()`` — compile cost is paid exactly once
    per entry, separable from execute time under a tracer). ``hops`` is the
    static shuffle-hop geometry captured at lowering time via
    :func:`repro.core.shuffle.record_hops`; ``collectives`` the jaxpr
    collective counts (only computed when the entry was built under an
    active tracer — the extra trace is not free). ``module`` is the
    executable's HLO module name and ``layers`` its op->layer map
    (:func:`repro.core.introspect.op_layers`), read once per compile.
    ``bucketing`` names the range-bucketing path of each sort stage, in
    stage order (:func:`bucketing`), and ``sort_keys`` the key layout each
    sorts (:func:`repro.core.sort.key_layout`)."""

    pipeline: "Dataflow"
    fn: Callable
    has_sort: bool
    hops: List[dict]
    collectives: Optional[Dict[str, int]]
    module: str
    layers: Dict[str, str]
    bucketing: Tuple[str, ...]
    sort_keys: Tuple[str, ...]


def bucketing(stage: SortStage) -> str:
    """How a sort stage buckets its keys: ``"arithmetic"`` (one division,
    :func:`repro.core.sort.uniform_bucket`) with the default even splitters,
    ``"search"`` (``searchsorted``) against splitters the stage was given."""
    return "arithmetic" if stage.splitters is None else "search"


_STAGE_KIND = {MapStage: "map", ShuffleStage: "shuffle",
               ReduceStage: "reduce", SortStage: "sort"}


# -- SPMD executor -----------------------------------------------------------


class SPMDExecutor:
    """Runs a :class:`Dataflow` as one compiled SPMD program.

    All stages fuse into a single ``jit(shard_map(...))``: per-device UDFs
    inline, shuffles as capacity-bounded collectives over ``axes`` (one axis
    = flat ``all_to_all``; a ``(dc, node)`` pair or a hierarchical ``plan`` =
    the two-level wide-area path). Every shuffle hop ships exactly one
    fused wire tensor (``wire_meta="min"`` — the executor regroups from the
    records themselves, so no per-record metadata rides the wire), and
    ``chunks`` sets the pipeline depth of every hop (``None`` defers to the
    explicit ``plan``'s chunks, or 1; a per-stage ``chunks`` overrides
    both). Compiled programs are cached on (pipeline identity, plan, input
    shapes/dtypes) in an LRU bounded by ``cache_size``, so re-running the
    same pipeline object on same-shaped data costs zero retracing while
    long-lived executors cannot accumulate compiled programs without bound.

    ``use_pallas`` runs every shuffle's partition/pack send path (and the
    stage-2 regroup) through the Pallas rank kernel instead of its jnp
    oracle. ``sort_algo`` pins the stage-2 segment-sort kernel
    (``"bitonic"`` / ``"radix"`` / ``"oracle"``); ``None`` defers to the
    backend-aware autotuner (:mod:`repro.kernels.autotune`) — measured once
    per segment geometry, replayed from cache afterwards.
    ``REPRO_KERNEL_FORCE`` overrides everything (and is part of the
    compile-cache key, so flipping it between runs retraces).

    ``debug_checks`` (on by default) validates, after each run of a
    pipeline containing a sort, that no real record key collided with the
    stage-2 padding sentinel (the key dtype's maximum) **while an unstable
    sort kernel is selected** — the bitonic network could silently swap
    such keys with padding slots. Stable kernels (radix, oracle) keep real
    keys ahead of the suffix padding, so max-value keys are delivered
    correctly and the check never fires. The check costs one scalar device
    sync per run; pass ``debug_checks=False`` to skip it.
    """

    def __init__(self, mesh: Mesh, axes: Sequence[str] = ("data",),
                 plan: Optional[ShufflePlan] = None,
                 use_pallas: bool = False,
                 chunks: Optional[int] = None,
                 cache_size: int = 32,
                 debug_checks: bool = True,
                 sort_algo: Optional[str] = None):
        self.mesh = mesh
        self.plan = plan
        self.axes = tuple(plan.axes) if plan is not None else tuple(
            (axes,) if isinstance(axes, str) else axes)
        self.use_pallas = use_pallas
        self.sort_algo = sort_algo
        self.chunks = chunks
        self.cache_size = cache_size
        self.debug_checks = debug_checks
        # LRU keyed on (pipeline id, plan, shapes/dtypes/shardings). Entries
        # hold a strong ref to the pipeline: while cached, its id() cannot be
        # reused by a new object, so an id-keyed hit is always the same
        # pipeline; eviction drops the ref together with the entry. Input
        # shardings are part of the key because entries store AOT-compiled
        # executables, which (unlike jit dispatch) do not re-specialize when
        # a committed input arrives with a different sharding.
        self._cache: "OrderedDict[Any, _CacheEntry]" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        # chaos/resume machinery: per-hop sub-pipelines (pinning their
        # parent so id()-keyed lookups stay sound) and sub-executors per
        # mesh, so repeated runs reuse compiled sub-programs
        self._subflows: Dict[Tuple, Tuple[Dataflow, Dataflow]] = {}
        self._sub_execs: Dict[Any, "SPMDExecutor"] = {}
        # the key layout of each sort stage, collected while a program is
        # lowered (None outside a lowering)
        self._sort_keys: Optional[List[str]] = None

    @property
    def axis_size(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.axes)

    def cache_info(self) -> CacheInfo:
        """Compile-cache counters (``functools.lru_cache`` style). A cache
        miss means the pipeline was (re)lowered and traced — steady-state
        streaming asserts ``misses`` is frozen after warm-up."""
        return CacheInfo(self._hits, self._misses, self._evictions,
                         len(self._cache), self.cache_size)

    def run(self, pipeline: Dataflow, records: Any,
            valid: Optional[Any] = None,
            carry: Optional[Tuple[Any, Any]] = None,
            chaos: Optional[Any] = None,
            trace: Optional[Any] = None) -> DataflowResult:
        """Execute ``pipeline`` over ``records`` sharded along ``axes``.

        ``records``: pytree of global arrays (or a
        :class:`repro.core.stream.SphereStream`, whose ``valid`` is used).

        ``chaos``: a :class:`repro.sphere.chaos.FaultPlan` or
        :class:`~repro.sphere.chaos.ChaosSchedule`. When given, the
        pipeline runs *segmented* — one compiled program per shuffle-hop
        phase, with a :class:`~repro.sphere.chaos.HopCheckpoint` sealed at
        every boundary — instead of one fused program, so an injected
        ``lose_device`` fault can be survived by re-forming a smaller mesh
        and resuming from the last checkpoint. ``kind="none"`` runs the
        segmented path with no fault (it must deliver exactly the fused
        result — asserted in tests/test_chaos.py).

        ``carry``: optional ``(records, valid)`` cross-batch state from the
        previous micro-batch of a *streaming* run. It is concatenated into
        the pipeline's **last reduce stage** input (per device — carry never
        crosses devices, which is sound because the deterministic shuffle
        sends a given key to the same device every batch), and the result
        carries the reduce output back out, compacted to the same fixed
        capacity (overflow is dropped and counted). Requires the reduce UDF
        to be schema-preserving; see :mod:`repro.sphere.streaming`.

        ``trace``: a :class:`repro.obs.trace.Tracer`. The run records
        lower/compile/execute spans (compile separated from execute by AOT
        compilation; execute fenced with ``block_until_ready`` so the span
        covers real device time) with wire-byte, chunk-round and drop
        attributes, and publishes wire-bytes / collective / drop counters
        to the metrics registry. Untraced runs skip everything that would
        force a device sync. Traced or not, the spans ``spmd.run``,
        ``spmd.execute`` and ``spmd.sentinel_check`` annotate a
        ``jax.profiler`` session, and the program's ops carry their layer
        scopes (:mod:`repro.obs.layers`): a profile attributes device time
        to stages through the op->layer map of the program that ran
        (``repro.obs.layers.last_run()``).
        """
        from repro.core.stream import SphereStream
        if isinstance(records, SphereStream):
            valid = records.valid if valid is None else valid
            records = records.data
        tr = trace if trace is not None else NULL_TRACER
        if chaos is not None:
            return self._run_segmented(pipeline, records, valid, carry,
                                       chaos, tr)
        records = jax.tree.map(jnp.asarray, records)
        n = _leading(records)
        if valid is None:
            valid = jnp.ones((n,), jnp.bool_)
        if carry is not None:
            carry = (jax.tree.map(jnp.asarray, carry[0]),
                     jnp.asarray(carry[1]))
            ckey = (jax.tree.structure(carry[0]),
                    tuple((tuple(l.shape), str(l.dtype))
                          for l in jax.tree.leaves(carry[0])),
                    tuple(carry[1].shape))
        else:
            ckey = None
        leaves = jax.tree.leaves(records)
        key = (id(pipeline), self.plan, self.chunks,
               self.sort_algo, os.environ.get(autotune.FORCE_ENV),
               jax.tree.structure(records),
               tuple((tuple(l.shape), str(l.dtype),
                      str(getattr(l, "sharding", None))) for l in leaves),
               ckey)
        args = ((records, valid, carry[0], carry[1]) if carry is not None
                else (records, valid))
        attrs = ({"pipeline": pipeline.describe(), "records": n}
                 if tr.enabled else {})
        with tr.span("spmd.run", **attrs) as root:
            entry = self._cache.get(key)
            if entry is None:
                root.set(cache="miss")
                entry = self._compile_entry(pipeline, args,
                                            carry is not None, key, tr)
            else:
                self._hits += 1
                REGISTRY.counter("spmd.cache.hits").inc()
                self._cache.move_to_end(key)
                root.set(cache="hit")
            obs_layers.ran(entry.module, entry.layers, entry.bucketing,
                           entry.sort_keys)
            with tr.span("spmd.execute", hops=len(entry.hops)):
                out = entry.fn(*args)
                if tr.enabled:
                    # fence: the span must cover device time, not dispatch
                    out = jax.block_until_ready(out)
            if carry is not None:
                (out_records, out_valid, dropped, sentinel_hits,
                 c_rec, c_valid) = out
                out_carry = (c_rec, c_valid)
            else:
                out_records, out_valid, dropped, sentinel_hits = out
                out_carry = None
            hits = 0
            if self.debug_checks and entry.has_sort:
                # one scalar sync a call: the host waits for the program
                with tr.span("spmd.sentinel_check"):
                    hits = int(sentinel_hits)
            if hits > 0:
                raise ValueError(
                    f"{hits} record key(s) equal the key "
                    f"dtype's maximum — the stage-2 sort padding sentinel — "
                    f"while the unstable 'bitonic' kernel is selected: the "
                    f"network's tie order is unspecified, so they could "
                    f"silently swap with padding slots. Use a stable sort "
                    f"(sort_algo='radix' or 'oracle' — both deliver "
                    f"max-value keys correctly), rescale the keys, or pass "
                    f"debug_checks=False to accept the old silent "
                    f"behaviour.")
            self._record_run(entry, n, dropped, tr, root)
        return DataflowResult(records=out_records, valid=out_valid,
                              dropped=dropped, carry=out_carry, trace=trace)

    # -- compile + per-run accounting -----------------------------------------
    def _compile_entry(self, pipeline: Dataflow, args: Tuple,
                       with_carry: bool, key, tr) -> _CacheEntry:
        self._misses += 1
        REGISTRY.counter("spmd.cache.misses").inc()
        hops: List[dict] = []
        attrs = {"pipeline": pipeline.describe()} if tr.enabled else {}
        with tr.span("spmd.lower", **attrs):
            jitted = self.program(pipeline, with_carry=with_carry)
            self._sort_keys = []            # filled by _sort while tracing
            try:
                with record_hops(hops):
                    lowered = jitted.lower(*args)
                sort_keys = tuple(self._sort_keys)
            finally:
                self._sort_keys = None
        with tr.span("spmd.compile"):
            fn = lowered.compile()
            # the op->layer map of this executable, read once per compile
            # (also when it came from the persistent compile cache)
            text = fn.as_text()
            module, layers = module_name(text), op_layers(text)
        collectives = None
        if tr.enabled:
            with tr.span("spmd.introspect"):
                collectives = collective_counts(jitted, *args)
        sorts = [s for s in pipeline.stages if isinstance(s, SortStage)]
        entry = _CacheEntry(
            pipeline=pipeline, fn=fn, has_sort=bool(sorts), hops=hops,
            collectives=collectives, module=module, layers=layers,
            bucketing=tuple(bucketing(s) for s in sorts),
            sort_keys=sort_keys)
        self._cache[key] = entry
        if len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)
            self._evictions += 1
            REGISTRY.counter("spmd.cache.evictions").inc()
        return entry

    def _record_run(self, entry: _CacheEntry, n: int, dropped, tr,
                    root) -> None:
        """Publish per-run metrics. Wire bytes replay the hop geometry
        captured at lowering time; drop counts force a device sync, so they
        are only recorded under an active tracer."""
        m = REGISTRY
        m.counter("spmd.runs").inc()
        m.counter("spmd.records_in").inc(n)
        wire = 0
        if entry.hops:
            wire = (sum(h["wire_bytes_per_device"] for h in entry.hops)
                    * self.axis_size)
            m.counter("spmd.shuffle.wire_bytes").inc(wire)
            m.counter("spmd.shuffle.hops").inc(len(entry.hops))
        if not tr.enabled:
            return
        d = int(dropped)
        m.counter("spmd.dropped").inc(d)
        if entry.collectives is not None:
            m.counter("spmd.collectives.all_to_all").inc(
                entry.collectives.get("all_to_all", 0))
        root.set(dropped=d, wire_bytes=wire,
                 hops=[{k: h[k] for k in ("axis", "num_dest", "chunks",
                                          "wire_bytes_per_device")}
                       for h in entry.hops])

    # -- segmented execution + device-loss recovery ---------------------------
    def _sub_executor(self, mesh: Mesh) -> "SPMDExecutor":
        sub = self._sub_execs.get(mesh)
        if sub is None:
            sub = SPMDExecutor(mesh, axes=self.axes, plan=None,
                               use_pallas=self.use_pallas, chunks=self.chunks,
                               cache_size=self.cache_size,
                               debug_checks=self.debug_checks,
                               sort_algo=self.sort_algo)
            self._sub_execs[mesh] = sub
        return sub

    def _subflow(self, pipeline: Dataflow, pi: int, phase) -> Dataflow:
        key = (id(pipeline), pi)
        hit = self._subflows.get(key)
        if hit is not None and hit[0] is pipeline:
            return hit[1]
        stages = tuple(phase.stages)
        if phase.terminator is not None:
            stages = stages + (phase.terminator,)
        sub = Dataflow(stages=stages, codec=pipeline.codec)
        self._subflows[key] = (pipeline, sub)
        return sub

    def _run_segmented(self, pipeline: Dataflow, records: Any, valid: Any,
                       carry, chaos, tr=NULL_TRACER) -> DataflowResult:
        """Run ``pipeline`` one shuffle-hop phase at a time, sealing a
        :class:`~repro.sphere.chaos.HopCheckpoint` at every boundary; on an
        injected device loss, re-form the largest usable smaller mesh
        (``elastic.shrink_mesh``) and resume the interrupted hop from the
        checkpoint (``elastic.remesh`` re-shards the layout-agnostic byte
        rows — every old shard lands whole on one new device, so the
        delivered multiset is identical to the fault-free run)."""
        from repro.sphere.chaos import (HOST_KINDS, STREAM_KINDS,
                                        HopCheckpoint, plan_kinds)
        from repro.train import elastic

        for kind in plan_kinds(chaos):
            if kind in HOST_KINDS:
                raise ValueError(
                    f"{kind!r} is a Sector-level fault; inject it via "
                    f"HostExecutor.run(chaos=...)")
            if kind in STREAM_KINDS:
                raise ValueError(
                    f"{kind!r} is a streaming fault; inject it via "
                    f"StreamExecutor(chaos=...)")
        if carry is not None:
            raise ValueError("chaos injection does not compose with "
                             "streaming carry state")
        if self.plan is not None:
            raise ValueError("chaos/resume re-forms the mesh on device loss "
                             "and cannot honor an explicit ShufflePlan; "
                             "construct the executor with axes=... instead")
        phases = _phases(pipeline)
        # the bucket layout must be pinned up front: after a device loss an
        # auto bucket count (= axis_size) would silently re-bucket the data
        nbs = []
        for ph in phases:
            t = ph.terminator
            if t is None:
                continue
            nb = t.num_buckets
            if (nb is None and isinstance(t, SortStage)
                    and t.splitters is not None):
                nb = int(np.asarray(t.splitters).shape[0]) + 1
            if nb is None:
                raise ValueError(
                    "chaos/resume needs an explicit num_buckets (or sort "
                    "splitters) on every shuffle/sort stage — an auto bucket "
                    "count would change when the mesh shrinks")
            nbs.append(nb)
        nb_constraint = math.gcd(*nbs) if nbs else self.axis_size

        records = jax.tree.map(jnp.asarray, records)
        if valid is None:
            valid = jnp.ones((_leading(records),), jnp.bool_)
        exec_ = self._sub_executor(self.mesh)
        dropped = 0
        recoveries = 0
        for pi, phase in enumerate(phases):
            # seal the hop: the checkpoint survives whatever dies next
            ckpt = HopCheckpoint.snapshot(records, valid, pi, dropped)
            lost = chaos.fire_spmd(pi, exec_.axis_size)
            if lost is not None:
                with tr.span(f"recover[{pi}]", lost_device=lost):
                    new_mesh = elastic.shrink_mesh(exec_.mesh, self.axes,
                                                   lost, nb_constraint)
                    exec_ = self._sub_executor(new_mesh)
                    records, valid = ckpt.restore(new_mesh, self.axes)
                    dropped = ckpt.dropped
                    recoveries += 1
                    REGISTRY.counter("spmd.recoveries").inc()
                chaos.events.append(
                    f"resumed hop {pi} on mesh "
                    f"{dict(zip(self.axes, (new_mesh.shape[a] for a in self.axes)))}")
            with tr.span(f"phase[{pi}]", devices=exec_.axis_size) as psp:
                res = exec_.run(self._subflow(pipeline, pi, phase), records,
                                valid=valid,
                                trace=tr if tr.enabled else None)
                records, valid = res.records, res.valid
                d = int(res.dropped)
                dropped += d
                psp.set(dropped=d)
        return DataflowResult(records=records, valid=valid,
                              dropped=dropped, recoveries=recoveries,
                              trace=tr if tr.enabled else None)

    # -- lowering -------------------------------------------------------------
    def program(self, df: Dataflow, with_carry: bool = False) -> Callable:
        """The fused program of ``df``: one jitted ``shard_map`` over the
        mesh taking ``(records, valid)`` (plus the carry pair when
        ``with_carry``) and returning ``(records, valid, dropped,
        sentinel_hits[, carry...])``. :meth:`run` compiles and caches it;
        ``program(df).lower(...)`` is its ahead-of-time entry, and it is
        what :func:`repro.core.introspect.collective_counts` inspects."""
        spec = P(self.axes[0]) if len(self.axes) == 1 else P(self.axes)
        axes = self.axes
        carry_at = _last_reduce_index(df) if with_carry else -1

        def body(records, valid, carry_records, carry_valid):
            valid = valid.reshape(-1)
            dropped = jnp.zeros((), jnp.int32)
            sentinel = jnp.zeros((), jnp.int32)
            new_carry = (None, None)
            for i, stage in enumerate(df.stages):
                if isinstance(stage, MapStage):
                    with jax.named_scope(UDF):
                        records = stage.fn(records)
                        if _leading(records) != valid.shape[0]:
                            valid = jnp.ones((_leading(records),), jnp.bool_)
                elif isinstance(stage, ReduceStage):
                    with jax.named_scope(UDF):
                        if i == carry_at:
                            # merge last batch's aggregate into this group;
                            # the reduce output below becomes the next
                            # batch's carry
                            records = jax.tree.map(
                                lambda a, c: jnp.concatenate([a, c], axis=0),
                                records, carry_records)
                            valid = jnp.concatenate(
                                [valid, carry_valid.reshape(-1)])
                        records, valid, rd = _split_reduce_out(
                            stage.fn(records, valid))
                        valid = valid.reshape(-1)
                        if rd is not None:
                            dropped += jax.lax.psum(
                                jnp.asarray(rd, jnp.int32), axes)
                        if i == carry_at:
                            cap = carry_valid.reshape(-1).shape[0]
                            c_rec, c_valid, c_drop = _compact_carry(
                                records, valid, cap)
                            new_carry = (c_rec, c_valid)
                            dropped += jax.lax.psum(
                                c_drop.astype(jnp.int32), axes)
                elif isinstance(stage, ShuffleStage):
                    with jax.named_scope(UDF):
                        ids = jnp.asarray(stage.by(records)).reshape(-1)
                    records, valid, d, _ = self._exchange(
                        records, valid, ids, stage.num_buckets,
                        stage.capacity_factor, stage.chunks)
                    dropped += d
                elif isinstance(stage, SortStage):
                    records, valid, d, hits = self._sort(records, valid,
                                                         stage)
                    dropped += d
                    sentinel += hits
                else:
                    raise TypeError(f"unknown stage {stage!r}")
            return records, valid, dropped, sentinel, new_carry

        # the program is named ``sphere_spmd`` (HLO module
        # ``jit_sphere_spmd``): the name labels it in a profile and in the
        # op->layer table, and the persistent compile cache keys it by this
        # name and by its IR without locations — layer scopes, being
        # locations, never change a key, so an executable compiled without
        # them is never loaded under this name
        if with_carry:
            def sphere_spmd(records, valid, carry_records, carry_valid):
                records, valid, dropped, sentinel, (c_rec, c_valid) = body(
                    records, valid, carry_records, carry_valid)
                return records, valid, dropped, sentinel, c_rec, c_valid

            mapped = shard_map(sphere_spmd, mesh=self.mesh,
                               in_specs=(spec, spec, spec, spec),
                               out_specs=(spec, spec, P(), P(), spec, spec),
                               check_vma=False)
            return jax.jit(mapped)

        def sphere_spmd(records, valid):
            records, valid, dropped, sentinel, _ = body(records, valid,
                                                        None, None)
            return records, valid, dropped, sentinel

        mapped = shard_map(sphere_spmd, mesh=self.mesh,
                           in_specs=(spec, spec),
                           out_specs=(spec, spec, P(), P()), check_vma=False)
        return jax.jit(mapped)

    def sort_cell(self, stage: SortStage, n_local: int) -> SortCell:
        """The stage-2 geometry ``stage`` sorts in when ``n_local``
        records per device enter it: the receive buffer's length and the
        ``buckets_per_device`` x ``segment`` cell the segment sort (and the
        autotuner's cell key) sees."""
        nb = (self.plan.num_buckets if self.plan is not None
              else stage.num_buckets or self.axis_size)
        plan = self._stage_plan(nb, n_local, stage.capacity_factor,
                                stage.chunks)
        recv, bpd = plan.recv_slots, plan.buckets_per_device
        return SortCell(recv, bpd,
                        _segment_capacity(recv, bpd, stage.capacity_factor))

    def _stage_plan(self, num_buckets: Optional[int], n_local: int,
                    capacity_factor: float,
                    chunks: Optional[int]) -> ShufflePlan:
        # precedence: stage chunks > executor chunks > plan chunks > 1
        w = chunks if chunks is not None else self.chunks
        if self.plan is not None:
            if num_buckets not in (None, self.plan.num_buckets):
                raise ValueError(
                    f"stage wants {num_buckets} buckets but the executor "
                    f"plan has {self.plan.num_buckets}")
            if w is None or w == self.plan.chunks:
                return self.plan
            return dataclasses.replace(self.plan, chunks=w)
        nb = num_buckets or self.axis_size
        return ShufflePlan.for_mesh(self.mesh, nb, n_local, capacity_factor,
                                    self.axes, use_pallas=self.use_pallas,
                                    chunks=1 if w is None else w)

    def _exchange(self, records, valid, ids, num_buckets, capacity_factor,
                  chunks=None):
        """One bucket shuffle: pack -> plan.shuffle -> unpack. The wire
        carries pure payload rows (``wire_meta="min"``): every post-shuffle
        consumer here regroups from the decoded records, so bucket/src
        metadata would be dead bytes."""
        codec = RecordCodec.from_example(records)
        with jax.named_scope(MOVE):
            packed = codec.pack(records)
        plan = self._stage_plan(num_buckets, packed.shape[0], capacity_factor,
                                chunks)
        with jax.named_scope(EXCHANGE):
            res = plan.shuffle(packed, ids.astype(jnp.int32), valid=valid,
                               wire_meta="min")
        with jax.named_scope(MOVE):
            out = codec.unpack(res.data.reshape(-1, codec.nbytes))
        return out, res.valid.reshape(-1), res.dropped, plan

    def _sort(self, records, valid, stage: SortStage):
        """Range-partition shuffle (stage 1) + local **segmented** sort
        (stage 2) — paper §4.2 / Fig 3.

        Stage 2 regroups the received records bucket-major with the same
        fused O(n) partition/pack the send path uses, then sorts the
        ``buckets_per_device`` segments independently through the autotuned
        :func:`repro.kernels.ops.sort_kv_segments` entry point (``sort_algo``
        pins bitonic/radix/oracle; ``None`` lets the autotuner measure the
        segment geometry once and replay the cached winner). Because each
        device's buckets are consecutive key ranges,
        concatenating its sorted segments is already globally sorted —
        cutting the sorting-network work from O(R log² R) to
        O(R log² (R/bpd)). With one bucket per device the segment is the
        whole receive buffer and the layout matches the historical path
        exactly. Segments get ``capacity_factor`` headroom over the uniform
        share; records past a segment's capacity are dropped *and counted*
        (the same §3.5.1 bounded-skew contract as the shuffle itself —
        impossible when ``buckets_per_device == 1``).

        Range bucketing (:func:`bucketing`): with the default even
        splitters a key's bucket is one division
        (:func:`repro.core.sort.uniform_bucket`); splitters the stage was
        given are searched (``searchsorted``). Both give
        ``searchsorted(splitters, key, side="right")``.

        Keys: the stage's ``key`` gives an int32 key (one word), or ``(n,
        k)`` uint8 bytes ordered lexicographically (memcmp order, as
        TeraSort's 10-byte keys): these become ``ceil(k / 4)`` big-endian
        words (:func:`repro.core.sort.key_words`), each mapped to an int32
        whose signed order is the word's unsigned order, and are bucketed
        on their leading 31 bits (:func:`repro.core.sort.word_bucket_key`)
        — monotone in the whole key, so the concatenated segments stay
        sorted. Stage 2 sorts the word planes lexicographically.

        Layer scopes (:mod:`repro.obs.layers`): ``sphere.bucket`` holds the
        splitters and both bucketings (send and regroup), ``sphere.key`` a
        byte key's conversion to words, ``sphere.segsort`` the segment sort
        and the sentinel keys feeding it, ``sphere.move`` the final gather;
        the exchange and partition/pack bring their own.

        Returns ``(records, valid, dropped, sentinel_hits)`` —
        ``sentinel_hits`` counts real received keys equal to the padding
        sentinel (the int32 maximum on every word, via
        :func:`repro.kernels.ops.pad_sentinel`), checked host-side by
        :meth:`run` when ``debug_checks``. The count is only taken when the
        resolved kernel is the *unstable* bitonic network — padding sits in
        each segment's suffix, so any stable sort (radix, oracle) keeps
        real max-value keys ahead of it and delivers them correctly; for
        those the hit count is a constant 0 and the guard can never fire.
        """
        nb = (self.plan.num_buckets if self.plan is not None
              else stage.num_buckets or self.axis_size)
        with jax.named_scope(BUCKET):
            if bucketing(stage) == "search":
                spl = jnp.asarray(stage.splitters)
                if spl.shape[0] != nb - 1:
                    raise ValueError(
                        f"{spl.shape[0]} splitters for {nb} buckets")

                def bucket_of(k):
                    return jnp.searchsorted(spl, k, side="right").astype(
                        jnp.int32)
            else:
                def bucket_of(k):
                    return uniform_bucket(k, nb)
            key = jnp.asarray(stage.key(records))
            if self._sort_keys is not None:       # a program being lowered
                self._sort_keys.append(key_layout(key))
            planes, bkey = self._key_planes(key)
            bucket = bucket_of(planes[0] if bkey is None else bkey)
        records, valid, dropped, plan = self._exchange(
            records, valid, bucket, nb, stage.capacity_factor, stage.chunks)

        # stage 2: bucket-major regroup (O(n) partition, stable) ...
        bpd = plan.buckets_per_device
        with jax.named_scope(SEGSORT):
            planes, bkey = self._key_planes(stage.key(records))
            sentinel = kops.pad_sentinel(planes[0].dtype)
            skeys = [jnp.where(valid, p, sentinel) for p in planes]
            seg_cap = _segment_capacity(skeys[0].shape[0], bpd,
                                        stage.capacity_factor)
            # resolve the stage-2 kernel now (trace-time): stability decides
            # whether sentinel-collision accounting is needed at all.
            algo = kops.resolve_sort_algo(bpd, seg_cap, skeys[0].dtype,
                                          self.sort_algo, kv=True,
                                          words=len(planes))
            if autotune.is_stable(algo):
                sentinel_hits = jnp.zeros((), jnp.int32)
            else:
                hit = valid
                for p in planes:
                    hit = hit & (p == sentinel)
                sentinel_hits = jax.lax.psum(
                    jnp.sum(hit.astype(jnp.int32)), plan.pmean_axes())
        with jax.named_scope(BUCKET):
            local = (bucket_of(skeys[0] if bkey is None else bkey)
                     - plan.device_index() * bpd)
            seg_dest = jnp.where(valid, local, bpd)   # invalid -> overflow
        leaves, treedef = jax.tree.flatten(records)
        w = len(planes)
        tiles, in_rng, _, seg_drop = kops.partition_pack(
            skeys + leaves, seg_dest, bpd, seg_cap,
            use_pallas=self.use_pallas)
        with jax.named_scope(MOVE):
            dropped += jax.lax.psum(seg_drop, plan.pmean_axes())

        # ... then one multi-segment sort: bpd rows of seg_cap. Empty slots
        # carry the max-key sentinel so each segment's valid records end up
        # in its prefix — exactly where ``in_rng`` already points (pads sit
        # in the suffix, which stable kernels preserve even on key ties).
        with jax.named_scope(SEGSORT):
            seg_keys = tuple(jnp.where(in_rng, t, sentinel)
                             for t in tiles[:w])
            pos = jnp.arange(bpd * seg_cap, dtype=jnp.int32).reshape(
                bpd, seg_cap)
            # one word is sorted as a plain key array, as int32 keys were
            _, order = kops.sort_kv_segments(
                seg_keys if w > 1 else seg_keys[0], pos, algo=algo)
            order = order.reshape(-1)
        with jax.named_scope(MOVE):
            records = jax.tree.unflatten(treedef, [
                jnp.take(t.reshape((bpd * seg_cap,) + t.shape[2:]), order,
                         axis=0)
                for t in tiles[w:]])
        return records, in_rng.reshape(-1), dropped, sentinel_hits

    @staticmethod
    def _key_planes(key):
        """The int32 planes a sort key is ordered by, most significant
        first, and the int32 it is bucketed on (None: the first plane).
        An int32 key is one plane; a byte key's words (in ``sphere.key``)
        become planes whose signed order is the words' unsigned order, and
        it is bucketed on its leading 31 bits."""
        key = jnp.asarray(key)
        if not is_byte_key(key):
            return (key.astype(jnp.int32).reshape(-1),), None
        with jax.named_scope(KEY):
            words = key_words(key)
            planes = tuple(sortable_bits_to_key(w, jnp.int32) for w in words)
            return planes, word_bucket_key(words[0])


# -- host (Sector/SPE) executor ----------------------------------------------


class _Phase:
    """Consecutive record-wise stages, optionally ended by a shuffle/sort."""

    def __init__(self, stages: List[Any], terminator: Optional[Any]):
        self.stages = stages
        self.terminator = terminator


def _phases(df: Dataflow) -> List[_Phase]:
    out, cur = [], []
    for st in df.stages:
        if isinstance(st, (ShuffleStage, SortStage)):
            out.append(_Phase(cur, st))
            cur = []
        else:
            cur.append(st)
    out.append(_Phase(cur, None))
    return out


def _np_records(records) -> Any:
    return jax.tree.map(np.asarray, records)


_scratch_counter = itertools.count()


class HostExecutor:
    """Runs a :class:`Dataflow` on the Sector/SPE data plane.

    The pipeline splits into phases at shuffle/sort boundaries. Each phase is
    one :class:`repro.sphere.engine.SphereProcess` stage: SPEs decode Sector
    segments through the source codec, run the phase's UDFs, and route the
    (re-encoded) outputs either back to the client or into **bucket files**
    (the paper's §3.2 "bucket writers"), which are uploaded to Sector and
    become the next phase's input stream. Locality scheduling, SPE failure
    retry, and data-error reporting all come from the engine; validity masks
    never appear on this path because host buckets are variable-size (no
    capacity bound -> nothing is dropped by shuffles here).
    """

    def __init__(self, master, client, spes: Sequence[Any],
                 max_retries: int = 2, scratch_prefix: str = "/.dataflow",
                 daemon: Optional[Any] = None,
                 retry_policy: Optional[Any] = None):
        self.master = master
        self.client = client
        self.spes = list(spes)
        self.max_retries = max_retries
        #: optional :class:`repro.core.retry.RetryPolicy` for the engine's
        #: segment re-pools (None keeps immediate zero-delay retries)
        self.retry_policy = retry_policy
        self.scratch_prefix = scratch_prefix
        #: optional :class:`repro.sector.master.ReplicationDaemon`; when set,
        #: freshly uploaded bucket files are replicated before the next phase
        #: reads them — without it a mid-job slave death can take the only
        #: copy of a bucket with it (a DATA_ERROR, not silent loss)
        self.daemon = daemon

    def run(self, pipeline: Dataflow, file_paths: Sequence[str],
            chaos: Optional[Any] = None,
            trace: Optional[Any] = None) -> DataflowResult:
        """Execute ``pipeline`` over Sector files. ``pipeline.codec`` is
        required: it decodes the source records (record_bytes =
        ``codec.nbytes``).

        ``chaos``: a :class:`repro.sphere.chaos.FaultPlan` or
        :class:`~repro.sphere.chaos.ChaosSchedule` fired at each phase
        boundary (``kill_slave`` / ``drop_bucket`` / ``rejoin_slave``).
        Recovery is
        always armed regardless: segment reads that fail because every
        listed replica is gone trigger ``SectorClient.recover`` (master
        prunes stale locations, rediscovers survivors by §2.2 scan,
        re-replicates) and the segment is re-pooled per §3.5.2.

        ``trace``: a :class:`repro.obs.trace.Tracer` — records
        ``host.run`` → ``phase[i]`` → per-segment spans (with retry /
        recovery sub-spans from the engine) and ``hop[i]:buckets`` spans
        for bucket materialization. Per-phase wall time is ALWAYS
        accounted in ``result.phase_times`` (a cheap ``time.monotonic``
        pair), tracer or not."""
        from repro.sphere.chaos import SPMD_KINDS, STREAM_KINDS, plan_kinds
        from repro.sphere.engine import SphereProcess

        if chaos is not None:
            for kind in plan_kinds(chaos):
                if kind in SPMD_KINDS:
                    raise ValueError(
                        f"{kind!r} is a device-mesh fault; inject it via "
                        f"SPMDExecutor.run(chaos=...)")
                if kind in STREAM_KINDS:
                    raise ValueError(
                        f"{kind!r} is a streaming fault; inject it via "
                        f"StreamExecutor(chaos=...)")

        if pipeline.codec is None:
            raise ValueError("HostExecutor needs Dataflow.source(codec=...) "
                             "to decode Sector records")
        tr = trace if trace is not None else NULL_TRACER
        codec = pipeline.codec
        paths = list(file_paths)
        scratch = f"{self.scratch_prefix}/run{next(_scratch_counter)}"
        errors: Dict[Any, str] = {}
        retries = 0
        dropped = 0
        recoveries = 0
        data_errors = 0
        pending_sort: Optional[SortStage] = None
        phase_times: List[Dict[str, Any]] = []

        phases = _phases(pipeline)
        attrs = ({"pipeline": pipeline.describe(), "files": len(paths)}
                 if tr.enabled else {})
        with tr.span("host.run", **attrs) as root:
            for pi, phase in enumerate(phases):
                t0 = time.monotonic()
                term = phase.terminator
                term_kind = ("output" if term is None else
                             _STAGE_KIND[type(term)])
                with tr.span(f"phase[{pi}]", paths=len(paths),
                             terminator=term_kind) as psp:
                    if chaos is not None:
                        chaos.fire_host(pi, self.master, paths, self.spes)
                    proc = SphereProcess(self.master, self.client.session_id,
                                         self.spes,
                                         max_retries=self.max_retries,
                                         retry_policy=self.retry_policy)
                    holder: Dict[str, Any] = {"codec": None, "dropped": 0}
                    udf = self._phase_udf(phase, pending_sort, holder)
                    nb = self._num_buckets(term)
                    if term is not None:
                        def bucket_fn(out):
                            packed, ids = out
                            return {b: packed[ids == b] for b in range(nb)}
                    else:
                        bucket_fn, nb = None, 0
                    # after a shuffle, a bucket file must stay one segment
                    # (one reduce group) — force whole-file segmentation
                    seg_kw = ({} if pi == 0 else
                              {"s_min": 1 << 40, "s_max": 1 << 40})
                    res = proc.run(paths, udf, record_bytes=codec.nbytes,
                                   codec=codec, bucket_fn=bucket_fn,
                                   num_buckets=nb,
                                   recover=self.client.recover,
                                   trace=trace, **seg_kw)
                    retries += res.retries
                    recoveries += res.recoveries
                    data_errors += res.data_errors
                    dropped += holder["dropped"]
                    errors.update({(pi, k): v for k, v in res.errors.items()})
                    out_codec = holder["codec"] or codec
                    psp.set(segments=res.segments_processed, retries=res.retries,
                            recoveries=res.recoveries,
                            data_errors=res.data_errors)
                    materialize_s = 0.0
                    if term is not None:
                        # materialize bucket files as the next phase's input
                        m0 = time.monotonic()
                        with tr.span(f"hop[{pi}]:buckets", buckets=nb):
                            prefix = f"{scratch}/s{pi}"
                            self.client.upload_dataset(
                                prefix,
                                [np.ascontiguousarray(res.outputs[b])
                                 .tobytes() for b in range(nb)])
                            paths = [f"{prefix}.{b:05d}" for b in range(nb)]
                            if self.daemon is not None:
                                # replicate fresh bucket files before
                                # anything can eat them
                                self.daemon.run_until_stable()
                        materialize_s = time.monotonic() - m0
                    elapsed = time.monotonic() - t0
                    phase_times.append({
                        "phase": pi, "terminator": term_kind,
                        "seconds": elapsed, "engine_s": res.elapsed_s,
                        "materialize_s": materialize_s,
                        "segments": res.segments_processed,
                        "retries": res.retries,
                        "recoveries": res.recoveries,
                        "data_errors": res.data_errors,
                    })
                    REGISTRY.histogram("host.phase_seconds").observe(elapsed)
                    if term is None:
                        REGISTRY.counter("host.dropped").inc(dropped)
                        root.set(phases=len(phase_times), dropped=dropped)
                        parts = [res.outputs[i] for i in sorted(res.outputs)]
                        packed = (np.concatenate(parts, axis=0) if parts
                                  else np.zeros((0, out_codec.nbytes),
                                                np.uint8))
                        records = out_codec.decode(packed)
                        return DataflowResult(
                            records=records,
                            valid=np.ones((_leading(records),), bool),
                            dropped=dropped, errors=errors, retries=retries,
                            recoveries=recoveries, data_errors=data_errors,
                            trace=trace, phase_times=phase_times)
                    codec = out_codec
                    pending_sort = (term if isinstance(term, SortStage)
                                    else None)
        raise AssertionError("unreachable: final phase returns")

    # -- phase lowering -------------------------------------------------------
    def _num_buckets(self, term) -> int:
        if term is None:
            return 0
        if term.num_buckets is not None:
            return term.num_buckets
        if isinstance(term, SortStage) and term.splitters is not None:
            return int(np.asarray(term.splitters).shape[0]) + 1
        return len(self.spes)

    def _phase_udf(self, phase: _Phase, pending_sort: Optional[SortStage],
                   holder: Dict[str, Any]) -> Callable:
        """Build the (decoded records) -> packed bytes UDF one SPE runs.

        The output record schema is only known once a segment has been
        processed; it is stashed in ``holder`` so the executor can decode the
        bucket files / final outputs (every segment must agree)."""
        term = phase.terminator
        nb = self._num_buckets(term)

        def udf(records):
            records = _np_records(records)
            if pending_sort is not None:
                # stage 2 of a sort: this segment IS one range partition
                key = np.asarray(pending_sort.key(records))
                order = (np.lexsort(key_words(key)[::-1]) if is_byte_key(key)
                         else np.argsort(key, kind="stable"))
                records = jax.tree.map(lambda a: a[order], records)
            valid = np.ones((_leading(records),), bool)
            for stage in phase.stages:
                if isinstance(stage, MapStage):
                    records = _np_records(stage.fn(records))
                    if _leading(records) != valid.shape[0]:
                        valid = np.ones((_leading(records),), bool)
                elif isinstance(stage, ReduceStage):
                    records, valid, rd = _split_reduce_out(
                        stage.fn(records, valid))
                    records = _np_records(records)
                    valid = np.asarray(valid).reshape(-1)
                    if rd is not None:
                        holder["dropped"] += int(rd)
                else:
                    raise TypeError(f"unexpected mid-phase stage {stage!r}")
            records = jax.tree.map(lambda a: a[valid], records)
            codec = RecordCodec.from_example(records)
            if holder["codec"] is None:
                holder["codec"] = codec
            elif holder["codec"] != codec:
                raise ValueError("UDF output schema differs across segments: "
                                 f"{holder['codec']} vs {codec}")
            packed = codec.encode(records)
            if term is None:
                return packed
            if isinstance(term, SortStage):
                keys = np.asarray(term.key(records))
                keys = (word_bucket_key(key_words(keys)[0])
                        if is_byte_key(keys) else keys.astype(np.int32))
                spl = (np.asarray(term.splitters) if term.splitters is not None
                       else uniform_splitters(nb))
                ids = np.searchsorted(spl, keys, side="right")
            else:
                ids = np.asarray(term.by(records)).reshape(-1)
            return packed, ids.astype(np.int64)

        return udf
