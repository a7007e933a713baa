"""Backend-aware sort-kernel autotuner: measure once, cache, replay.

Three interchangeable implementations back ``ops.sort_segments`` /
``ops.sort_kv_segments``:

- ``"bitonic"`` — the in-VMEM sorting network (O(S log² S), not stable),
- ``"radix"``   — the stable LSD counting-radix kernel (O(S · 32/bits)),
- ``"oracle"``  — XLA's stable sort (``jnp.sort`` / stable argsort+gather).

Which one wins depends on the backend and the segment geometry: on TPU the
Pallas kernels keep segments resident in VMEM; on the CPU container they run
in interpret mode, where XLA's native sort often wins and the O(S²/chunk)
matmul permutation makes radix a guaranteed loss. Rather than scatter
``use_pallas`` booleans through every call site, this module picks **per
shape**: the first call for a given ``(kv, dtype, key words, num_segments,
segment_len, backend, mode)`` cell times every candidate on synthetic data
and caches the winner for the life of the process — every later call
replays the cached choice with zero measurement. The measured table can be
exported (the kernel benchmark persists it into ``BENCH_kernels.json`` and
CI uploads it as an artifact) and pre-loaded via
``REPRO_AUTOTUNE_TABLE=<path>`` so production runs never measure at all.

Resolution order (first hit wins):

1. ``REPRO_KERNEL_FORCE=radix|bitonic|oracle`` — unconditional override,
2. the in-process cache (each cell is measured at most once — asserted in
   tests via :data:`MEASUREMENTS`),
3. a pre-loaded table entry for this backend/mode,
4. below :data:`MIN_MEASURE_ELEMS` (or with ``REPRO_AUTOTUNE=0``): the
   static default ``"oracle"`` — measurement noise beats kernel differences
   on tiny segments, and the stable oracle is always correct,
5. measure all eligible candidates, pick the fastest. Candidates outside
   their envelope (bitonic or radix beyond its VMEM bound, or
   interpret-mode radix past the measurement budget) are skipped **with a
   recorded reason** — never silently. A candidate inside its envelope that
   fails to compile or run raises: a kernel the device's compiler refuses
   is a fault to fix, never a reason to fall back to the XLA oracle.

Every choice is stable-aware: callers that need stability (the stage-2
segmented sort's suffix padding) ask :func:`is_stable` about the resolved
algorithm and only then enable the sentinel-collision guard.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

ALGOS = ("bitonic", "radix", "oracle")

#: algorithms that preserve the input order of equal keys.
STABLE_ALGOS = frozenset({"radix", "oracle"})

FORCE_ENV = "REPRO_KERNEL_FORCE"
TABLE_ENV = "REPRO_AUTOTUNE_TABLE"
MEASURE_ENV = "REPRO_AUTOTUNE"

#: cells smaller than this take the static default instead of measuring.
MIN_MEASURE_ELEMS = 1 << 14

#: interpret-mode radix measurement budget: the matmul permutation is
#: emulated, so measuring huge cells would stall the caller for seconds.
_RADIX_MEASURE_MAX_SEGLEN = 512
_RADIX_MEASURE_MAX_ELEMS = 1 << 15

_MEASURE_ITERS = 3


@dataclasses.dataclass(frozen=True)
class Choice:
    """Resolved algorithm for one cell.

    source: "forced" | "cached" | "table" | "static" | "measured".
    melem:  algo -> measured throughput (Melem/s); measurement cells only.
    skipped: algo -> reason it was not measured.
    """
    algo: str
    source: str
    melem: Mapping[str, float] = dataclasses.field(default_factory=dict)
    skipped: Mapping[str, str] = dataclasses.field(default_factory=dict)


#: cell key -> times that cell was actually measured (test introspection:
#: the replay test asserts every value stays at 1).
MEASUREMENTS: "collections.Counter[str]" = collections.Counter()

_cache: Dict[str, Choice] = {}
#: pre-built source="cached" views of _cache entries, so the hot replay
#: path (every sort call after the first) is one dict hit, not a
#: dataclasses.replace allocation.
_cached_view: Dict[str, Choice] = {}
_table: Dict[str, str] = {}
_table_loaded = False
_cell_key_memo: Dict[Tuple, str] = {}


INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def interpret_default() -> bool:
    """Pallas interpret mode for every kernel entry point: on exactly when
    the backend is CPU (no Mosaic compiler). ``REPRO_PALLAS_INTERPRET=0``
    forces compiled kernels (compiling for a described, unattached TPU from
    a CPU host); ``=1`` is refused on any backend but the CPU, so a device
    run can never quietly execute the interpreter."""
    backend = jax.default_backend()
    env = os.environ.get(INTERPRET_ENV)
    if env is None:
        return backend == "cpu"
    interpret = env not in ("0", "false", "False")
    if interpret and backend != "cpu":
        raise RuntimeError(
            f"{INTERPRET_ENV}={env!r} on the {backend!r} backend: the Pallas "
            f"interpreter is for CPU hosts only; unset it to run the "
            f"compiled kernels")
    return interpret


def cell_key(num_segments: int, segment_len: int, dtype, kv: bool,
             interpret: Optional[bool] = None, words: int = 1) -> str:
    """Stable string id of an autotune cell — also the JSON table key. A
    compound key of ``words`` word planes keys its dtype as
    ``<dtype>x<words>`` (``kv|int32x3|...``), so a one-word pick is never
    replayed for it. Memoized: this sits on the per-call sort dispatch
    path."""
    interp = interpret_default() if interpret is None else interpret
    backend = jax.default_backend()
    memo_k = (num_segments, segment_len, dtype, kv, interp, backend, words)
    key = _cell_key_memo.get(memo_k)
    if key is None:
        mode = "interp" if interp else "compiled"
        name = jnp.dtype(dtype).name + (f"x{words}" if words > 1 else "")
        key = (f"{'kv' if kv else 'keys'}|{name}"
               f"|{num_segments}x{segment_len}|{backend}|{mode}")
        _cell_key_memo[memo_k] = key
    return key


def reset() -> None:
    """Drop every cached choice, loaded table and measurement count
    (tests; also lets a long-lived process re-tune after backend changes)."""
    _cache.clear()
    _cached_view.clear()
    _table.clear()
    _cell_key_memo.clear()
    MEASUREMENTS.clear()
    global _table_loaded
    _table_loaded = False


def is_stable(algo: str) -> bool:
    return algo in STABLE_ALGOS


def load_table(table: Mapping[str, str]) -> None:
    """Pre-load ``cell key -> algo`` choices (e.g. the ``autotune_table``
    entry of BENCH_kernels.json). Keys for other backends/modes are kept but
    never match, so one file can carry several backends' tables."""
    for k, v in table.items():
        algo = v["algo"] if isinstance(v, Mapping) else v
        if algo in ALGOS:
            _table[str(k)] = algo


def export_table() -> Dict[str, Dict]:
    """JSON-ready ``cell key -> {algo, source, melem, skipped}`` snapshot of
    every resolved cell (the benchmark persists this)."""
    return {k: {"algo": c.algo, "source": c.source,
                "melem": dict(c.melem), "skipped": dict(c.skipped)}
            for k, c in _cache.items()}


def _load_table_env() -> None:
    global _table_loaded
    if _table_loaded:
        return
    _table_loaded = True
    path = os.environ.get(TABLE_ENV)
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return
    results = doc.get("results", doc)
    table = results.get("autotune_table", {})
    load_table(table.get("entries", table) if isinstance(table, Mapping)
               else {})


def _synth(num_segments: int, segment_len: int, dtype, kv: bool,
           words: int = 1):
    rng = np.random.default_rng(0)
    dtype = jnp.dtype(dtype)
    shape = (num_segments, segment_len)
    if dtype == jnp.float32:
        keys = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    elif dtype == jnp.uint32:
        keys = jnp.asarray(
            rng.integers(0, 1 << 32, size=shape, dtype=np.uint64)
            .astype(np.uint32))
    else:
        keys = jnp.asarray(
            rng.integers(0, (1 << 31) - 1, size=shape, dtype=np.int64)
            .astype(np.int32))
    if words > 1:
        # a compound key: uniform 32-bit words behind the first
        keys = (keys,) + tuple(jnp.asarray(
            rng.integers(-(1 << 31), 1 << 31, size=shape, dtype=np.int64)
            .astype(np.int32)) for _ in range(words - 1))
    if not kv:
        return (keys,)
    vals = jnp.arange(num_segments * segment_len,
                      dtype=jnp.int32).reshape(shape)
    return keys, vals


def _candidate(algo: str, kv: bool, interpret: bool) -> Callable:
    # kernel modules import this one (interpret_default), so import lazily
    from repro.kernels.bitonic_sort import (sort_kv_segments_pallas,
                                            sort_segments_pallas)
    from repro.kernels.radix_sort import (sort_kv_segments_radix,
                                          sort_segments_radix)
    if algo == "oracle":
        fn = ref.sort_kv_segments_ref if kv else ref.sort_segments_ref
        return jax.jit(fn)
    if algo == "bitonic":
        if kv:
            return lambda k, v: sort_kv_segments_pallas(
                k, v, interpret=interpret)
        return lambda k: sort_segments_pallas(k, interpret=interpret)
    if kv:
        return lambda k, v: sort_kv_segments_radix(k, v, interpret=interpret)
    return lambda k: sort_segments_radix(k, interpret=interpret)


def _time(fn: Callable, args) -> float:
    """Best-of-N wall time (first call compiles and is discarded)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(_MEASURE_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _envelope(algo: str, num_segments: int, segment_len: int, dtype,
              interpret: bool, planes: int = 2) -> Optional[str]:
    """Why ``algo`` cannot run this cell of ``planes`` operands (key words
    and values), or None. These are the only reasons a candidate is
    skipped; any other failure propagates."""
    from repro.kernels.bitonic_sort import bitonic_supported
    from repro.kernels.radix_sort import radix_supported
    if algo == "bitonic":
        return bitonic_supported(segment_len, planes=planes)
    if algo != "radix":
        return None
    reason = radix_supported(segment_len)
    if reason is None and jnp.dtype(dtype) not in (
            jnp.int32, jnp.uint32, jnp.float32):
        reason = f"unsupported key dtype {jnp.dtype(dtype).name}"
    n_elem = num_segments * segment_len
    if reason is None and interpret and (
            segment_len > _RADIX_MEASURE_MAX_SEGLEN
            or n_elem > _RADIX_MEASURE_MAX_ELEMS):
        reason = (f"interpret-mode measurement budget: "
                  f"{num_segments}x{segment_len} exceeds "
                  f"{_RADIX_MEASURE_MAX_ELEMS} elems / "
                  f"{_RADIX_MEASURE_MAX_SEGLEN} seg-len "
                  f"(matmul permutation is emulated on CPU)")
    return reason


def _measure(num_segments: int, segment_len: int, dtype, kv: bool,
             interpret: bool, words: int = 1) -> Choice:
    args = _synth(num_segments, segment_len, dtype, kv, words)
    n_elem = num_segments * segment_len
    melem: Dict[str, float] = {}
    skipped: Dict[str, str] = {}
    for algo in ALGOS:
        reason = _envelope(algo, num_segments, segment_len, dtype, interpret,
                           planes=words + 1)
        if reason is not None:
            skipped[algo] = reason
            continue
        melem[algo] = n_elem / _time(_candidate(algo, kv, interpret),
                                     args) / 1e6
    best = max(melem, key=lambda a: melem[a])
    return Choice(best, "measured", melem=melem, skipped=skipped)


def choose(num_segments: int, segment_len: int, dtype, *, kv: bool = True,
           interpret: Optional[bool] = None, words: int = 1) -> Choice:
    """Resolve the sort algorithm for one cell (see module docstring for
    the resolution order); ``words`` counts a compound key's word planes
    (every candidate orders by all of them). Safe to call during tracing:
    measurement runs jitted candidates on synthetic concrete inputs."""
    forced = os.environ.get(FORCE_ENV)
    if forced:
        if forced not in ALGOS:
            raise ValueError(f"{FORCE_ENV}={forced!r}: expected one of "
                             f"{ALGOS}")
        return Choice(forced, "forced")
    interp = interpret_default() if interpret is None else interpret
    key = cell_key(num_segments, segment_len, dtype, kv, interp, words)
    hit = _cached_view.get(key)
    if hit is not None:
        return hit
    _load_table_env()
    if key in _table:
        choice = Choice(_table[key], "table")
    elif (num_segments * segment_len < MIN_MEASURE_ELEMS
          or os.environ.get(MEASURE_ENV) == "0"):
        choice = Choice("oracle", "static")
    else:
        choice = _measure(num_segments, segment_len, dtype, kv, interp,
                          words)
        if choice.source == "measured":
            MEASUREMENTS[key] += 1
    _cache[key] = choice
    _cached_view[key] = dataclasses.replace(choice, source="cached")
    return choice
