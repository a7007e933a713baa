"""Reduction from a ``jax.profiler`` trace to the benchmark's numbers.

A traced run wraps the part it traces in a ``bench.traced`` host span and
each call into the program in a ``bench.<driver>.<what>`` span
(``jax.profiler.TraceAnnotation``). The profiler writes one ``.xplane.pb``;
:func:`load_xspace` keeps the device planes' ops and the host's ``bench.*``
spans as plain :class:`Event` records, and :class:`Trace` reduces them:

- device busy time: the union of the op intervals on each device's
  ``XLA Ops`` line inside the traced window, averaged over the devices;
- a kernel's time: the summed durations of the ops whose names match the
  patterns its metric file lists, averaged over the devices;
- idle gaps: the stretches of the window in which no op runs, each labelled
  with the innermost ``bench.*`` host span around its middle;
- the top device ops by self time (an op's duration less the ops nested in
  it, as a ``while`` holds its body's ops).

Device and host events share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
OUTSIDE = "outside bench spans"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xspace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xspace(path: str) -> List[Event]:
    """Device-plane events and the host's ``bench.*`` spans of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


class Profile:
    """The profiler over part of a run, inside a ``bench.traced`` span:
    started on construction, reduced to a :class:`Trace` by :meth:`stop`.
    The trace is written under ``$TMPDIR`` and deleted once read."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no py calls
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()

    def stop(self) -> "Trace":
        self.span.__exit__(None, None, None)
        try:
            self._jax.profiler.stop_trace()
            return Trace(load_xspace(find_xspace(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo_text: str) -> str:
    """``fusion.42 fusion s32[12582913]`` from an op's HLO text: the
    instruction's name, its opcode and its (first) output shape."""
    if " = " not in hlo_text:
        return hlo_text[:100]
    lhs, rhs = hlo_text.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    opcode = m.group(1) if m else "?"
    shape = _LAYOUT.sub("", rhs[:m.start()] if m else rhs).strip()
    return f"{lhs.lstrip('%')} {opcode} {shape}"[:100]


class Trace:
    """The events of one traced window (see module docstring)."""

    def __init__(self, events: Sequence[Event]):
        spans = [e for e in events if e.plane == HOST_PLANE]
        windows = [e for e in spans if e.name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
        self.start_ns = min(e.start_ns for e in windows)
        self.end_ns = max(e.end_ns for e in windows)
        self.spans = [e for e in spans if e.name != WINDOW_SPAN]
        self.devices = sorted({e.plane for e in events
                               if DEVICE_PLANE.match(e.plane)})
        self._ops: Dict[str, List[Event]] = defaultdict(list)
        for e in events:
            if (e.line == OPS_LINE and e.plane in self.devices
                    and e.end_ns > self.start_ns and e.start_ns < self.end_ns):
                self._ops[e.plane].append(e)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def _clipped(self, device: str) -> List[Tuple[float, float]]:
        return [(max(e.start_ns, self.start_ns), min(e.end_ns, self.end_ns))
                for e in self._ops[device]]

    def busy_s(self) -> float:
        """Union of op intervals inside the window, mean over devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for d in self.devices
                    for s, e in _union(self._clipped(d)))
        return total / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_s(self, patterns: Sequence[str]) -> float:
        """Summed duration of the ops matching any of ``patterns`` (regular
        expressions searched in the op's HLO text), mean over devices."""
        regs = [re.compile(p) for p in patterns]
        if not self.devices:
            return 0.0
        total = sum(e.dur_ns for d in self.devices for e in self._ops[d]
                    if any(r.search(e.name) for r in regs))
        return total / len(self.devices) / 1e9

    def label(self, t_ns: float) -> str:
        """The innermost ``bench.*`` host span around ``t_ns``."""
        inside = [e for e in self.spans if e.start_ns <= t_ns < e.end_ns]
        if not inside:
            return OUTSIDE
        return min(inside, key=lambda e: e.dur_ns).name

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest stretches with no op running, on any device, as
        ``(host span, seconds)``, longest first."""
        gaps = []
        for d in self.devices:
            cursor = self.start_ns
            for s, e in _union(self._clipped(d)) + [(self.end_ns,
                                                       self.end_ns)]:
                if s > cursor:
                    gaps.append((self.label((cursor + s) / 2),
                                 (s - cursor) / 1e9))
                cursor = max(cursor, e)
        return sorted(gaps, key=lambda g: -g[1])[:top]

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Ops by self time over the window, mean over devices."""
        self_ns: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            stack: List[Tuple[float, str]] = []     # (end, name) of parents
            for e in sorted(self._ops[d], key=lambda e: (e.start_ns,
                                                         -e.dur_ns)):
                name = short_name(e.name)
                # a parent holds the ops that lie wholly inside it
                while stack and (stack[-1][0] <= e.start_ns
                                 or e.end_ns > stack[-1][0]):
                    stack.pop()
                if stack:
                    self_ns[stack[-1][1]] -= e.dur_ns
                self_ns[name] += e.dur_ns
                stack.append((e.end_ns, name))
        n = max(len(self.devices), 1)
        ranked = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
        return [(name, ns / n / 1e9) for name, ns in ranked]


def breakdown(trace: Optional[Trace]) -> Optional[dict]:
    if trace is None:
        return None
    return {"device_ops": [list(x) for x in trace.top_ops()],
            "idle_gaps": [list(x) for x in trace.idle_gaps()]}
