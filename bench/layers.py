"""Device time per layer of the program, from a trace and the program's
op->layer map.

The program wraps each layer's work in a named scope; at each compile it
reads the map from its optimised instructions to their layers, and each run
notes the map of the program that ran (``repro.obs.layers``). A trace names
each device op by its instruction, so the self time of every op in the
traced window (:meth:`bench.traceread.Trace.top_ops`, with its nesting
rule: a ``while`` keeps only what its body's ops leave) sums into its
layer; ops the map does not name count as :data:`UNSCOPED`. A program
without the map (one built before the scopes) gives nothing to read.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Optional

UNSCOPED = "unscoped"


def program_map() -> Optional[Dict[str, str]]:
    """The op->layer map of the program that ran last, or None."""
    try:
        from repro.obs import layers
    except ImportError:
        return None
    last = layers.last_run()
    return last[1] if last and last[1] else None


def layer_s(trace, op_layers: Dict[str, str]) -> Dict[str, float]:
    """Self time in seconds per layer over the whole traced window, mean
    over the devices."""
    out: Dict[str, float] = defaultdict(float)
    for name, seconds in trace.top_ops(top=None):
        out[op_layers.get(name.split(" ", 1)[0], UNSCOPED)] += seconds
    return dict(out)


def per_call_ms(ctx) -> Optional[Dict[str, float]]:
    """``{layer: ms per call}`` of a traced run's context, or None when
    there is no trace or no map."""
    if ctx.trace is None or not ctx.trace.devices or not ctx.calls:
        return None
    op_layers = program_map()
    if op_layers is None:
        return None
    return {k: v * 1e3 / ctx.calls
            for k, v in layer_s(ctx.trace, op_layers).items()}
