"""sphere_map: apply a User-Defined Function to every segment (paper §3.2-3.3).

"each element in the input data array is processed independently by the same
processing function using multiple computing units" — the stream-processing
paradigm. A device plays the SPE role; ``shard_map`` gives the UDF its local
segment; the traced jaxpr plays the role of the ``.so`` UDF library the paper
ships to each SPE.

This is the low-level SPMD primitive **under** the unified dataflow layer:
multi-stage programs (map -> shuffle -> reduce/sort) should be written as a
:class:`repro.sphere.dataflow.Dataflow`, which runs unmodified on either the
compiled SPMD executor or the host Sector/SPE executor. ``sphere_map``
remains the direct escape hatch for one-shot segment UDFs with arbitrary
(non-record) outputs.

Supports the paper's extensions:
- multiple input streams (``sphere_map(f, [a, b], ...)`` == ``f(A[], B[])``);
- record-wise, group-wise or whole-segment UDFs (the UDF sees the entire
  local segment and may reduce/expand it);
- bucket output via :func:`repro.core.shuffle.sphere_shuffle` composed inside
  the UDF (see :mod:`repro.sphere.dataflow` for the canonical use).
"""

from __future__ import annotations

from typing import Callable, Sequence, Union

import jax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.stream import SphereStream

Arrays = Union[jax.Array, Sequence[jax.Array]]


def sphere_map(
    udf: Callable,
    streams: Union[SphereStream, Sequence[SphereStream]],
    mesh: Mesh,
    axis: str = "data",
    out_axis: str | None = "data",
    check_vma: bool = False,
):
    """Run ``udf`` on each segment of the input stream(s).

    Args:
      udf: function of one local segment per input stream -> local output
        (an array or pytree of arrays). Runs per-device.
      streams: one or more SphereStreams sharded along ``axis``.
      mesh: the device mesh.
      axis: mesh axis name the stream is sharded over.
      out_axis: mesh axis of the output sharding (None = replicated output,
        e.g. for segment-level reductions followed by a psum inside the UDF).
    Returns:
      SphereStream wrapping the UDF output.
    """
    single = isinstance(streams, SphereStream)
    stream_list = [streams] if single else list(streams)
    in_specs = tuple(P(axis) for _ in stream_list)
    out_spec = P(out_axis) if out_axis is not None else P()

    mapped = shard_map(
        udf, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=check_vma,
    )
    out = mapped(*[s.data for s in stream_list])
    template = stream_list[0]
    # a record-wise UDF (leading dim preserved, same sharding) keeps the
    # input's validity mask; any reshaping UDF invalidates it
    valid = None
    if out_axis == axis and template.valid is not None:
        leaves = jax.tree.leaves(out)
        if leaves and all(
                l.ndim and l.shape[0] == template.num_records for l in leaves):
            valid = template.valid
    return template.with_data(out, valid)
