"""Terasort demo (paper Fig 3): the two-stage distributed sort as ONE
dataflow pipeline on 8 virtual devices, with the Pallas bitonic kernel as
stage 2.

The whole sort is `Dataflow.source().sort(key=..., splitters=...)`; the SPMD
executor fuses range-partition shuffle + local sort into one jit'd program
and caches the compilation, so the timed second call is pure execution.

Run:  PYTHONPATH=src python examples/terasort_demo.py
"""

import _bootstrap

_bootstrap.setup(devices=8)

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sort import hadoop_style_sort, is_globally_sorted, \
    sampled_splitters
from repro.sphere.dataflow import Dataflow, SPMDExecutor


def main() -> None:
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    n = 8 * 16_384
    keys = rng.integers(0, 2**31 - 2, size=n).astype(np.int32)
    payload = np.arange(n, dtype=np.int32)   # index into the 90-byte values
    kd, pd = jnp.asarray(keys), jnp.asarray(payload)

    with mesh:
        # non-uniform keys? sample splitters like the paper's 'more advanced
        # hashing technique' (§3.6)
        spl = sampled_splitters(kd, 8, sample_per_shard=128, mesh=mesh)
        df = Dataflow.source().sort(key=lambda r: r["key"], splitters=spl,
                                    num_buckets=8)
        print(f"pipeline: {df.describe()}")

        def run_df(executor):
            return executor.run(df, {"key": kd, "payload": pd})

        for name, fn in (
            ("sphere (pallas stage-2)",
             lambda ex=SPMDExecutor(
                 mesh, use_pallas=True, sort_algo="bitonic"): run_df(ex)),
            ("sphere (xla sort)",
             lambda ex=SPMDExecutor(
                 mesh, sort_algo="oracle"): run_df(ex)),
            ("hadoop-style (allgather)",
             lambda: hadoop_style_sort(kd, pd, mesh)),
        ):
            res = fn()                        # compile (cached in executor)
            jax.block_until_ready(jax.tree.leaves(res.records
                                  if hasattr(res, "records") else res.keys)[0])
            t0 = time.time()
            res = fn()                        # cache hit: execution only
            out_keys = (res.records["key"] if hasattr(res, "records")
                        else res.keys)
            jax.block_until_ready(out_keys)
            dt = time.time() - t0
            ok = is_globally_sorted_result(res, out_keys)
            print(f"{name:28s} {n / dt / 1e6:7.2f} Mrec/s "
                  f"sorted={ok} dropped={int(res.dropped)}")


def is_globally_sorted_result(res, out_keys) -> bool:
    if hasattr(res, "records"):               # DataflowResult
        vk = np.asarray(out_keys)[np.asarray(res.valid)]
        return bool((np.diff(vk) >= 0).all())
    return is_globally_sorted(res, 8)         # SortResult baseline


if __name__ == "__main__":
    main()
