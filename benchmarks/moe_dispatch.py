"""MoE dispatch = Sphere bucket shuffle (paper §3.6 generalization claim).

Compares the sphere (all_to_all bucket shuffle) expert dispatch against the
dense einsum dispatch, measured on virtual devices, and reports the
collective bytes each one compiles to (the wide-area-traffic argument of the
paper, transplanted to ICI).
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List

_CODE = """
import time, dataclasses, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import moe as moe_mod
mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(get_smoke_config("qwen3_moe_30b_a3b"),
                          capacity_factor=2.0)
params, _ = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, tp=4)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, cfg.d_model), jnp.bfloat16)
xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))

def run_one(name, fn):
    with mesh:
        out = fn(); jax.block_until_ready(out[0])
        t0 = time.time(); iters = 5
        for _ in range(iters):
            out = fn(); jax.block_until_ready(out[0])
        dt = (time.time() - t0) / iters
    print(f"RESULT moe_{name} {dt*1e6:.1f}")
    return out

sphere = jax.jit(lambda p, xx: moe_mod.moe_apply_sphere(p, xx, cfg, mesh, ("data",)))
dense  = jax.jit(lambda p, xx: moe_mod.moe_apply_dense(p, xx, cfg))
o1 = run_one("sphere", lambda: sphere(params, xs))
o2 = run_one("dense",  lambda: dense(params, x))

# collective bytes of each compiled program
import re
from repro.launch.dryrun import collective_bytes
with mesh:
    h1 = sphere.lower(params, xs).compile().as_text()
    h2 = dense.lower(params, x).compile().as_text()
c1, c2 = collective_bytes(h1), collective_bytes(h2)
print(f"RESULT moe_sphere_coll_bytes {sum(c1.values())} {c1}")
print(f"RESULT moe_dense_coll_bytes {sum(c2.values())} {c2}")
"""


def run(csv: bool = True) -> List[str]:
    env = dict(os.environ)
    # semantics on 8 virtual CPU devices: the child never needs the chip,
    # which a parent that has touched JAX may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CODE], env=env,
                          capture_output=True, text=True, timeout=560)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    lines = []
    for l in proc.stdout.splitlines():
        if l.startswith("RESULT"):
            parts = l.split(maxsplit=3)
            lines.append(f"{parts[1]},{parts[2]},"
                         f"{parts[3] if len(parts) > 3 else ''}")
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
