"""End-to-end behaviour tests for the full Sector/Sphere system (paper §3.1
pseudo-code, §3.6 inverted index, and checkpoint-restart training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import synthetic_tokens, upload_token_dataset, \
    SectorDataPipeline
from repro.models import build
from repro.sector import (Master, NodeAddress, ReplicationDaemon,
                          SectorClient, SecurityServer, SlaveNode, Topology)
from repro.sphere.engine import SphereProcess
from repro.sphere.spe import SPE
from repro.train.checkpoint import SectorCheckpointer
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.trainer import build_train_step


@pytest.fixture
def deployment(tmp_path):
    sec = SecurityServer()
    sec.add_user("u", "pw")
    sec.allow_slaves("10.0.0.0/8")
    m = Master(sec, replication_factor=2)
    topo = Topology(pods=1, racks=2, nodes_per_rack=3)
    for i, addr in enumerate(topo.all_addresses()):
        m.register_slave(SlaveNode(i, addr, str(tmp_path / f"s{i}"),
                                   ip=f"10.0.0.{i + 1}"))
    c = SectorClient(m, "u", "pw", client_addr=NodeAddress(0, 0, 0))
    return m, c, ReplicationDaemon(m)


def test_sphere_process_find_brown_dwarfs(deployment):
    """The paper's §3.1 example: apply findBrownDwarf to every 'image'
    record of a sliced dataset; one SPE crashes mid-run and its segments are
    re-executed elsewhere (no data loss, no duplicates)."""
    m, c, daemon = deployment
    rng = np.random.default_rng(0)
    record_bytes = 64
    slices = [rng.integers(0, 256, size=(50, record_bytes), dtype=np.uint8)
              for _ in range(4)]
    c.upload_dataset("/sdss/slice", [s.tobytes() for s in slices])
    daemon.run_until_stable()

    def find_brown_dwarf(records: np.ndarray) -> np.ndarray:
        return records[:, 0][records[:, 0] > 200]  # "detect" bright pixels

    # SPE 0 dies on its FIRST segment (locality assignment may give a
    # given SPE only one segment, so a later fail_after might never fire)
    spes = [SPE(i, m.slaves[i].address, m, c.session_id,
                fail_after=0 if i == 0 else None)
            for i in range(4)]
    proc = SphereProcess(m, c.session_id, spes)
    result = proc.run([f"/sdss/slice.{i:05d}" for i in range(4)],
                      find_brown_dwarf, record_bytes)
    assert not result.errors
    got = np.sort(result.concat())
    want = np.sort(np.concatenate([find_brown_dwarf(s) for s in slices]))
    np.testing.assert_array_equal(got, want)
    assert result.retries >= 1  # the crash was absorbed


def test_sphere_bucket_output_inverted_index(deployment):
    """§3.6: two-stage inverted index via buckets. Stage 1 hashes words to
    buckets; stage 2 aggregates per bucket."""
    m, c, daemon = deployment
    rng = np.random.default_rng(1)
    # "web pages": records of (word, page) uint8 pairs
    pages = [rng.integers(0, 26, size=(40, 2), dtype=np.uint8)
             for _ in range(3)]
    for i, p in enumerate(pages):
        p[:, 1] = i
    c.upload_dataset("/web/page", [p.tobytes() for p in pages])

    n_buckets = 4
    spes = [SPE(i, m.slaves[i].address, m, c.session_id) for i in range(3)]
    proc = SphereProcess(m, c.session_id, spes)

    def extract(records):
        return records.reshape(-1, 2)

    def bucket_fn(out):
        return {b: out[out[:, 0] % n_buckets == b] for b in range(n_buckets)}

    stage1 = proc.run([f"/web/page.{i:05d}" for i in range(3)], extract,
                      record_bytes=2, bucket_fn=bucket_fn,
                      num_buckets=n_buckets)
    # stage 2: per-bucket aggregation into word -> sorted page list
    index = {}
    for b, recs in stage1.outputs.items():
        recs = recs.reshape(-1, 2)
        for w in np.unique(recs[:, 0]):
            index[int(w)] = sorted(set(recs[recs[:, 0] == w][:, 1].tolist()))
    want = {}
    for i, p in enumerate(pages):
        for w in p[:, 0]:
            want.setdefault(int(w), set()).add(i)
    assert index == {k: sorted(v) for k, v in want.items()}


def test_train_checkpoint_restart_continuity(deployment):
    """Kill the 'job' mid-training, restore from Sector, verify bitwise
    state continuity (same loss trajectory after restart)."""
    m, c, daemon = deployment
    cfg = get_smoke_config("tinyllama_1_1b")
    model = build(cfg)
    toks = synthetic_tokens(40_000, cfg.vocab)
    upload_token_dataset(c, "/corpus/ckpt", toks, num_slices=4)
    pipe = SectorDataPipeline(m, c, "/corpus/ckpt", batch=4, seq_len=32,
                              seed=7)
    batches = [b for _, b in zip(range(20), iter(pipe))]
    assert len(batches) == 20
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(build_train_step(model, AdamWConfig(lr=1e-3,
                                                       warmup_steps=0,
                                                       total_steps=20), None))
    ck = SectorCheckpointer(c, "/ckpt/job", num_slices=4)

    ref_losses = []
    for i, b in enumerate(batches):
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, metrics = step(params, opt, jb)
        ref_losses.append(float(metrics["loss"]))
        if i == 9:
            ck.save(10, {"params": params, "opt": opt})
            daemon.run_until_stable()

    # "crash": throw everything away, restore, replay the tail
    like = {"params": params, "opt": opt}
    restored, s = ck.restore(like)
    assert s == 10
    p2, o2 = restored["params"], restored["opt"]
    for i, b in enumerate(batches[10:]):
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        p2, o2, metrics = step(p2, o2, jb)
        assert float(metrics["loss"]) == pytest.approx(
            ref_losses[10 + i], rel=1e-5), i


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.cache import CHECKOUT_DIR, enable_compile_cache
used = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == used, used
print(used)
if used != str(CHECKOUT_DIR):     # never write into the checkout here
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(7)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(env_dir, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and receives the cache; without it
    the entry points use the checkout's fixed .jax_cache directory."""
    import os
    import subprocess
    import sys
    from repro.launch.cache import CHECKOUT_DIR

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(CHECKOUT_DIR.parent / "src")
    want = str(CHECKOUT_DIR)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = want = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == want
    if env_dir:
        assert any((tmp_path / "cache").iterdir())
