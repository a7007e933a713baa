"""Mixture-of-Experts with Sphere bucket-shuffle dispatch.

The paper's bucket shuffle (§3.2) *is* expert dispatch: record = token,
bucket = expert, capacity factor = the scheduler's segment-size clamp
(§3.5.1), dropped-on-overflow = the same bounded-skew contract. The
``sphere`` implementation routes tokens through
:func:`repro.core.shuffle.sphere_shuffle` / ``sphere_combine`` over the
``model`` mesh axis (expert parallelism); the ``dense`` implementation is the
einsum/one-hot dispatch baseline (Switch-Transformer style) used for small
token counts (decode) and as the paper-technique-ablation baseline.

Experts are zero-padded to a multiple of the expert-parallel axis (qwen2-moe:
60 -> 64); the router never selects padding experts.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.core.shuffle import ShufflePlan
from repro.kernels.ops import partition_pack
from repro.models.layers import COMPUTE_DTYPE, dense_init


def padded_experts(cfg: ModelConfig, tp: int = 16) -> int:
    e = cfg.num_experts
    return ((e + tp - 1) // tp) * tp


def moe_init(key, cfg: ModelConfig, tp: int = 16) -> Tuple[Dict, Dict]:
    e_pad = padded_experts(cfg, tp)
    d, f = cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 7)

    def experts(k):
        w = jax.random.normal(k, (e_pad, d, f), jnp.float32) * (d ** -0.5)
        return w.at[cfg.num_experts:].set(0.0)

    params = {
        "router": dense_init(ks[0], d, cfg.num_experts, scale=0.02),
        "w_gate": experts(ks[1]),
        "w_up": experts(ks[2]),
        "w_down": jax.random.normal(ks[3], (e_pad, f, d), jnp.float32)
                  * (f ** -0.5),
    }
    specs = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff * cfg.n_shared_experts
        params["ws_gate"] = dense_init(ks[4], d, fs)
        params["ws_up"] = dense_init(ks[5], d, fs)
        params["ws_down"] = dense_init(ks[6], fs, d, scale=fs ** -0.5)
        params["shared_gate"] = dense_init(ks[4], d, 1, scale=0.02)
        specs.update({"ws_gate": P(None, "model"), "ws_up": P(None, "model"),
                      "ws_down": P("model", None), "shared_gate": P(None, None)})
    return params, specs


def _route(params, x_flat, cfg: ModelConfig):
    """Router: top-k expert ids + renormalized probs (fp32)."""
    logits = (x_flat.astype(jnp.float32) @ params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balance aux (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32), 1),
        axis=0) / cfg.top_k
    aux = cfg.num_experts * jnp.sum(me * ce)
    return top_i.astype(jnp.int32), top_p.astype(jnp.float32), aux


def _expert_ffn(w_gate, w_up, w_down, xe):
    """xe: (E_loc, C, d) tokens grouped per local expert."""
    xe = xe.astype(COMPUTE_DTYPE)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(COMPUTE_DTYPE)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up.astype(COMPUTE_DTYPE))
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(COMPUTE_DTYPE))


def _shared_ffn(params, x):
    x = x.astype(COMPUTE_DTYPE)
    h = jax.nn.silu(x @ params["ws_gate"].astype(COMPUTE_DTYPE))
    h = h * (x @ params["ws_up"].astype(COMPUTE_DTYPE))
    out = h @ params["ws_down"].astype(COMPUTE_DTYPE)
    g = jax.nn.sigmoid((x @ params["shared_gate"].astype(COMPUTE_DTYPE))
                       .astype(jnp.float32))
    return out * g.astype(COMPUTE_DTYPE)


# -- sphere (bucket shuffle) dispatch ----------------------------------------------

def _moe_sphere_local(params_local, x_local, cfg: ModelConfig,
                      plan: ShufflePlan):
    """Runs inside shard_map. x_local: (b, s_loc, d) — tokens sharded over
    the expert-parallel axes so every rank contributes distinct tokens. The
    plan decides the wire pattern: flat all_to_all over one axis, or the
    two-level (dc, node) WAN shuffle for cross-data-center expert
    parallelism."""
    b, s_loc, d = x_local.shape
    n = b * s_loc
    x_flat = x_local.reshape(n, d)
    top_i, top_p, aux = _route(params_local, x_flat, cfg)

    k = cfg.top_k
    ep = plan.num_devices
    # records: token replicated k times, carrying its routing prob.
    # bf16 on the wire: halves the all-to-all bytes (§Perf H4); the prob
    # column round-trips bf16 with ~3 decimal digits — enough for combine
    # weighting (top-k probs are O(0.1)).
    rec = jnp.concatenate(
        [jnp.repeat(x_flat, k, axis=0).astype(COMPUTE_DTYPE),
         top_p.reshape(n * k, 1).astype(COMPUTE_DTYPE)], axis=1)
    buckets = top_i.reshape(n * k)
    num_buckets = plan.num_buckets
    res = plan.shuffle(rec, buckets)

    # local regroup (stage C of the shuffle, on-device): received rows ->
    # (E_loc, C2, d) per local expert, via the same fused O(n)
    # partition/pack the send path uses (no sort in the dispatch hot loop)
    e_loc = num_buckets // ep
    me = plan.device_index()
    flat = res.data.reshape(-1, d + 1)
    fvalid = res.valid.reshape(-1)
    fbucket = res.bucket.reshape(-1) - me * e_loc       # local expert idx
    n_recv = flat.shape[0]
    c2 = int(n_recv / e_loc * cfg.capacity_factor) + 1
    dest = jnp.where(fvalid, fbucket, e_loc)            # invalid -> overflow
    (grouped,), in_rng, origin, _ = partition_pack(
        [flat], dest, e_loc, c2, use_pallas=plan.use_pallas)
    xe, pe = grouped[..., :d], grouped[..., d]

    ye = _expert_ffn(params_local["w_gate"], params_local["w_up"],
                     params_local["w_down"], xe)
    ye = ye * pe[..., None].astype(COMPUTE_DTYPE)       # weight by router prob
    ye = ye * in_rng[..., None].astype(COMPUTE_DTYPE)

    # inverse regroup: back to the received-row layout (origin = the source
    # row of each (expert, slot), the exact inverse gather)
    back = jnp.zeros((n_recv + 1, d), COMPUTE_DTYPE)
    scatter_rows = jnp.where(in_rng, origin, n_recv)
    back = back.at[scatter_rows.reshape(-1)].set(
        ye.reshape(-1, d), mode="drop")[:n_recv]
    processed = back.reshape(res.data.shape[0], -1, d)

    # combine back to the n*k record rows (src_pos indexes the k-duplicated
    # record array), then sum each token's k expert contributions
    combined, _ = plan.combine(processed, res, n * k)
    out = combined.reshape(n, k, d).sum(axis=1).reshape(b, s_loc, d)
    for a in plan.pmean_axes():
        aux = jax.lax.pmean(aux, a)
    dropped = res.dropped
    return out, aux, dropped


def moe_apply_sphere(params, x, cfg: ModelConfig, mesh: Mesh,
                     dp_axes: Sequence[str], tp_axis: str = "model",
                     ep_axes: Optional[Sequence[str]] = None,
                     chunks: int = 1):
    """x: (B, S, d) with S divisible by the tp axis size.

    ``ep_axes=(dc_axis, node_axis)`` spreads the experts over *both* axes —
    wide-area expert parallelism, with tokens crossing the DC boundary via
    the hierarchical two-level shuffle (batch shards over the dc axis,
    sequence over the node axis). ``chunks=W`` pipelines the dispatch
    shuffle: the token stream splits into W chunks whose partition/pack
    overlaps the previous chunk's all_to_all (send-buffer memory drops ~W×).
    """
    b, s, d = x.shape
    k = cfg.top_k
    if ep_axes is not None:
        ep_axes = tuple(ep_axes)
        ep = math.prod(mesh.shape[a] for a in ep_axes)
        n_local = (b // mesh.shape[ep_axes[0]]) * (s // mesh.shape[ep_axes[1]])
        x_spec = P(ep_axes[0], ep_axes[1], None)
        w_spec = P(ep_axes, None, None)
    else:
        ep_axes = (tp_axis,)
        ep = mesh.shape[tp_axis]
        dp = tuple(dp_axes)
        n_local = (b // math.prod(mesh.shape[a] for a in dp)) * (s // ep)
        x_spec = P(dp, tp_axis, None)
        w_spec = P(tp_axis, None, None)
    plan = ShufflePlan.for_mesh(mesh, padded_experts(cfg, ep), n_local * k,
                                cfg.capacity_factor, ep_axes, chunks=chunks)

    def body(p, xin):
        out, aux, dropped = _moe_sphere_local(p, xin, cfg, plan)
        return out, aux, dropped

    routed = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
    param_specs = {"router": P(None, None), "w_gate": w_spec,
                   "w_up": w_spec, "w_down": w_spec}

    out, aux, dropped = shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(routed, x)
    shared = _shared_ffn(params, x) if cfg.n_shared_experts else 0.0
    return out + shared, {"moe_aux": aux, "moe_dropped": dropped}


# -- dense (einsum one-hot) dispatch ------------------------------------------------

def moe_apply_dense(params, x, cfg: ModelConfig):
    """Switch-style capacity dispatch with one-hot einsums; no shard_map.
    Used for decode (tiny token counts) and as the non-paper baseline."""
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)
    top_i, top_p, aux = _route(params, x_flat, cfg)
    e_pad = params["w_gate"].shape[0]
    k = cfg.top_k
    cap = max(int(n * k / cfg.num_experts * cfg.capacity_factor), 1)

    oh = jax.nn.one_hot(top_i, e_pad, dtype=jnp.float32)       # (n, k, E)
    # position of each (token, slot) within its expert
    pos = jnp.cumsum(oh.reshape(n * k, e_pad), axis=0) - 1.0   # (n*k, E)
    pos = jnp.sum(pos.reshape(n, k, e_pad) * oh, axis=-1)      # (n, k)
    keep = pos < cap
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    disp = jnp.einsum("nke,nkc->nkec", oh, pos_oh) * keep[..., None, None]
    dispatch = jnp.sum(disp, axis=1)                           # (n, E, C)
    xe = jnp.einsum("nec,nd->ecd", dispatch, x_flat.astype(jnp.float32))
    ye = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"], xe)
    comb = jnp.einsum("nkec,nk->nec", disp, top_p)
    out = jnp.einsum("nec,ecd->nd", comb, ye.astype(jnp.float32))
    dropped = jnp.sum(1.0 - keep.astype(jnp.float32))
    out = out.reshape(b, s, d).astype(COMPUTE_DTYPE)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(params, x)
    return out, {"moe_aux": aux, "moe_dropped": dropped}


def moe_apply(params, x, cfg: ModelConfig, mesh: Optional[Mesh] = None,
              dp_axes: Sequence[str] = ("data",), tp_axis: str = "model",
              ep_axes: Optional[Sequence[str]] = None, chunks: int = 1):
    """Select implementation: sphere bucket shuffle when the sequence can be
    sharded over the expert axis, dense einsum otherwise. ``ep_axes``
    requests wide-area (two-level) expert parallelism over a (dc, node)
    axis pair — see :func:`moe_apply_sphere`.

    Like the flat gate below, ``ep_axes`` is a preference, not a demand:
    when the mesh lacks the axes or the batch/sequence don't divide them,
    this falls back to the flat or dense path silently (decode shapes hit
    this constantly). Call :func:`moe_apply_sphere` directly to get a hard
    error instead."""
    if (ep_axes is not None and mesh is not None and len(ep_axes) == 2
            and all(a in mesh.shape for a in ep_axes)):
        dcs, nodes = (mesh.shape[a] for a in ep_axes)
        if (cfg.moe_impl == "sphere" and x.shape[0] % dcs == 0
                and x.shape[1] % nodes == 0 and dcs * nodes > 1):
            return moe_apply_sphere(params, x, cfg, mesh, dp_axes, tp_axis,
                                    ep_axes=ep_axes, chunks=chunks)
    use_sphere = (
        cfg.moe_impl == "sphere" and mesh is not None
        and tp_axis in mesh.shape and x.shape[1] % mesh.shape[tp_axis] == 0
        and mesh.shape[tp_axis] > 1
    )
    if use_sphere:
        return moe_apply_sphere(params, x, cfg, mesh, dp_axes, tp_axis,
                                chunks=chunks)
    return moe_apply_dense(params, x, cfg)
