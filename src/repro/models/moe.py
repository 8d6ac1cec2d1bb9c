"""Mixture-of-Experts block (Qwen3-MoE, DeepSeek-V3 style).

Execution (DESIGN.md §5): expert parallelism over the ``model`` axis with
activations replicated across it — each model shard owns E/|model| experts,
scatters its *local* tokens into an (E_loc, C, D) capacity buffer, runs the
expert MLPs as dense einsums, gathers back, and a single psum over ``model``
combines. Expert weights are additionally FSDP-sharded over ``data`` and
all-gathered per layer inside the shard_map body (the canonical FSDP unshard,
visible to the roofline as all-gather bytes).

Router: softmax (or sigmoid for DeepSeek-style) top-k with optional
normalization and a static aux-free bias (DeepSeek-V3's balancing bias is a
buffer, not updated here), plus an optional load-balance aux loss.

``mesh=None`` (or an absent axis) degrades to single-shard execution with the
same math — used by CPU smoke tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .common import act_fn
from .params import meta


def moe_meta(cfg, dtype):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": meta((D, E), ("embed", None), dtype, scale=0.02),
        "bias": meta((E,), (None,), jnp.float32, init="zeros"),
        "w_gate": meta((E, D, F), ("expert", "embed", "expert_mlp"), dtype),
        "w_up": meta((E, D, F), ("expert", "embed", "expert_mlp"), dtype),
        "w_down": meta((E, F, D), ("expert", "expert_mlp", "embed"), dtype),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": meta((D, Fs), ("embed", "mlp"), dtype),
            "w_up": meta((D, Fs), ("embed", "mlp"), dtype),
            "w_down": meta((Fs, D), ("mlp", "embed"), dtype),
        }
    return p


def _expert_ffn(x, wg, wu, wd, act):
    h = act_fn(act)(jnp.einsum("ecd,edf->ecf", x, wg)) * jnp.einsum(
        "ecd,edf->ecf", x, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _local_moe(x_loc, router_w, bias, wg, wu, wd, *, cfg, e_lo: int,
               capacity: int, act: str, fsdp_axis: Optional[str],
               model_axis: Optional[str]):
    """Body shared by the shard_map and single-device paths.
    x_loc: (T_loc, D); wg/wu/wd: this model-shard's experts, possibly
    FSDP-sharded on dim 1/2 (all-gathered here)."""
    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
    E_loc = wg.shape[0]
    T, D = x_loc.shape
    k = cfg.top_k

    logits = (x_loc @ router_w).astype(jnp.float32)            # (T, E)
    if cfg.router_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(scores + bias[None, :], k)     # (T, k)
    gates = jnp.take_along_axis(scores, eidx, axis=1)          # bias only routes
    if cfg.router_norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)                                   # (T*k,)
    loc_e = flat_e - e_lo
    mine = (loc_e >= 0) & (loc_e < E_loc)
    loc_e_safe = jnp.where(mine, loc_e, 0)
    onehot = (jax.nn.one_hot(loc_e_safe, E_loc, dtype=jnp.int32) *
              mine[:, None].astype(jnp.int32))                  # (T*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # exclusive
    pos_e = jnp.sum(pos * onehot, axis=1)                       # (T*k,)
    keep = mine & (pos_e < capacity)
    tok = jnp.repeat(jnp.arange(T), k)

    buf = jnp.zeros((E_loc, capacity, D), x_loc.dtype)
    buf = buf.at[jnp.where(keep, loc_e_safe, 0),
                 jnp.where(keep, pos_e, 0)].add(
        jnp.where(keep[:, None], x_loc[tok], 0))
    out_buf = _expert_ffn(buf, wg, wu, wd, act)                 # (E_loc, C, D)
    vals = out_buf[loc_e_safe, jnp.where(keep, pos_e, 0)]       # (T*k, D)
    vals = jnp.where(keep[:, None], vals, 0) * gates.reshape(-1)[:, None]
    out = jnp.zeros_like(x_loc).at[tok].add(vals)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)

    # load-balance aux (switch-style), computed on the replicated router state
    me = jnp.mean(jax.nn.softmax(logits, -1), axis=0)
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], cfg.n_experts, dtype=jnp.float32), axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return out, aux


def moe_apply(p, x, *, cfg, mesh: Optional[Mesh], batch_axes,
              capacity_factor: float = 1.25, mode: str = "train"):
    """x: (B, S, D) -> (B, S, D). Chooses sharded or local execution.

    Serving (mode != 'train', few tokens): experts shard over the FULL mesh
    when divisible — tokens are tiny at decode, expert weights dominate HBM,
    so maximal EP is the right trade (EXPERIMENTS.md §Perf iteration 2)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    act = cfg.act

    if mesh is not None and mode != "train" and B * S <= 16384:
        ep_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        while ep_axes and E % int(np.prod([mesh.shape[a] for a in ep_axes])) != 0:
            ep_axes = ep_axes[1:]
        if len(ep_axes) > 1:
            return _moe_full_ep(p, x, cfg=cfg, mesh=mesh, ep_axes=ep_axes,
                                capacity_factor=capacity_factor)

    model_ok = mesh is not None and "model" in mesh.shape and \
        mesh.shape["model"] > 1 and E % mesh.shape["model"] == 0
    data_axes = tuple(a for a in (batch_axes or ()) if mesh is not None
                      and a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    T_loc = (B // dp) * S
    capacity = int(np.ceil(T_loc * k / E * capacity_factor))
    capacity = max(capacity, 4)

    if not model_ok:
        def run_local(xf):
            return _local_moe(xf, p["router"], p["bias"], p["w_gate"],
                              p["w_up"], p["w_down"], cfg=cfg, e_lo=0,
                              capacity=capacity, act=act, fsdp_axis=None,
                              model_axis=None)
        out, aux = run_local(x.reshape(B * S, D))
        y = out.reshape(B, S, D)
    else:
        mp = mesh.shape["model"]
        E_loc = E // mp
        # expert weights are FSDP-sharded over 'data' on their D dim when the
        # param specs could shard them (divisibility); gathered per layer.
        fsdp_axis = ("data" if ("data" in mesh.shape and mesh.shape["data"] > 1
                                and D % mesh.shape["data"] == 0) else None)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(None, None), P(None),
                      P("model", "data" if fsdp_axis else None, None),
                      P("model", "data" if fsdp_axis else None, None),
                      P("model", None, "data" if fsdp_axis else None)),
            out_specs=(P(batch_axes, None, None), P()),
            check_vma=False)
        def run(x_blk, router_w, bias, wg, wu, wd):
            Bl, Sl, Dl = x_blk.shape
            e_lo = jax.lax.axis_index("model") * E_loc
            out, aux = _local_moe(x_blk.reshape(Bl * Sl, Dl), router_w, bias,
                                  wg, wu, wd, cfg=cfg, e_lo=e_lo,
                                  capacity=capacity, act=act,
                                  fsdp_axis=fsdp_axis, model_axis="model")
            axes = data_axes + ("model",)
            return out.reshape(Bl, Sl, Dl), jax.lax.pmean(aux, axes)

        y, aux = run(x, p["router"], p["bias"], p["w_gate"], p["w_up"],
                     p["w_down"])

    if cfg.n_shared_experts:
        from .common import mlp
        y = y + mlp(p["shared"], x, act)
    return y, aux


def _moe_full_ep(p, x, *, cfg, mesh, ep_axes, capacity_factor):
    """Serving-time full-mesh expert parallelism: tokens replicated (tiny),
    each device runs its E/devices experts, one psum over all EP axes."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ep = int(np.prod([mesh.shape[a] for a in ep_axes]))
    E_loc = E // ep
    T = B * S
    capacity = max(int(np.ceil(T * k / E * capacity_factor)), 4)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None), P(None),
                  P(ep_axes, None, None), P(ep_axes, None, None),
                  P(ep_axes, None, None)),
        out_specs=(P(None, None, None), P()),
        check_vma=False)
    def run(x_rep, router_w, bias, wg, wu, wd):
        e_lo = jnp.zeros((), jnp.int32)
        stride = E_loc
        for a in reversed(ep_axes):
            e_lo = e_lo + jax.lax.axis_index(a) * stride
            stride = stride * mesh.shape[a]
        out, aux = _local_moe(x_rep.reshape(T, D), router_w, bias, wg, wu, wd,
                              cfg=cfg, e_lo=e_lo, capacity=capacity,
                              act=cfg.act, fsdp_axis=None, model_axis=ep_axes)
        return out.reshape(B, S, D), jax.lax.pmean(aux, ep_axes)

    y, aux = run(x, p["router"], p["bias"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts:
        from .common import mlp
        y = y + mlp(p["shared"], x, cfg.act)
    return y, aux
