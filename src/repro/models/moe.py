"""Mixture-of-Experts FFN: top-k router + three dispatch implementations.

  * ``dense``  — oracle: every expert computes every token (exact, O(E) flops);
                 correctness reference for tests.
  * ``ragged`` — sort-by-expert + ``jax.lax.ragged_dot`` grouped GEMM
                 (MegaBlocks idea, TPU-native; single-shard hot path).
  * ``ep``     — expert-parallel via ``shard_map`` over the "model" mesh axis:
                 activations replicated over model (as in the TP block), each
                 shard computes its local experts with GShard-style static
                 capacity, one psum over "model" combines (same collective cost
                 as a TP FFN all-reduce — no all-to-all needed).

Router: softmax → top-k → renormalize over the k gates (Qwen/Mixtral style),
with the standard Switch load-balance auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


def init(key, cfg) -> Dict[str, Any]:
    d = cfg.d_model
    E = cfg.n_experts
    f = cfg.moe_dff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), scale=0.02),
        "w_gate": dense_init(ks[1], (E, d, f)),
        "w_up": dense_init(ks[2], (E, d, f)),
        "w_down": dense_init(ks[3], (E, f, d), in_axis=1),
    }
    if cfg.dense_residual:
        from repro.models.layers import mlp_init
        p["dense"] = mlp_init(ks[4], d, cfg.d_ff)
    return p


def _route(params, cfg, xf):
    """xf [T,d] → (gates [T,k], idx [T,k] int32, aux scalar)."""
    # bf16 GEMM with fp32 accumulation — avoids materializing an fp32 copy of
    # the [T, d] activations just for the router
    logits = jnp.einsum("td,de->te", xf, params["router"].astype(xf.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # [T,E]
    top_p, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)                             # [E]
    ce = jnp.mean(
        (jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates.astype(xf.dtype), idx.astype(jnp.int32), aux


def _swiglu_batched(x_disp, wg, wu, wd):
    """x_disp [E,C,d] × per-expert weights → [E,C,d]."""
    dt = x_disp.dtype
    g = jnp.einsum("ecd,edf->ecf", x_disp, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", x_disp, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))


# ---------------------------------------------------------------------------
def apply_dense(params, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: all experts on all tokens, combined by gates."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    gates, idx, aux = _route(params, cfg, xf)
    dt = x.dtype
    g = jnp.einsum("td,edf->etf", xf, params["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->etf", xf, params["w_up"].astype(dt))
    y_all = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, params["w_down"].astype(dt))
    comb = jnp.zeros((xf.shape[0], cfg.n_experts), dt)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], idx].set(gates)
    y = jnp.einsum("te,etd->td", comb, y_all)
    y = _maybe_dense_residual(params, cfg, xf, y)
    return y.reshape(B, S, d), aux


def apply_ragged(params, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort + ragged_dot grouped GEMM (single shard)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    gates, idx, aux = _route(params, cfg, xf)
    flat = idx.reshape(-1)                                    # [T*k]
    order = jnp.argsort(flat, stable=True)
    token_of = order // k
    xs = xf[token_of]                                         # [T*k, d]
    group_sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    dt = x.dtype
    g = jax.lax.ragged_dot(xs, params["w_gate"].astype(dt), group_sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"].astype(dt), group_sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(g) * u, params["w_down"].astype(dt), group_sizes)
    inv = jnp.argsort(order)                                  # unsort
    y = y[inv] * gates.reshape(-1, 1)
    y = y.reshape(T, k, d).sum(axis=1)
    y = _maybe_dense_residual(params, cfg, xf, y)
    return y.reshape(B, S, d), aux


def _ep_local(params, cfg, x, E_loc: int, capacity: int, axis: str,
              fsdp_axes=(), data_axes=("data",)):
    """Body run per (data, model) shard inside shard_map.

    Expert weights arrive EP-sharded on E (model axis) and ZeRO-3-sharded on
    the hidden dim over the data axes; they are all-gathered here layer-by-
    layer (the FSDP collective, visible in the roofline), used, and dropped.
    """
    B, S, d = x.shape
    dt = x.dtype
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if fsdp_axes:
        wg = jax.lax.all_gather(wg.astype(dt), fsdp_axes, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu.astype(dt), fsdp_axes, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd.astype(dt), fsdp_axes, axis=1, tiled=True)
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    k = cfg.top_k
    gates, idx, aux = _route(params, cfg, xf)                 # router replicated
    aux = jax.lax.pmean(aux, tuple(data_axes) + (axis,) if data_axes else axis)
    e0 = jax.lax.axis_index(axis) * E_loc
    flat = idx.reshape(-1)                                    # [T*k]
    gflat = gates.reshape(-1)
    local = (flat >= e0) & (flat < e0 + E_loc)
    lidx = jnp.where(local, flat - e0, E_loc)                 # E_loc = drop bucket
    # ---- sort-based dispatch (MegaBlocks-style): small index tables + row
    # gathers.  (A scatter of [T·k, d] row updates lowers to elementwise
    # scatters with [T·k, d] u32 index tensors — gigabytes of pure index.)
    order = jnp.argsort(lidx, stable=True)                    # assignments by expert
    sorted_lidx = lidx[order]
    counts = jnp.bincount(lidx, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * k) - starts[sorted_lidx]            # slot within expert
    token_of_sorted = (order // k).astype(jnp.int32)
    valid = (sorted_lidx < E_loc) & (rank < capacity)
    # slot→token table [E_loc, C]; sentinel T = zero row of xf_pad
    slot_token = jnp.full((E_loc, capacity), T, jnp.int32)
    slot_token = slot_token.at[sorted_lidx, rank.astype(jnp.int32)].set(
        jnp.where(valid, token_of_sorted, T), mode="drop")
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    x_disp = xf_pad[slot_token]                               # [E_loc, C, d]
    y_disp = _swiglu_batched(x_disp, wg, wu, wd)
    # combine: per-assignment row gather (dropped → 0), weight, sum over k
    slot_of = jnp.zeros((T * k,), jnp.int32).at[order].set(rank.astype(jnp.int32))
    kept = (lidx < E_loc) & (slot_of < capacity)
    y_tok = y_disp[jnp.minimum(lidx, E_loc - 1),
                   jnp.minimum(slot_of, capacity - 1)]        # [T*k, d]
    y_tok = jnp.where(kept[:, None], y_tok, 0.0) * gflat[:, None]
    y = y_tok.reshape(T, k, d).sum(axis=1)
    if cfg.dense_residual and "dense" in params:
        # arctic parallel dense MLP: f-sharded over the model axis, its partial
        # sums ride the same psum as the expert combine
        dn = params["dense"]
        hdn = jax.nn.silu(xf @ dn["w_gate"].astype(dt)) * (xf @ dn["w_up"].astype(dt))
        y = y + hdn @ dn["w_down"].astype(dt)
    y = jax.lax.psum(y, axis)
    return y.reshape(B, S, d), aux


def apply_ep(params, cfg, x, mesh, data_axes=("data",), model_axis="model",
             capacity_factor: float = 1.25, fsdp: bool = True,
             remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel dispatch via shard_map.  x replicated over model axis;
    expert weights P(model, ·, data…) = EP × ZeRO-3."""
    E = cfg.n_experts
    ep = mesh.shape[model_axis]
    assert E % ep == 0, (E, ep)
    E_loc = E // ep
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    bshard = x.shape[0] % n_data == 0
    if not bshard:
        n_data = 1              # batch-1 decode: tokens replicated over data
    T_loc = (x.shape[0] // n_data) * x.shape[1]
    capacity = max(8, int(math.ceil(T_loc * cfg.top_k / E * capacity_factor)))
    dspec = (tuple(data_axes) if len(data_axes) > 1 else data_axes[0]) if bshard else None
    f = cfg.moe_dff or cfg.d_ff
    fsdp_axes = tuple(data_axes) if (fsdp and f % math.prod(
        mesh.shape[a] for a in data_axes) == 0) else ()
    fspec = (tuple(data_axes) if len(data_axes) > 1 else data_axes[0]) if fsdp_axes else None

    wspec = {
        "router": P(),
        "w_gate": P(model_axis, None, fspec),
        "w_up": P(model_axis, None, fspec),
        "w_down": P(model_axis, fspec, None),
    }
    if "dense" in params:
        wspec["dense"] = {"w_gate": P(None, model_axis),
                          "w_up": P(None, model_axis),
                          "w_down": P(model_axis, None)}

    fn = jax.shard_map(
        lambda p, xx: _ep_local(p, cfg, xx, E_loc, capacity, model_axis,
                                fsdp_axes=fsdp_axes,
                                data_axes=tuple(data_axes) if bshard else ()),
        mesh=mesh,
        in_specs=(wspec, P(dspec, None, None)),
        out_specs=(P(dspec, None, None), P()),
        check_vma=False,
    )
    if remat:
        # §Perf iteration: jax.checkpoint does NOT see through shard_map from
        # an enclosing scope, so without this every MoE internal ([E_loc,C,f]
        # hiddens, dispatch gathers) is saved per layer for the backward —
        # tens of GiB at 94 layers.  Remat here keeps only (x, weights).
        fn = jax.checkpoint(fn)
    return fn(params, x)


def _maybe_dense_residual(params, cfg, xf, y):
    if cfg.dense_residual and "dense" in params:
        from repro.models.layers import mlp
        y = y + mlp(params["dense"], xf)
    return y


def apply(params, cfg, x, impl: str = "ragged", mesh=None,
          data_axes=("data",), model_axis="model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    if impl == "dense":
        return apply_dense(params, cfg, x)
    if impl == "ragged":
        return apply_ragged(params, cfg, x)
    if impl == "ep":
        return apply_ep(params, cfg, x, mesh, data_axes, model_axis)
    raise ValueError(impl)
