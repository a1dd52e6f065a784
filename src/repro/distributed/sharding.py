"""Sharding plan: TP / FSDP(ZeRO-3) / EP / SP rules for every param & activation.

Mesh axes (launch/mesh.py):
  single-pod  (16, 16)        →  ("data", "model")
  multi-pod   (2, 16, 16)     →  ("pod", "data", "model")

Parallelism mapping:
  * TP   — attention heads / FFN hidden / vocab sharded over "model".
  * FSDP — the non-TP dim of every large matrix additionally sharded over
           ("pod",)+("data",) (ZeRO-3; XLA all-gathers per scan step).
  * EP   — MoE experts over "model" via shard_map (models/moe.py), expert
           hidden dim ZeRO-3-sharded over the data axes.
  * SP   — sequence (Megatron-style) sharding of the residual stream over
           "model" between blocks; GSPMD turns the per-sublayer output
           all-reduce into reduce-scatter + all-gather pairs.
  * DP   — batch over ("pod",)+("data",); for batch-1 long-context decode the
           *cache sequence* dim shards over "data" instead (context
           parallelism — softmax reductions cross shards via psum).

Head padding: archs whose head count doesn't divide TP=16 (arctic 56,
minicpm 36) are padded with zero-init heads (56→64, 36→48) — the padded
model strictly contains the original (zero wo rows ⇒ identical function);
documented in DESIGN.md §assumptions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    mesh: Mesh
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)
    fsdp: bool = True
    seq_parallel: bool = True

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def n_dp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    @property
    def dp(self):
        if not self.dp_axes:                 # tp-only serving submesh
            return None
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def plan_for_mesh(mesh: Mesh, fsdp: bool = True, seq_parallel: bool = True) -> MeshPlan:
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    return MeshPlan(mesh=mesh, dp_axes=dp_axes, fsdp=fsdp, seq_parallel=seq_parallel)


def pad_cfg_for_tp(cfg: ModelConfig, tp: int) -> ModelConfig:
    """Pad head counts up to the next TP multiple (zero-init extra heads)."""
    nh = cfg.n_heads
    nkv = cfg.n_kv_heads
    if nh % tp == 0:
        return cfg
    new_nh = -(-nh // tp) * tp
    if cfg.q_group == 1:
        new_nkv = new_nh                 # MHA: pad kv heads along
    else:
        new_nkv = nkv                    # GQA: keep kv heads, grow the group
        while new_nh % new_nkv:          # (arctic 56→64: group 7→8)
            new_nh += tp
    return dataclasses.replace(cfg, n_heads=new_nh, n_kv_heads=new_nkv,
                               d_head=cfg.head_dim)


# ---------------------------------------------------------------------------
# parameter specs (path-rule based)
# ---------------------------------------------------------------------------

def _spec_for(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
              plan: MeshPlan) -> P:
    tp = plan.tp_axis
    fsdp = plan.dp if plan.fsdp else None
    stacked = path.startswith("blocks/")
    name = path.split("/")[-1]
    div = lambda dim, n: dim % n == 0

    def with_stack(*spec):
        return P(None, *spec) if stacked else P(*spec)

    n_dp, ntp = plan.n_dp, plan.tp
    fs = lambda dim: fsdp if (fsdp and div(dim, n_dp)) else None
    tps = lambda dim: tp if div(dim, ntp) else None

    body = shape[1:] if stacked else shape
    if name == "table":                       # [V, d]
        return with_stack(tps(body[0]), fs(body[1]))
    if path.startswith("lm_head"):            # [d, V]
        return with_stack(fs(body[0]), tps(body[1]))
    if name in ("scale", "conv_b", "dt_b", "D"):
        if name in ("conv_b", "dt_b", "D"):   # [di]
            return with_stack(tps(body[0]))
        return with_stack(None)
    if "ffn/dense" in path:                   # arctic parallel MLP (shard_map specs)
        if name in ("w_gate", "w_up"):
            return with_stack(None, tps(body[1]))
        return with_stack(tps(body[0]), None)
    if "ffn" in path and name == "router":    # [d, E] (replicated for shard_map)
        return with_stack(None, None)
    if "ffn" in path and len(body) == 3 and name in ("w_gate", "w_up"):
        # MoE experts [E, d, f]: EP over model, ZeRO-3 over data on f
        return with_stack(tps(body[0]), None, fs(body[2]))
    if "ffn" in path and len(body) == 3 and name == "w_down":   # [E, f, d]
        return with_stack(tps(body[0]), fs(body[1]), None)
    if name in ("w_gate", "w_up"):            # dense MLP [d, f]
        return with_stack(fs(body[0]), tps(body[1]))
    if name == "w_down":                      # [f, d]
        return with_stack(tps(body[0]), fs(body[1]))
    if name == "wq":                          # [d, nh, dh]
        return with_stack(fs(body[0]), tps(body[1]), None)
    if name in ("wk", "wv", "wk_e"):          # [d, nkv, *]
        return with_stack(fs(body[0]), tps(body[1]), None)
    if name == "wo":                          # [nh, dh, d]
        return with_stack(tps(body[0]), None, fs(body[2]))
    if name in ("a_kv", "a_k", "a_v"):        # [d, d_c]
        return with_stack(fs(body[0]), None)
    if name in ("bk", "bv"):                  # [d_c, nkv, *]
        return with_stack(None, tps(body[1]), None)
    # --- mamba ---
    if name == "in_proj":                     # [d, 2di]
        return with_stack(fs(body[0]), tps(body[1]))
    if name == "conv_w":                      # [K, di]
        return with_stack(None, tps(body[1]))
    if name == "x_proj":                      # [di, dtr+2N]
        return with_stack(tps(body[0]), None)
    if name == "dt_w":                        # [dtr, di]
        return with_stack(None, tps(body[1]))
    if name == "A_log":                       # [di, N]
        return with_stack(tps(body[0]), None)
    if name == "out_proj":                    # [di, d]
        return with_stack(tps(body[0]), fs(body[1]))
    if name == "elite_freqs":                 # [nkv, r] buffer
        return with_stack(None, None)
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_pspecs(params, cfg: ModelConfig, plan: MeshPlan):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(_path_str(path), leaf.shape, cfg, plan), params)


def param_shardings(params, cfg, plan):
    return jax.tree.map(plan.named, param_pspecs(params, cfg, plan),
                        is_leaf=lambda x: isinstance(x, P))


def opt_pspecs(opt_state, params, cfg: ModelConfig, plan: MeshPlan, moment_dtype: str):
    pspecs = param_pspecs(params, cfg, plan)

    if moment_dtype == "int8":
        def mom(spec):
            # {'q': full shape spec, 's': last dim collapsed to 1 → unshard it}
            return {"q": spec, "s": P(*(tuple(spec)[:-1] + (None,)))}
        m = jax.tree.map(mom, pspecs, is_leaf=lambda x: isinstance(x, P))
    else:
        m = pspecs
    return {"step": P(), "m": m, "v": m}


# ---------------------------------------------------------------------------
# serving pool pages
# ---------------------------------------------------------------------------

def serving_page_pspecs(cfg: ModelConfig, plan: MeshPlan) -> Dict[str, P]:
    """PartitionSpecs for the paged serving pool's per-stream page arrays.

    Per page the pool stores ``k_e [n_super, n_slots, nkv·2r]`` plus the
    latent stream(s) ``c``/``c_k``/``c_v`` ``[n_super, n_slots, d_c]`` and,
    when quantized, per-token f32 ``*_scale [n_super, n_slots]`` arrays
    (core/cache.py).  Only ``k_e`` has heads: kv head ``h`` owns columns
    ``[h·2r, (h+1)·2r)`` of its flat row, so sharding the row over the TP
    axis gives each shard the contiguous columns of its kv heads, when
    ``nkv`` divides — mirroring the ``wk_e``/``bk``/``bv`` head sharding
    in :func:`_spec_for`.  The latent is head-*shared* (J-LRD), and scales
    are per-token, so both replicate — which is exactly what lets block ids,
    prefix hashes, COW copies, swap and int8 scales stay shard-invariant.
    """
    head = plan.tp_axis if (plan.tp > 1 and cfg.n_kv_heads % plan.tp == 0) else None
    specs: Dict[str, P] = {"k_e": P(None, None, head)}
    for name in ("c", "c_k", "c_v"):
        specs[name] = P()
    for name in ("k_e_scale", "c_scale", "c_k_scale", "c_v_scale"):
        specs[name] = P()
    # sparse-decode block summaries [n_super, num_blocks, d_c] (head-shared
    # latent space, f32): replicate — block selection is computed once per
    # step and must be shard-invariant for the bit-identity wall to hold
    for name in ("c_blkmean", "c_blkmax", "c_k_blkmean", "c_k_blkmax"):
        specs[name] = P()
    return specs


def serving_page_shardings(cfg: ModelConfig, plan: MeshPlan) -> Dict[str, NamedSharding]:
    return {k: plan.named(v) for k, v in serving_page_pspecs(cfg, plan).items()}


# ---------------------------------------------------------------------------
# inputs / cache / activations
# ---------------------------------------------------------------------------

def input_pspecs(cfg: ModelConfig, shape: ShapeConfig, plan: MeshPlan) -> Dict[str, P]:
    B = shape.global_batch
    bshard = B % plan.n_dp == 0
    dp = plan.dp if bshard else None
    out = {}
    names = {"tokens": 2, "labels": 2, "frames": 3, "patch_embeds": 3}
    for name, nd in names.items():
        out[name] = P(dp, *([None] * (nd - 1)))
    return out


def cache_pspecs(cache, cfg: ModelConfig, plan: MeshPlan, batch: int,
                 seq_over_tp: bool = False) -> Any:
    """Cache sharding: batch over DP when divisible, else cache-sequence over
    "data" (context parallelism for the batch-1 long_500k cell).

    ``seq_over_tp`` (§Perf decode-v2): additionally shard the cache sequence
    over the otherwise-idle *model* axis — the attention softmax reduces
    across shards with two tiny psums per layer, and per-device cache
    memory/traffic drops by TP×."""
    bshard = batch % plan.n_dp == 0

    def spec(path, leaf):
        name = _path_str(path)
        nd = leaf.ndim
        if "index" in name:
            return P()
        if "conv" in name or "ssm" in name:
            # [L, B, K-1, di] / [L, B, di, N]
            di_axis = 3 if "conv" in name else 2
            s = [None] * nd
            if bshard:
                s[1] = plan.dp
            if leaf.shape[di_axis] % plan.tp == 0:
                s[di_axis] = plan.tp_axis
            return P(*s)
        # attention caches: [L, B, S, ...]
        s = [None] * nd
        if bshard:
            s[1] = plan.dp
            if seq_over_tp and leaf.shape[2] % plan.tp == 0:
                s[2] = plan.tp_axis             # decode-v2 context parallel
        elif leaf.shape[2] % plan.n_dp == 0:
            s[2] = plan.dp                      # sequence/context parallel
        # kv-head dim shards over model when divisible (k_e/k/v: dim 3)
        if s[2] is None and nd >= 4 and leaf.shape[3] % plan.tp == 0:
            s[3] = plan.tp_axis
        return P(*s)

    return jax.tree_util.tree_map_with_path(spec, cache)


def make_constrain(plan: MeshPlan, cfg: ModelConfig, seq_len: int, batch: int,
                   decode: bool = False, seq_over_tp: bool = False):
    """Activation-constraint hook for lm.apply (residual stream + logits)."""
    mesh = plan.mesh
    bshard = batch % plan.n_dp == 0
    dp = plan.dp if bshard else None
    sp = (plan.tp_axis if (plan.seq_parallel and not decode
                           and seq_len % plan.tp == 0) else None)

    tp = plan.tp_axis
    ntp = plan.tp
    # for batch-1 decode the cache sequence dim shards over data instead
    seq_dp = plan.dp if (not bshard and decode and seq_len % plan.n_dp == 0) else None
    if decode and seq_over_tp and bshard and seq_len % ntp == 0:
        seq_dp = tp  # decode-v2: cache-length tensors S-sharded over model

    def constrain(name: str, x):
        if mesh is None:
            return x
        if name in ("embed", "residual", "attn_out", "ffn_out", "attn_in_sharded"):
            # Megatron-SP: the carried residual stream lives S-sharded over
            # the TP axis; GSPMD places all-gather at sublayer entry and
            # reduce-scatter at sublayer exit.
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, sp, None)))
        if name == "attn_in":
            # gathered (full-S) bf16 normed input — pins the SP gather to the
            # *bf16* tensor (otherwise XLA may gather an f32 norm intermediate)
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, None, None)))
        if name == "logits":
            vp = tp if x.shape[-1] % ntp == 0 else None
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, None, vp)))
        if name in ("attn_q", "heads4", "attn_kv"):   # [B,S,heads,*]
            sdim = seq_dp if x.shape[1] > 1 else None
            hp = (tp if (x.shape[2] % ntp == 0 and sdim != tp) else None)
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, sdim, hp, None)))
        if name in ("mlp_h", "ssm_h"):         # [B,S,f|di] — hidden over TP
            hp = tp if x.shape[-1] % ntp == 0 else None
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, None, hp)))
        if name == "latent":                   # [B,S,d_c] — replicated latent
            sdim = seq_dp if x.shape[1] > 1 else None
            return jax.lax.with_sharding_constraint(
                x, plan.named(P(dp, sdim, None)))
        return x

    return constrain
