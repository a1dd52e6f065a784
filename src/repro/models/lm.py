"""Unified decoder-only LM covering every assigned architecture.

Layer stacking: layers are grouped into *superblocks* of ``P = block_period``
positions (P=8 for jamba's 1:7 mamba:attn interleave + MoE-every-2; P=1 for
homogeneous stacks).  The ``num_layers / P`` superblocks are parameter-stacked
and driven by ``lax.scan`` (+ optional ``jax.checkpoint``), keeping HLO size
O(1) in depth — essential at 94-layer/128-expert dry-run scale.

Modes:
  * ``apply_train``   — logits over the full sequence.
  * ``apply_prefill`` — logits + filled cache.
  * ``apply_decode``  — one token + cache → logits + new cache.
  * ``capture_attn_inputs`` — per-attention-layer normed inputs (RoPElite search).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import elite_attention
from repro.models import attention as gqa_attention
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import (cross_entropy, dense_init, embed, embed_init,
                                 mlp, mlp_init, rmsnorm, rmsnorm_init, unembed)

_NOOP = lambda name, x: x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg, layer_idx: int):
    """(params, buffers) for one absolute layer index."""
    kinds = (cfg.layer_kind(layer_idx), cfg.ffn_kind(layer_idx))
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"attn_norm": rmsnorm_init(cfg.d_model)}
    b: Dict[str, Any] = {}
    if kinds[0] == "attn":
        if cfg.elitekv.enabled:
            p["attn"], b_attn = elite_attention.init(ks[0], cfg)
            b.update(b_attn)
        else:
            p["attn"] = gqa_attention.init(ks[0], cfg)
    else:
        p["attn"] = mamba_lib.init(ks[0], cfg)
    if kinds[1] != "none":
        p["ffn_norm"] = rmsnorm_init(cfg.d_model)
        if kinds[1] == "moe":
            p["ffn"] = moe_lib.init(ks[1], cfg)
        else:
            p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return p, b


def init(key, cfg) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    P_ = cfg.block_period
    assert cfg.num_layers % P_ == 0 or P_ == 1, (cfg.num_layers, P_)
    n_super = cfg.num_layers // P_ if cfg.num_layers % P_ == 0 else cfg.num_layers
    keys = jax.random.split(key, 3)
    params: Dict[str, Any] = {}
    buffers: Dict[str, Any] = {"blocks": {}}
    Vp = cfg.padded_vocab
    if cfg.frontend != "audio":
        params["embed"] = embed_init(keys[0], Vp, cfg.d_model)
    if cfg.frontend == "audio" or not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(keys[1], (cfg.d_model, Vp), scale=0.02)}
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    blocks: Dict[str, Any] = {}
    bkeys = jax.random.split(keys[2], cfg.num_layers)
    for p_pos in range(P_):
        layer_keys = [bkeys[s * P_ + p_pos] for s in range(n_super)]
        inits = [_init_layer(k, cfg, s * P_ + p_pos) for s, k in enumerate(layer_keys)]
        stacked_p = jax.tree.map(lambda *xs: jnp.stack(xs), *[i[0] for i in inits])
        stacked_b = jax.tree.map(lambda *xs: jnp.stack(xs), *[i[1] for i in inits])
        blocks[f"p{p_pos}"] = stacked_p
        buffers["blocks"][f"p{p_pos}"] = stacked_b
    params["blocks"] = blocks
    return params, buffers


# ---------------------------------------------------------------------------
# embedding / frontend stubs
# ---------------------------------------------------------------------------

def _embed_step(params, cfg, batch: Dict[str, Any]):
    """Token/frame embedding dispatch shared by every prefill/decode entry
    point: audio frontends feed raw frames, everything else embeds tokens."""
    if cfg.frontend == "audio":
        return batch["frames"].astype(cfg.dtype)
    return embed(params["embed"], batch["tokens"], cfg.dtype)


def _embed_inputs(params, cfg, batch: Dict[str, Any], dtype):
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        txt = embed(params["embed"], batch["tokens"], dtype)
        return jnp.concatenate([batch["patch_embeds"].astype(dtype), txt], axis=1)
    return _embed_step(params, cfg, batch)


def _logits(params, cfg, h, constrain=_NOOP):
    if cfg.tie_embeddings and cfg.frontend != "audio":
        out = unembed(params["embed"], h)
    else:
        out = h.astype(jnp.float32) @ params["lm_head"]["w"].astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:  # mask Megatron-style vocab padding
        out = jnp.where(jnp.arange(out.shape[-1]) < cfg.vocab_size, out, -1e30)
    return constrain("logits", out)


def _head(params, cfg, h, constrain=_NOOP):
    """Final norm and logits of a paged serving forward."""
    with jax.named_scope("head"):
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return _logits(params, cfg, h, constrain)


# ---------------------------------------------------------------------------
# superblock body
# ---------------------------------------------------------------------------

def _run_layer(p, b, cfg, p_pos: int, h, positions, mode, cache, index,
               moe_impl, mesh, constrain, data_axes=("data",), paged=None):
    kind = cfg.layer_kind(p_pos)
    ffn_kind = cfg.ffn_kind(p_pos)
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    with jax.named_scope("attn"):
        hn = rmsnorm(p["attn_norm"], h, cfg.norm_eps)
        # double pin: norm output stays S-sharded (bf16), then the SP all-gather
        # happens exactly here — on the bf16 tensor, not an f32 norm intermediate
        hn = constrain("attn_in", constrain("attn_in_sharded", hn))
        if kind == "attn":
            if cfg.elitekv.enabled:
                if mode == "train":
                    a = elite_attention.apply_full(p["attn"], cfg, b, hn, positions,
                                                   constrain=constrain)
                elif mode == "prefill" and paged is not None:
                    a, new_cache = elite_attention.apply_prefill_paged(
                        p["attn"], cfg, b, hn, positions, cache,
                        paged["slot_mapping"],
                        block_tables=paged.get("block_tables"),
                        prefix_lens=paged.get("prefix_lens"),
                        block_size=paged.get("block_size", 0),
                        constrain=constrain, mesh=paged.get("mesh"))
                elif mode == "prefill":
                    a, new_cache = elite_attention.apply_prefill(
                        p["attn"], cfg, b, hn, positions, cache, constrain=constrain)
                elif paged is not None and paged.get("verify"):
                    a, new_cache = elite_attention.apply_verify_paged(
                        p["attn"], cfg, b, hn, cache, paged["slot_mapping"],
                        paged["block_tables"], paged["q_offsets"],
                        paged["lengths"], paged["block_size"],
                        use_kernel=paged.get("use_kernel", True),
                        constrain=constrain, mesh=paged.get("mesh"))
                elif paged is not None:
                    a, new_cache = elite_attention.apply_decode_paged(
                        p["attn"], cfg, b, hn, cache, paged["slot_mapping"],
                        paged["block_tables"], paged["lengths"],
                        paged["block_size"], use_kernel=paged.get("use_kernel", True),
                        constrain=constrain, mesh=paged.get("mesh"),
                        sparse_topk=paged.get("sparse_topk", 0),
                        sparse_recent=paged.get("sparse_recent", 0))
                else:
                    a, new_cache = elite_attention.apply_decode(
                        p["attn"], cfg, b, hn, index, cache, constrain=constrain)
            else:
                if mode == "train":
                    a = gqa_attention.apply_full(p["attn"], cfg, hn, positions,
                                                 constrain=constrain)
                elif mode == "prefill":
                    a, new_cache = gqa_attention.apply_prefill(
                        p["attn"], cfg, hn, positions, cache, constrain=constrain)
                else:
                    a, new_cache = gqa_attention.apply_decode(
                        p["attn"], cfg, hn, index, cache, constrain=constrain)
        else:  # mamba
            if mode == "train":
                a = mamba_lib.apply_full(p["attn"], cfg, hn, constrain=constrain)
            elif mode == "prefill":
                a, (conv_s, ssm_s) = mamba_lib.apply_full(
                    p["attn"], cfg, hn, return_state=True, constrain=constrain)
                new_cache = {"conv": conv_s.astype(cache["conv"].dtype), "ssm": ssm_s}
            else:
                a, new_cache = mamba_lib.apply_decode(p["attn"], cfg, hn, cache,
                                                      constrain=constrain)
    h = constrain("residual", h + constrain("attn_out", a))
    with jax.named_scope("mlp"):
        if ffn_kind != "none":
            hn = constrain("attn_in", constrain(
                "attn_in_sharded", rmsnorm(p["ffn_norm"], h, cfg.norm_eps)))
            if ffn_kind == "moe":
                f, aux = moe_lib.apply(p["ffn"], cfg, hn, impl=moe_impl, mesh=mesh,
                                       data_axes=data_axes)
            else:
                f = mlp(p["ffn"], hn, constrain=constrain)
            h = constrain("residual", h + constrain("ffn_out", f))
    return h, aux, new_cache


def _superblock(cfg, mode, moe_impl, mesh, constrain, positions, index,
                data_axes=("data",), paged=None):
    """Returns a scan body: (carry=(h, aux), xs=(params, buffers, cache)) → ..."""

    def body(carry, xs):
        h, aux_acc = carry
        p_blk, b_blk, c_blk, capture = xs
        caps = {}
        for p_pos in range(cfg.block_period):
            key = f"p{p_pos}"
            cache_p = c_blk.get(key) if c_blk else None
            if capture is not None and cfg.layer_kind(p_pos) == "attn":
                caps[key] = rmsnorm(p_blk[key]["attn_norm"], h, cfg.norm_eps)
            h, aux, nc = _run_layer(
                p_blk[key], b_blk.get(key, {}), cfg, p_pos, h, positions, mode,
                cache_p, index, moe_impl, mesh, constrain, data_axes,
                paged=paged)
            aux_acc = aux_acc + aux
            if c_blk:
                c_blk = dict(c_blk)
                c_blk[key] = nc
        ys = c_blk if mode in ("prefill", "decode") else (caps if capture is not None else None)
        return (h, aux_acc), ys

    return body


def _scan_blocks(params, buffers, cfg, h, positions, mode="train", cache=None,
                 index=None, moe_impl="ragged", mesh=None, constrain=_NOOP,
                 capture: bool = False, data_axes=("data",), paged=None):
    P_ = cfg.block_period
    n_super = cfg.num_layers // P_
    body = _superblock(cfg, mode, moe_impl, mesh, constrain, positions, index,
                       data_axes=data_axes, paged=paged)
    if cfg.remat:
        policy = {
            "dots": jax.checkpoint_policies.dots_saveable,
            "none": None,
            "full": jax.checkpoint_policies.nothing_saveable,
        }[cfg.remat_policy if cfg.remat_policy != "none" else "none"]
        body = jax.checkpoint(body, policy=policy) if policy is not None else jax.checkpoint(body)
    cache_blocks = cache["blocks"] if cache is not None else {}
    cap_xs = jnp.zeros((n_super,), jnp.int32) if capture else None
    xs = (params["blocks"], buffers["blocks"], cache_blocks, cap_xs)
    if not cfg.scan_layers:  # unrolled (dry-run flop accounting / tiny models)
        carry = (h, jnp.zeros((), jnp.float32))
        ys_list = []
        for s_i in range(n_super):
            xs_s = jax.tree.map(lambda t: t[s_i], xs)
            carry, ys_s = body(carry, xs_s)
            ys_list.append(ys_s)
        h, aux = carry
        ys = (None if ys_list[0] is None
              else jax.tree.map(lambda *a: jnp.stack(a), *ys_list))
        return h, aux, ys
    (h, aux), ys = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), xs,
                                unroll=cfg.scan_unroll)
    return h, aux, ys


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def apply_train(params, buffers, cfg, batch, moe_impl="ragged", mesh=None,
                constrain=_NOOP, data_axes=("data",), return_hidden=False):
    """→ (logits [B,S,V] fp32, aux_loss scalar) — or (h, aux) if return_hidden."""
    h = _embed_inputs(params, cfg, batch, cfg.dtype)
    h = constrain("embed", h)
    S = h.shape[1]
    positions = jnp.arange(S)
    h, aux, _ = _scan_blocks(params, buffers, cfg, h, positions, mode="train",
                             moe_impl=moe_impl, mesh=mesh, constrain=constrain,
                             data_axes=data_axes)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if return_hidden:
        return h, aux
    return _logits(params, cfg, h, constrain), aux


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Cache pytree: {"index": scalar, "blocks": {p*: stacked layer caches}}."""
    P_ = cfg.block_period
    n_super = cfg.num_layers // P_
    blocks = {}
    for p_pos in range(P_):
        if cfg.layer_kind(p_pos) == "attn":
            one = (elite_attention.init_cache(cfg, batch, max_len, dtype)
                   if cfg.elitekv.enabled else
                   gqa_attention.init_cache(cfg, batch, max_len, dtype))
        else:
            one = mamba_lib.init_state(cfg, batch, dtype)
        blocks[f"p{p_pos}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_super,) + x.shape), one)
    return {"index": jnp.zeros((), jnp.int32), "blocks": blocks}


def apply_prefill(params, buffers, cfg, batch, cache, moe_impl="ragged",
                  mesh=None, constrain=_NOOP, data_axes=("data",)):
    """Full forward that also fills the cache.  → (logits, new_cache)."""
    h = _embed_inputs(params, cfg, batch, cfg.dtype)
    h = constrain("embed", h)
    S = h.shape[1]
    positions = jnp.arange(S)
    h, aux, new_blocks = _scan_blocks(
        params, buffers, cfg, h, positions, mode="prefill", cache=cache,
        moe_impl=moe_impl, mesh=mesh, constrain=constrain, data_axes=data_axes)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, cfg, h, constrain)
    return logits, {"index": jnp.asarray(S, jnp.int32), "blocks": new_blocks}


def apply_decode(params, buffers, cfg, batch, cache, moe_impl="ragged",
                 mesh=None, constrain=_NOOP, data_axes=("data",)):
    """One new token.  batch["tokens"]: [B,1].  → (logits [B,1,V], new_cache)."""
    h = _embed_step(params, cfg, batch)
    index = cache["index"]
    positions = jnp.full((h.shape[0], 1), index, jnp.int32)
    h, aux, new_blocks = _scan_blocks(
        params, buffers, cfg, h, positions, mode="decode", cache=cache,
        index=index, moe_impl=moe_impl, mesh=mesh, constrain=constrain,
        data_axes=data_axes)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _logits(params, cfg, h, constrain)
    return logits, {"index": index + 1, "blocks": new_blocks}


def apply_prefill_paged(params, buffers, cfg, batch, pages, slot_mapping,
                        chunk_start=None, block_tables=None, prefix_lens=None,
                        block_size: int = 0, moe_impl="ragged", mesh=None,
                        constrain=_NOOP, data_axes=("data",)):
    """Prefill sequences (or chunks of them) into the paged pool.

    ``pages``: the pool's per-``p_pos`` stream dict (``PagedKVPool.pages``);
    ``slot_mapping`` [B,S] flat pool slots per prompt token (padding → the
    pool's out-of-bounds sentinel, dropped on write).

    One-shot mode (default): prompts start at position 0 and attend causally
    to themselves only.

    Chunked mode (``chunk_start`` given — a traced scalar or a per-lane [B]
    vector, so one jit covers every chunk *and* every batch composition):
    lane ``b``'s tokens sit at global positions ``chunk_start[b] + i``; RoPE
    is applied at those positions and attention additionally sees each lane's
    own already-cached prefix, located by ``block_tables`` [B,mb] /
    ``prefix_lens`` [B] / static ``block_size``.  Lanes whose chunk is fresh
    (``chunk_start == prefix_lens == 0``) reduce exactly to causal prefill,
    so mid-prefill chunks of *different* sequences — resumed or not — pack
    into one forward (batched chunked prefill, see docs/serving.md).
    → (logits [B,S,V], new_pages).
    """
    assert cfg.elitekv.enabled, "paged serving requires an EliteKV cache"
    with jax.named_scope("embed"):
        h = constrain("embed", _embed_inputs(params, cfg, batch, cfg.dtype))
    S = h.shape[1]
    positions = jnp.arange(S)
    paged = {"slot_mapping": slot_mapping, "mesh": mesh}
    if chunk_start is not None:
        cs = jnp.asarray(chunk_start, jnp.int32)
        # scalar → [S] positions (PR-3 single-lane path); [B] → [B,S] per-lane
        positions = (positions + cs if cs.ndim == 0
                     else positions[None, :] + cs[:, None])
        paged.update(block_tables=block_tables, prefix_lens=prefix_lens,
                     block_size=block_size)
    h, aux, new_pages = _scan_blocks(
        params, buffers, cfg, h, positions, mode="prefill",
        cache={"blocks": pages}, moe_impl=moe_impl, mesh=mesh,
        constrain=constrain, data_axes=data_axes, paged=paged)
    return _head(params, cfg, h, constrain), new_pages


def apply_decode_paged(params, buffers, cfg, batch, pages, slot_mapping,
                       block_tables, lengths, block_size: int,
                       use_kernel: bool = True, moe_impl="ragged", mesh=None,
                       constrain=_NOOP, data_axes=("data",),
                       sparse_topk: int = 0, sparse_recent: int = 0):
    """One decode step for every serving slot, reading/writing pool pages.

    ``lengths`` [B] int32: live length *including* this token (0 = idle lane);
    ``slot_mapping`` [B] flat write slot for the new token; ``block_tables``
    [B, max_blocks].  Shapes are slot-count-static, so one jit covers the
    whole serving run regardless of which lanes are live.
    ``sparse_topk > 0`` enables latent-space sparse decode (top-k blocks +
    ``sparse_recent`` newest; needs a ``block_summaries=True`` pool — see
    core/elite_attention.py::apply_decode_paged).
    → (logits [B,1,V], new_pages).
    """
    assert cfg.elitekv.enabled, "paged serving requires an EliteKV cache"
    with jax.named_scope("embed"):
        h = _embed_step(params, cfg, batch)
    paged = {"slot_mapping": slot_mapping, "block_tables": block_tables,
             "lengths": lengths, "block_size": block_size,
             "use_kernel": use_kernel, "mesh": mesh,
             "sparse_topk": sparse_topk, "sparse_recent": sparse_recent}
    h, aux, new_pages = _scan_blocks(
        params, buffers, cfg, h, None, mode="decode",
        cache={"blocks": pages}, moe_impl=moe_impl, mesh=mesh,
        constrain=constrain, data_axes=data_axes, paged=paged)
    return _head(params, cfg, h, constrain), new_pages


def apply_verify_paged(params, buffers, cfg, batch, pages, slot_mapping,
                       block_tables, q_offsets, lengths, block_size: int,
                       use_kernel: bool = True, moe_impl="ragged", mesh=None,
                       constrain=_NOOP, data_axes=("data",)):
    """Speculative-verify forward: score a ``W = k+1``-token window per lane
    (the pending token + ``k`` draft proposals) against its paged prefix in
    ONE call, writing the window's full-model compressed streams to the pool.

    batch["tokens"] [B,W]; ``q_offsets`` [B] global position of each lane's
    window row 0 (== that lane's cached prefix length); ``lengths`` [B] live
    length *including* the window (0 = dead lane); ``slot_mapping`` [B,W]
    flat write slots (pad → sentinel).  Logits row ``w`` of lane ``b`` is the
    full model's next-token distribution after window token ``w`` — rows
    ``0..k-1`` judge the draft proposals, row ``k`` samples the bonus token.
    Shapes are (slots, W)-static, so one jit covers the whole serving run.
    → (logits [B,W,V], new_pages).
    """
    assert cfg.elitekv.enabled, "paged serving requires an EliteKV cache"
    with jax.named_scope("embed"):
        h = _embed_step(params, cfg, batch)
    paged = {"slot_mapping": slot_mapping, "block_tables": block_tables,
             "q_offsets": q_offsets, "lengths": lengths,
             "block_size": block_size, "use_kernel": use_kernel,
             "mesh": mesh, "verify": True}  # explicit dispatch tag, not
    h, aux, new_pages = _scan_blocks(      # key-presence sniffing
        params, buffers, cfg, h, None, mode="decode",
        cache={"blocks": pages}, moe_impl=moe_impl, mesh=mesh,
        constrain=constrain, data_axes=data_axes, paged=paged)
    return _head(params, cfg, h, constrain), new_pages


def make_draft_params(params, cfg, draft_rank: int):
    """Draft-model weights for self-speculative decode: every EliteKV layer's
    joint up-projections ``bk``/``bv`` are projected onto their top
    ``draft_rank`` singular directions (``core.lrd.truncate_joint_rank``) —
    no new trained weights, identical pytree structure/shapes, so the draft
    runs through the same jitted decode step and reads/writes the same paged
    pool as the full model (``a_kv`` stays full-width: draft-written latents
    occupy the verify-compatible layout and are overwritten by the verify
    forward anyway).  ``draft_rank <= 0`` or ``>= d_ckv`` returns ``params``
    unchanged (the full-rank draft, acceptance ≡ 1)."""
    from repro.core import lrd
    assert cfg.elitekv.enabled, "speculative decode requires an EliteKV cache"
    if draft_rank <= 0 or draft_rank >= cfg.elitekv.d_ckv:
        return params                       # full-rank draft (any LRD kind)
    assert cfg.elitekv.lrd == "joint", \
        "draft truncation targets the joint low-rank factors"
    import numpy as np
    draft = jax.tree.map(lambda t: t, params)            # shallow leaf copy
    for p_key, blk in draft["blocks"].items():
        if "bk" not in blk.get("attn", {}):
            continue
        bk = np.asarray(blk["attn"]["bk"])               # [n_super, d_ckv, ...]
        bv = np.asarray(blk["attn"]["bv"])
        outs = [lrd.truncate_joint_rank(bk[s], bv[s], draft_rank)
                for s in range(bk.shape[0])]
        blk["attn"] = dict(blk["attn"])
        blk["attn"]["bk"] = jnp.stack([o[0] for o in outs])
        blk["attn"]["bv"] = jnp.stack([o[1] for o in outs])
    return draft


def capture_attn_inputs(params, buffers, cfg, batch, moe_impl="ragged", mesh=None):
    """Normed attention inputs per attention layer (for the RoPElite search).

    Returns dict {p_pos: [n_super, B, S, d]} restricted to attention positions.
    """
    h = _embed_inputs(params, cfg, batch, cfg.dtype)
    S = h.shape[1]
    positions = jnp.arange(S)
    _, _, caps = _scan_blocks(params, buffers, cfg, h, positions, mode="train",
                              moe_impl=moe_impl, mesh=mesh, capture=True)
    return caps


def loss_fn(params, buffers, cfg, batch, moe_impl="ragged", mesh=None,
            constrain=_NOOP, aux_weight: float = 0.01, data_axes=("data",)):
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    nv = batch["patch_embeds"].shape[1] if (
        cfg.frontend == "vision" and "patch_embeds" in batch) else 0
    if cfg.loss_chunk and labels.shape[1] % cfg.loss_chunk == 0 and nv == 0:
        # §Perf: sequence-chunked CE — logits for one S-chunk at a time
        # (never materializes the [B,S,V] fp32 logits or their cotangent;
        # per-chunk logits are rematerialized in the backward)
        h, aux = apply_train(params, buffers, cfg, batch, moe_impl, mesh,
                             constrain, data_axes=data_axes, return_hidden=True)
        B, S, _ = h.shape
        ck = cfg.loss_chunk
        n = S // ck
        hs = jnp.moveaxis(h.reshape(B, n, ck, -1), 1, 0)
        ls = jnp.moveaxis(labels.reshape(B, n, ck), 1, 0)
        ms = (jnp.moveaxis(mask.reshape(B, n, ck), 1, 0) if mask is not None
              else jnp.ones((n, B, ck), jnp.float32))

        @jax.checkpoint
        def chunk(carry, xs):
            h_c, l_c, m_c = xs
            logits_c = _logits(params, cfg, h_c, constrain)
            logz = jax.nn.logsumexp(logits_c.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits_c.astype(jnp.float32), l_c[..., None], axis=-1)[..., 0]
            nll, cnt = carry
            return (nll + jnp.sum((logz - gold) * m_c), cnt + jnp.sum(m_c)), None

        (nll, cnt), _ = jax.lax.scan(chunk, (0.0, 0.0), (hs, ls, ms))
        ce = nll / jnp.maximum(cnt, 1.0)
    else:
        logits, aux = apply_train(params, buffers, cfg, batch, moe_impl, mesh,
                                  constrain, data_axes=data_axes)
        if nv:
            logits = logits[:, nv:, :]
        ce = cross_entropy(logits, labels, mask)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}
