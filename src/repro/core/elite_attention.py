"""EliteKV attention: RoPElite partial rotation + joint low-rank KV latent.

Weight layout (after conversion from a baseline GQA/MHA checkpoint, or direct
init for from-scratch training):

  wq    [d, n_h, d_h]     — query projection, columns permuted per head so that
                            dims [0:2r) are that head's KV-group elite chunks
                            (in greedy-selection order) and [2r:) the non-elite.
  wk_e  [d, n_kv, 2r]     — elite key slice (rotated with per-head elite freqs).
  a_kv  [d, d_ckv]        — J-LRD shared down-projection  (or a_k/a_v for S-LRD).
  bk    [d_c, n_kv, d_h-2r] — K up-projection  (latent → non-elite key dims).
  bv    [d_c, n_kv, d_h]  — V up-projection.
  wo    [n_h, d_h, d]     — output projection (unchanged).

Buffers (non-trainable): ``elite_freqs`` [n_kv, r] — theta values of the elite
chunks, in the order the greedy search picked them.

Cache per token per layer (paper §3.2):  2·r·n_kv  (rotated elite keys, stored
POST-rotation — never re-rotated at decode)  +  d_ckv  (shared latent).

Decode uses MLA-style *absorption at the activation level*:
    q_ne · k_neᵀ = q_ne · (c·bk)ᵀ = (q_ne·bkᵀ) · cᵀ        (bk absorbed into q)
    o = p · v = p · (c·bv) = (p·c) · bv                     (bv absorbed into o)
so only the compressed cache is ever read — the paper's systems win.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core import rope as rope_lib
from repro.models.attention import causal_mask


# ---------------------------------------------------------------------------
# init (from scratch; convert.py builds these from a baseline checkpoint)
# ---------------------------------------------------------------------------

def init(key, cfg) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (params, buffers)."""
    from repro.models.layers import dense_init
    d, dh, nh, nkv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    e = cfg.elitekv
    r2 = 2 * e.elite_r
    d_nope = dh - r2
    ks = jax.random.split(key, 8)
    params = {
        "wq": dense_init(ks[0], (d, nh, dh)),
        "wk_e": dense_init(ks[1], (d, nkv, r2)),
        "wo": dense_init(ks[2], (nh, dh, d), in_axis=2, scale=(nh * dh) ** -0.5),
    }
    if e.lrd == "joint":
        params["a_kv"] = dense_init(ks[3], (d, e.d_ckv))
        params["bk"] = dense_init(ks[4], (e.d_ckv, nkv, d_nope), scale=e.d_ckv ** -0.5)
        params["bv"] = dense_init(ks[5], (e.d_ckv, nkv, dh), scale=e.d_ckv ** -0.5)
    else:
        params["a_k"] = dense_init(ks[3], (d, e.d_ck))
        params["a_v"] = dense_init(ks[6], (d, e.d_cv))
        params["bk"] = dense_init(ks[4], (e.d_ck, nkv, d_nope), scale=e.d_ck ** -0.5)
        params["bv"] = dense_init(ks[5], (e.d_cv, nkv, dh), scale=e.d_cv ** -0.5)
    # default elite chunks: top-r highest frequencies (uniform init; the real
    # sets come from the RoPElite search at conversion time).
    freqs = rope_lib.chunk_freqs(dh, cfg.rope_theta)
    buffers = {"elite_freqs": jnp.tile(freqs[None, :e.elite_r], (nkv, 1))}
    return params, buffers


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _project_q(params, cfg, x, positions):
    """Returns rotated q_e [B,S,nh,2r] and linear q_ne [B,S,nh,d_nope]."""
    dt = x.dtype
    e = cfg.elitekv
    r2 = 2 * e.elite_r
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(dt))
    q_e, q_ne = q[..., :r2], q[..., r2:]
    return q_e, q_ne


def _rot_q(cfg, buffers, q_e, positions):
    ef_q = rope_lib.expand_kv_to_q(buffers["elite_freqs"], cfg.q_group)  # [nh, r]
    return rope_lib.apply_elite_rope(q_e, positions, ef_q)


def _latents(params, cfg, x):
    """Down-projected latent(s): (c_k, c_v) — identical object for J-LRD."""
    dt = x.dtype
    if cfg.elitekv.lrd == "joint":
        c = x @ params["a_kv"].astype(dt)
        return c, c
    return x @ params["a_k"].astype(dt), x @ params["a_v"].astype(dt)


# ---------------------------------------------------------------------------
# full-sequence (training / prefill): materialized K,V
# ---------------------------------------------------------------------------

def _materialized(params, cfg, buffers, x, positions, constrain=lambda n, t: t):
    dt = x.dtype
    e = cfg.elitekv
    q_e, q_ne = _project_q(params, cfg, x, positions)
    q_e = _rot_q(cfg, buffers, q_e, positions)
    k_e = jnp.einsum("bsd,dhe->bshe", x, params["wk_e"].astype(dt))
    k_e = rope_lib.apply_elite_rope(k_e, positions, buffers["elite_freqs"])
    c_k, c_v = _latents(params, cfg, x)
    c_k, c_v = constrain("latent", c_k), constrain("latent", c_v)
    k_ne = jnp.einsum("bsc,che->bshe", c_k, params["bk"].astype(dt))
    v = constrain("attn_kv", jnp.einsum("bsc,che->bshe", c_v, params["bv"].astype(dt)))
    q = constrain("attn_q", jnp.concatenate([q_e, q_ne], axis=-1))
    k = constrain("attn_kv", jnp.concatenate([k_e, k_ne], axis=-1))
    return q, k, v, k_e, c_k, c_v


def apply_full(params, cfg, buffers, x, positions, constrain=lambda n, t: t) -> jnp.ndarray:
    from repro.models.attention import _attend
    q, k, v, *_ = _materialized(params, cfg, buffers, x, positions, constrain)
    o = _attend(q, k, v, cfg.q_group, cfg.head_dim ** -0.5,
                chunk_q=cfg.attn_chunk_q, constrain=constrain,
                unroll=cfg.attn_chunk_unroll)
    return jnp.einsum("bshe,hed->bsd", o, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    e = cfg.elitekv
    r2 = 2 * e.elite_r
    cache = {"k_e": jnp.zeros((batch, max_len, cfg.n_kv_heads, r2), dtype)}
    if e.lrd == "joint":
        cache["c"] = jnp.zeros((batch, max_len, e.d_ckv), dtype)
    else:
        cache["c_k"] = jnp.zeros((batch, max_len, e.d_ck), dtype)
        cache["c_v"] = jnp.zeros((batch, max_len, e.d_cv), dtype)
    return cache


def _cache_latents(cache):
    if "c" in cache:
        return cache["c"], cache["c"]
    return cache["c_k"], cache["c_v"]


def apply_prefill(params, cfg, buffers, x, positions, cache, constrain=lambda n, t: t):
    from repro.models.attention import _attend
    q, k, v, k_e, c_k, c_v = _materialized(params, cfg, buffers, x, positions, constrain)
    upd = lambda buf, val: jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype), (0,) * buf.ndim)
    new_cache = dict(cache)
    new_cache["k_e"] = upd(cache["k_e"], k_e)
    if "c" in cache:
        new_cache["c"] = upd(cache["c"], c_k)
    else:
        new_cache["c_k"] = upd(cache["c_k"], c_k)
        new_cache["c_v"] = upd(cache["c_v"], c_v)
    o = _attend(q, k, v, cfg.q_group, cfg.head_dim ** -0.5,
                chunk_q=cfg.attn_chunk_q, constrain=constrain,
                unroll=cfg.attn_chunk_unroll)
    return jnp.einsum("bshe,hed->bsd", o, params["wo"].astype(x.dtype)), new_cache


# ---------------------------------------------------------------------------
# absorbed decode — reads ONLY the compressed cache
# ---------------------------------------------------------------------------

def apply_decode(params, cfg, buffers, x, index, cache, use_kernel: bool = False,
                 constrain=lambda n, t: t):
    """x: [B,1,d].  Returns (out [B,1,d], new_cache)."""
    dt = x.dtype
    e = cfg.elitekv
    B = x.shape[0]
    nh, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = cfg.q_group
    pos = jnp.full((B, 1), index, jnp.int32)

    q_e, q_ne = _project_q(params, cfg, x, pos)
    q_e = constrain("attn_q", _rot_q(cfg, buffers, q_e, pos))  # [B,1,nh,2r]
    # absorb bk into the query (activation-level): q_lat [B,1,nh,d_c]
    bk_q = rope_lib.expand_kv_to_q(
        jnp.moveaxis(params["bk"], 1, 0), G)                 # [nh, d_c, d_nope]
    q_lat = constrain("attn_q", jnp.einsum("bshn,hcn->bshc", q_ne, bk_q.astype(dt)))

    # new cache entries
    k_e_new = jnp.einsum("bsd,dhe->bshe", x, params["wk_e"].astype(dt))
    k_e_new = rope_lib.apply_elite_rope(k_e_new, pos, buffers["elite_freqs"])
    c_k_new, c_v_new = _latents(params, cfg, x)
    new_cache = dict(cache)
    new_cache["k_e"] = jax.lax.dynamic_update_slice(
        cache["k_e"], k_e_new.astype(cache["k_e"].dtype), (0, index, 0, 0))
    if "c" in cache:
        new_cache["c"] = jax.lax.dynamic_update_slice(
            cache["c"], c_k_new.astype(cache["c"].dtype), (0, index, 0))
    else:
        new_cache["c_k"] = jax.lax.dynamic_update_slice(
            cache["c_k"], c_k_new.astype(cache["c_k"].dtype), (0, index, 0))
        new_cache["c_v"] = jax.lax.dynamic_update_slice(
            cache["c_v"], c_v_new.astype(cache["c_v"].dtype), (0, index, 0))

    K_e = new_cache["k_e"].astype(dt)                        # [B,S,nkv,2r]
    C_k, C_v = _cache_latents(new_cache)
    C_k, C_v = C_k.astype(dt), C_v.astype(dt)
    Smax = K_e.shape[1]

    if use_kernel:
        from repro.kernels import ops as kops
        lengths = jnp.full((B,), index + 1, jnp.int32)
        o = kops.elite_decode(
            q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k, C_v,
            lengths, q_group=G, scale=dh ** -0.5)
        o = o.reshape(B, 1, nh, C_v.shape[-1])
    else:
        # scores: rotary-elite part (K_e repeated to q heads — GSPMD-clean)
        # + latent part (shared C, no repeat)
        K_e_rep = constrain("heads4", jnp.repeat(K_e, G, axis=2)) if G > 1 else K_e
        s_e = jnp.einsum("bqhe,bkhe->bhqk", q_e, K_e_rep,
                         preferred_element_type=jnp.float32)
        s_lat = jnp.einsum("bqhc,bkc->bhqk", q_lat, C_k,
                           preferred_element_type=jnp.float32)
        s = s_e + s_lat
        s = s * (dh ** -0.5)
        valid = jnp.arange(Smax)[None, None, None, :] <= index
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(dt)            # [B,nh,1,S]
        o = jnp.einsum("bhqk,bkc->bqhc", p, C_v)             # [B,1,nh,d_c]

    # absorb bv into the output (activation-level)
    bv_q = rope_lib.expand_kv_to_q(jnp.moveaxis(params["bv"], 1, 0), G)  # [nh,d_c,dh]
    o_heads = jnp.einsum("bqhc,hcd->bqhd", o, bv_q.astype(dt))
    out = jnp.einsum("bshe,hed->bsd", o_heads, params["wo"].astype(dt))
    return out, new_cache


# ---------------------------------------------------------------------------
# paged variants — the cache lives in a shared block pool (serving runtime)
# ---------------------------------------------------------------------------

@jax.named_scope("pool_write")
def _scatter_pages(pages, k_e_new, c_k_new, c_v_new, slot_mapping):
    """Write per-token compressed streams into pool pages at flat slots.
    Out-of-range slots (the inactive-lane / prompt-padding sentinel) are
    dropped.  k_e_new [N,nkv,2r] lands as the pool's flat [N, nkv·2r] rows,
    c_*_new [N,dc], slot_mapping [N].

    Quantized pool (``"k_e_scale" in pages``, see ``core/quant.py``): each
    token row is symmetric-absmax quantized to int8 *here, at write time* —
    a pure function of the row, so chunked/one-shot/preempted/speculative
    write orders all land bit-identical pages — and the per-slot f32 scale is
    scattered beside it through the same drop sentinel."""
    new = dict(pages)
    quantized = "k_e_scale" in pages

    def put(name, val):
        buf = pages[name]
        if quantized:
            q, s = quant.quantize_rows(val)
            new[name] = buf.at[slot_mapping].set(q, mode="drop")
            new[name + "_scale"] = pages[name + "_scale"].at[
                slot_mapping].set(s, mode="drop")
        else:
            new[name] = buf.at[slot_mapping].set(
                val.astype(buf.dtype), mode="drop")

    put("k_e", k_e_new.reshape(k_e_new.shape[0], -1))
    if "c" in pages:
        put("c", c_k_new)
    else:
        put("c_k", c_k_new)
        put("c_v", c_v_new)
    lat_key = "c" if "c" in pages else "c_k"
    if lat_key + "_blkmean" in pages:
        _update_block_summaries(new, lat_key, slot_mapping)
    return new


def _update_block_summaries(pages, key, slot_mapping):
    """Refresh the per-block latent summary rows touched by a scatter.

    ``pages[key + "_blkmean"]/[key + "_blkmax"]`` are [n_blocks, d_c] f32
    (core/cache.py block-summary leaves).  For every written slot's block,
    recompute the masked mean / absmax over that block's VALID rows from the
    just-updated pool content (dequantized for an int8 pool, so summaries are
    always f32 statistics of what attention will actually read).  Valid-row
    count = max written offset + 1 — writes within a block are sequential, so
    the newest offset in this call is the block's live height; a truncated
    block (speculative rejection / preemption) is re-summarized by its next
    write before any read.  Duplicate blocks in one call first scatter-max
    their offsets, then every duplicate writes the identical summary —
    order-independent.  Mutates ``pages`` in place (callers own the dict).
    """
    mean_buf = pages[key + "_blkmean"]
    n_blocks, d_c = mean_buf.shape
    content = pages[key]                                     # post-write
    n_slots = content.shape[0]
    bs = n_slots // n_blocks
    blk = slot_mapping // bs                                 # [N] (oob → drop)
    off = slot_mapping % bs
    maxoff = jnp.zeros((n_blocks,), jnp.int32).at[blk].max(
        off + 1, mode="drop")
    counts = maxoff[blk]                                     # per-entry, agree
    rows_idx = (blk * bs)[:, None] + jnp.arange(bs)[None, :]  # [N, bs]
    rows_idx = jnp.clip(rows_idx, 0, n_slots - 1)
    rows = content[rows_idx].astype(jnp.float32)             # [N, bs, d_c]
    if key + "_scale" in pages:
        rows = rows * pages[key + "_scale"][rows_idx][..., None]
    mask = (jnp.arange(bs)[None, :] < counts[:, None])[..., None]
    cnt = jnp.maximum(counts, 1).astype(jnp.float32)[:, None]
    mean = jnp.where(mask, rows, 0.0).sum(axis=1) / cnt
    amax = jnp.max(jnp.where(mask, jnp.abs(rows), 0.0), axis=1)
    pages[key + "_blkmean"] = mean_buf.at[blk].set(mean, mode="drop")
    pages[key + "_blkmax"] = pages[key + "_blkmax"].at[blk].set(
        amax, mode="drop")


def _page_latents(pages):
    """``(c_k, c_v)`` pool streams; ``c_v`` is ``None`` for J-LRD's joint
    ``c``, which the paged kernels then fetch once for both roles."""
    if "c" in pages:
        return pages["c"], None
    return pages["c_k"], pages["c_v"]


def _page_scales(pages):
    """Per-slot quantization scales ``(k_e, c_k, c_v)`` — None for an
    unquantized (f32) pool.  J-LRD shares one latent scale for both roles
    (``c_v`` scale ``None``, as in ``_page_latents``)."""
    if "k_e_scale" not in pages:
        return None
    if "c" in pages:
        return pages["k_e_scale"], pages["c_scale"], None
    return pages["k_e_scale"], pages["c_k_scale"], pages["c_v_scale"]


def _tp(mesh, tp_axis: str) -> int:
    """Tensor-parallel width of ``mesh`` (1 when unsharded / axis absent)."""
    if mesh is None or tp_axis not in mesh.shape:
        return 1
    return mesh.shape[tp_axis]


def _pin(mesh, x, *spec):
    """Constrain ``x`` to ``PartitionSpec(*spec)`` on ``mesh``.

    Used to force gathered pool reads back to *replicated* before any
    cross-head reduction: the ``k_e`` pages are head-sharded, and without the
    pin GSPMD propagates that sharding into the ``wo`` contraction, summing
    shard partials in a different float order than single-device — which
    breaks the bit-identity serving wall."""
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))


def _pin_projections(mesh, *xs):
    """Pin per-head projections to *replicated* under TP, so every device
    computes them at single-device shapes.  Left free, GSPMD shards them
    along heads (from the head-sharded ``k_e`` pages and the shard_map
    inputs), and the TPU compiles those narrower dots with a different float
    order than single-device — which breaks the bit-identity serving wall."""
    return tuple(_pin(mesh, x) for x in xs)


def _gather_prefix(pages, params, cfg, block_tables, block_size: int, dt):
    """Materialize K/V for a sequence's cached *prefix* from pool pages.

    block_tables [B, mb] → K_pre [B, mb·bs, nkv, dh], V_pre [B, mb·bs, nkv, dh].
    Positions past the live prefix length land on pool blocks owned by other
    sequences (or the pad block 0) — the caller masks them by ``prefix_lens``.
    The gather reads only the compressed 2r·n_kv + d_ckv floats/token and
    up-projects through bk/bv, mirroring ``kernels.ops.elite_decode_paged``'s
    XLA fallback.
    """
    B, mb = block_tables.shape

    def gather(stream):
        paged = stream.reshape((-1, block_size) + stream.shape[1:])
        return paged[block_tables].reshape((B, mb * block_size) + stream.shape[1:])

    k_e_pre = gather(pages["k_e"]).astype(dt)                # [B,P,nkv·2r]
    c_k_pre, c_v_pre = _page_latents(pages)
    c_v_pre = c_k_pre if c_v_pre is None else c_v_pre
    c_k_pre, c_v_pre = gather(c_k_pre).astype(dt), gather(c_v_pre).astype(dt)
    scales = _page_scales(pages)
    if scales is not None:
        # int8 pool: dequantize the gathered prefix rows before up-projecting
        # (one multiply by the per-slot scale — core/quant.py)
        ks, cks, cvs = scales
        cvs = cks if cvs is None else cvs
        ks, cks, cvs = (gather(s).astype(dt) for s in (ks, cks, cvs))  # [B,P]
        k_e_pre = k_e_pre * ks[..., None]
        c_k_pre = c_k_pre * cks[..., None]
        c_v_pre = c_v_pre * cvs[..., None]
    k_e_pre = k_e_pre.reshape(B, mb * block_size, cfg.n_kv_heads, -1)
    k_ne_pre = jnp.einsum("bsc,che->bshe", c_k_pre, params["bk"].astype(dt))
    v_pre = jnp.einsum("bsc,che->bshe", c_v_pre, params["bv"].astype(dt))
    return jnp.concatenate([k_e_pre, k_ne_pre], axis=-1), v_pre


def _attend_resumed(q, k_pre, v_pre, k_cur, v_cur, prefix_lens, q_group: int,
                    scale: float, constrain=lambda n, t: t):
    """Attention for a batch of resumed prefill chunks: lane ``b``'s queries
    see that lane's cached prefix (key j valid iff j < prefix_lens[b] — the
    gather window is padded with foreign blocks) plus its current chunk
    causally.  Everything is per lane, so chunks of *different* sequences at
    different offsets pack into one call; ``prefix_lens[b] == 0`` reduces
    lane ``b`` to ordinary causal prefill (fresh chunk), and all-pad lanes
    produce garbage rows that the caller never reads (their pool writes hit
    the drop sentinel).  q/k_cur/v_cur [B,S,*,dh], k_pre/v_pre [B,P,nkv,dh],
    prefix_lens [B] int32.  → [B,S,nh,dh]."""
    B, S = q.shape[:2]
    P = k_pre.shape[1]
    k = jnp.concatenate([k_pre, k_cur], axis=1)
    v = jnp.concatenate([v_pre, v_cur], axis=1)
    if q_group > 1:
        k = constrain("heads4", jnp.repeat(k, q_group, axis=2))
        v = constrain("heads4", jnp.repeat(v, q_group, axis=2))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    pre_ok = jnp.arange(P)[None, :] < prefix_lens[:, None]   # [B,P]
    cur_ok = jnp.tril(jnp.ones((S, S), bool))                # within-chunk causal
    mask = jnp.concatenate([
        jnp.broadcast_to(pre_ok[:, None, :], (B, S, P)),
        jnp.broadcast_to(cur_ok[None], (B, S, S))], axis=-1) # [B,S,P+S]
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def apply_prefill_paged(params, cfg, buffers, x, positions, pages,
                        slot_mapping, block_tables=None, prefix_lens=None,
                        block_size: int = 0, constrain=lambda n, t: t,
                        mesh=None, tp_axis: str = "model"):
    """Prefill a (chunk of a) sequence and scatter its streams into pool pages.

    Fresh sequences (``block_tables is None``): no prior context, so attention
    is ordinary causal self-attention over the (padded) prompt; only the cache
    *write* is paged.  x [B,S,d]; slot_mapping [B,S] flat pool slots (pad
    positions → sentinel).

    Resumed chunks (chunked prefill): ``positions`` carry the chunks' global
    offsets — [S] when every lane shares one offset, [B,S] when lanes hold
    chunks of different sequences (batched chunked prefill) — and
    ``block_tables`` [B,mb] + ``prefix_lens`` [B] locate each lane's already-
    cached prefix, which is gathered from the pool, up-projected through
    bk/bv, and attended with the per-lane offset causal mask (the XLA
    analogue of ``flash_prefill``'s ``q_offsets``; see docs/serving.md).
    → (out [B,S,d], new_pages)
    """
    from repro.models.attention import _attend
    q, k, v, k_e, c_k, c_v = _materialized(params, cfg, buffers, x, positions,
                                           constrain)
    if _tp(mesh, tp_axis) > 1:
        q, k, v, k_e = _pin_projections(mesh, q, k, v, k_e)
    B, S = x.shape[:2]
    if "k_e_scale" in pages:
        # int8 pool: in-chunk attention must see exactly what a later pool
        # read will dequantize, so round-trip the current chunk's streams
        # before rebuilding K/V — otherwise chunked and one-shot prefill
        # attend over different keys and the golden invariants break
        # (core/quant.py, tests/test_quant.py).  The scatter below still
        # quantizes the RAW streams — the canonical pool content.
        dt = x.dtype
        k_e_rt = quant.roundtrip_rows(k_e, batch_dims=2)
        c_k_rt = quant.roundtrip_rows(c_k, batch_dims=2)
        c_v_rt = quant.roundtrip_rows(c_v, batch_dims=2)
        k_ne = jnp.einsum("bsc,che->bshe", c_k_rt, params["bk"].astype(dt))
        v = constrain("attn_kv", jnp.einsum("bsc,che->bshe", c_v_rt,
                                            params["bv"].astype(dt)))
        k = constrain("attn_kv", jnp.concatenate([k_e_rt, k_ne], axis=-1))
    new_pages = _scatter_pages(
        pages, k_e.reshape(B * S, *k_e.shape[2:]),
        c_k.reshape(B * S, -1), c_v.reshape(B * S, -1),
        slot_mapping.reshape(B * S))
    if block_tables is None:
        o = _attend(q, k, v, cfg.q_group, cfg.head_dim ** -0.5,
                    chunk_q=cfg.attn_chunk_q, constrain=constrain,
                    unroll=cfg.attn_chunk_unroll)
    else:
        k_pre, v_pre = _gather_prefix(pages, params, cfg, block_tables,
                                      block_size, x.dtype)
        if _tp(mesh, tp_axis) > 1:
            # Prefill compute is deliberately *replicated* under TP (only the
            # pool page storage is sharded; the slot scatter needs no
            # communication).  The prefix gather is the one place the
            # head-sharded pages leak into activations — pin it back (_pin).
            k_pre = _pin(mesh, k_pre)
            v_pre = _pin(mesh, v_pre)
        o = _attend_resumed(q, k_pre, v_pre, k, v, prefix_lens, cfg.q_group,
                            cfg.head_dim ** -0.5, constrain=constrain)
    return jnp.einsum("bshe,hed->bsd", o, params["wo"].astype(x.dtype)), new_pages


def apply_verify_paged(params, cfg, buffers, x, pages, slot_mapping,
                       block_tables, q_offsets, lengths, block_size: int,
                       use_kernel: bool = True, constrain=lambda n, t: t,
                       mesh=None, tp_axis: str = "model"):
    """Absorbed multi-query *verify* attention for speculative decode.

    A verify window is a resumed chunk of ``W = k+1`` tokens — the pending
    token plus ``k`` draft proposals — re-scored by the full model in ONE
    forward: lane ``b``'s window starts at global position ``q_offsets[b]``
    and its rows attend offset-causally to the lane's paged prefix *plus* the
    window itself (whose compressed streams are scattered into the pool
    first, exactly like decode — so accepted tokens' cache entries are
    final full-model values and rejected tokens are erased by truncating the
    pool length, never by rewriting pages).

    Unlike chunked-prefill's ``apply_prefill_paged`` (which gathers the
    prefix and *materializes* K/V through bk/bv), verify stays in the
    absorbed latent space end to end — the same compressed-stream roofline
    as decode, with ``W·n_h`` query rows per lane.

    x [B,W,d]; slot_mapping [B,W] flat pool slots (pad → sentinel);
    q_offsets [B] window start positions; lengths [B] live length including
    the window (0 = dead lane → zero output).  → (out [B,W,d], new_pages).
    """
    dt = x.dtype
    B, W = x.shape[:2]
    dh = cfg.head_dim
    G = cfg.q_group
    pos = q_offsets[:, None] + jnp.arange(W)[None, :]        # [B,W] per-lane

    q_e, q_ne = _project_q(params, cfg, x, pos)
    q_e = constrain("attn_q", _rot_q(cfg, buffers, q_e, pos))
    bk_q = rope_lib.expand_kv_to_q(jnp.moveaxis(params["bk"], 1, 0), G)
    q_lat = constrain("attn_q", jnp.einsum("bshn,hcn->bshc", q_ne, bk_q.astype(dt)))

    k_e_new = jnp.einsum("bsd,dhe->bshe", x, params["wk_e"].astype(dt))
    k_e_new = rope_lib.apply_elite_rope(k_e_new, pos, buffers["elite_freqs"])
    c_k_new, c_v_new = _latents(params, cfg, x)
    if _tp(mesh, tp_axis) > 1:
        q_e, q_lat, k_e_new = _pin_projections(mesh, q_e, q_lat, k_e_new)
    new_pages = _scatter_pages(
        pages, k_e_new.reshape(B * W, *k_e_new.shape[2:]),
        c_k_new.reshape(B * W, -1), c_v_new.reshape(B * W, -1),
        slot_mapping.reshape(B * W))

    from repro.kernels import ops as kops
    K_e, (C_k, C_v) = new_pages["k_e"], _page_latents(new_pages)
    scales = _page_scales(new_pages)
    if _tp(mesh, tp_axis) > 1:
        o = kops.elite_verify_paged_tp(
            q_e, q_lat, K_e, C_k, C_v, scales, block_tables, q_offsets,
            lengths, q_group=G, scale=dh ** -0.5, block_size=block_size,
            mesh=mesh, tp_axis=tp_axis, force_xla=not use_kernel)
    elif scales is None:
        o = kops.elite_verify_paged(
            q_e, q_lat, K_e, C_k, C_v, block_tables, q_offsets, lengths,
            q_group=G, scale=dh ** -0.5, block_size=block_size,
            force_xla=not use_kernel)
    else:
        o = kops.elite_verify_paged_q8(
            q_e, q_lat, K_e, C_k, C_v, *scales, block_tables, q_offsets,
            lengths, q_group=G, scale=dh ** -0.5, block_size=block_size,
            force_xla=not use_kernel)
    o = o.astype(dt)                                         # [B,W,nh,d_c]

    bv_q = rope_lib.expand_kv_to_q(jnp.moveaxis(params["bv"], 1, 0), G)
    o_heads = jnp.einsum("bqhc,hcd->bqhd", o, bv_q.astype(dt))
    out = jnp.einsum("bshe,hed->bsd", o_heads, params["wo"].astype(dt))
    return out, new_pages


def apply_decode_paged(params, cfg, buffers, x, pages, slot_mapping,
                       block_tables, lengths, block_size: int,
                       use_kernel: bool = True, constrain=lambda n, t: t,
                       mesh=None, tp_axis: str = "model",
                       sparse_topk: int = 0, sparse_recent: int = 0):
    """Absorbed decode over the block pool — one token per serving slot.

    x [B,1,d]; lengths [B] live length *including* the new token (0 for
    inactive lanes, whose writes hit the sentinel slot and whose attention
    output is zeroed); slot_mapping [B]; block_tables [B,max_blocks].
    → (out [B,1,d], new_pages)

    ``sparse_topk > 0`` switches to latent-space sparse decode: the query is
    scored against the pool's per-block summaries (written by the scatter
    above, so the newest token is always visible) and only the top-k blocks
    plus the ``sparse_recent`` newest are attended — O(k·block) per token.
    Requires a ``block_summaries=True`` pool.  Selection runs on the FULL-head
    query before any tensor-parallel split, so every shard walks identical
    blocks.  ``sparse_topk + sparse_recent >= max_blocks`` selects the whole
    chain and is bit-identical to dense (docs/serving.md, tests/test_sparse.py).
    """
    dt = x.dtype
    B = x.shape[0]
    nh, dh = cfg.n_heads, cfg.head_dim
    G = cfg.q_group
    pos = (lengths - 1)[:, None]                             # [B,1] per-lane

    q_e, q_ne = _project_q(params, cfg, x, pos)
    q_e = constrain("attn_q", _rot_q(cfg, buffers, q_e, pos))
    bk_q = rope_lib.expand_kv_to_q(jnp.moveaxis(params["bk"], 1, 0), G)
    q_lat = constrain("attn_q", jnp.einsum("bshn,hcn->bshc", q_ne, bk_q.astype(dt)))

    k_e_new = jnp.einsum("bsd,dhe->bshe", x, params["wk_e"].astype(dt))
    k_e_new = rope_lib.apply_elite_rope(k_e_new, pos, buffers["elite_freqs"])
    c_k_new, c_v_new = _latents(params, cfg, x)
    if _tp(mesh, tp_axis) > 1:
        q_e, q_lat, k_e_new = _pin_projections(mesh, q_e, q_lat, k_e_new)
    new_pages = _scatter_pages(pages, k_e_new[:, 0], c_k_new[:, 0],
                               c_v_new[:, 0], slot_mapping)

    from repro.kernels import ops as kops
    K_e, (C_k, C_v) = new_pages["k_e"], _page_latents(new_pages)
    scales = _page_scales(new_pages)
    if sparse_topk > 0:
        lat_key = "c" if "c" in new_pages else "c_k"
        mb = block_tables.shape[1]
        num_sel = min(sparse_topk + sparse_recent, mb)
        sel_tables, sel_counts = kops.select_topk_blocks(
            q_lat.reshape(B, nh, -1).astype(jnp.float32),
            new_pages[lat_key + "_blkmean"], new_pages[lat_key + "_blkmax"],
            block_tables, lengths, block_size, num_sel, sparse_recent)
        if _tp(mesh, tp_axis) > 1:
            o = kops.elite_decode_sparse_paged_tp(
                q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k,
                C_v, scales, sel_tables, sel_counts, q_group=G,
                scale=dh ** -0.5, block_size=block_size, mesh=mesh,
                tp_axis=tp_axis, force_xla=not use_kernel)
        elif scales is None:
            o = kops.elite_decode_sparse_paged(
                q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k,
                C_v, sel_tables, sel_counts, q_group=G, scale=dh ** -0.5,
                block_size=block_size, force_xla=not use_kernel)
        else:
            o = kops.elite_decode_sparse_paged_q8(
                q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k,
                C_v, *scales, sel_tables, sel_counts, q_group=G,
                scale=dh ** -0.5, block_size=block_size,
                force_xla=not use_kernel)
    elif _tp(mesh, tp_axis) > 1:
        o = kops.elite_decode_paged_tp(
            q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k, C_v,
            scales, block_tables, lengths, q_group=G, scale=dh ** -0.5,
            block_size=block_size, mesh=mesh, tp_axis=tp_axis,
            force_xla=not use_kernel)
    elif scales is None:
        o = kops.elite_decode_paged(
            q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k, C_v,
            block_tables, lengths, q_group=G, scale=dh ** -0.5,
            block_size=block_size, force_xla=not use_kernel)
    else:
        o = kops.elite_decode_paged_q8(
            q_e.reshape(B, nh, -1), q_lat.reshape(B, nh, -1), K_e, C_k, C_v,
            *scales, block_tables, lengths, q_group=G, scale=dh ** -0.5,
            block_size=block_size, force_xla=not use_kernel)
    o = o.reshape(B, 1, nh, C_k.shape[-1]).astype(dt)

    bv_q = rope_lib.expand_kv_to_q(jnp.moveaxis(params["bv"], 1, 0), G)
    o_heads = jnp.einsum("bqhc,hcd->bqhd", o, bv_q.astype(dt))
    out = jnp.einsum("bshe,hed->bsd", o_heads, params["wo"].astype(dt))
    return out, new_pages
