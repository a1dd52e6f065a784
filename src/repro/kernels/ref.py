"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _decode_masked(q_e, q_lat, k_e, c_k, c_v, valid, q_group: int,
                   scale: float) -> jnp.ndarray:
    """Shared decode-attention core with an explicit key-validity mask
    ``valid [B, 1, S]``.  Both the dense and the sparse paged oracles route
    through here, so when their gathered arrays and masks are equal the
    outputs are *bitwise* equal — the sparse ``k >= n_blocks`` identity wall
    rests on this sharing."""
    B, nh, r2 = q_e.shape
    nkv = k_e.shape[2]
    S = k_e.shape[1]
    qe_g = q_e.reshape(B, nkv, q_group, r2)
    s_e = jnp.einsum("bhge,bkhe->bhgk", qe_g, k_e, preferred_element_type=jnp.float32)
    s_e = s_e.reshape(B, nh, S)
    s_lat = jnp.einsum("bhc,bkc->bhk", q_lat, c_k, preferred_element_type=jnp.float32)
    s = (s_e + s_lat) * scale
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible key (empty serving slots) attend to nothing →
    # zero output (softmax over an all-masked row would otherwise yield a
    # uniform p)
    p = jnp.where(jnp.any(valid, axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhk,bkc->bhc", p.astype(c_v.dtype), c_v)


def elite_decode_ref(q_e, q_lat, k_e, c_k, c_v, lengths, q_group: int,
                     scale: float) -> jnp.ndarray:
    """Absorbed EliteKV decode attention.

    q_e   [B, nh, 2r]   rotated elite query
    q_lat [B, nh, dc]   bk-absorbed non-elite query
    k_e   [B, S, nkv, 2r]  rotated elite key cache
    c_k   [B, S, dc]    latent cache (K side)
    c_v   [B, S, dc]    latent cache (V side; same array under J-LRD)
    lengths [B] int32   valid cache length per sequence
    →     [B, nh, dc]   latent attention output (pre bv/wo absorption)
    """
    S = k_e.shape[1]
    valid = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    return _decode_masked(q_e, q_lat, k_e, c_k, c_v, valid, q_group, scale)


def _split_kv_heads(k_e_pages, q_e, q_group: int):
    """Pool rows [n_slots, nkv·2r] → [n_slots, nkv, 2r]: kv head h holds
    columns [h·2r, (h+1)·2r) of a row (nkv from the query heads)."""
    return k_e_pages.reshape(k_e_pages.shape[0], q_e.shape[-2] // q_group, -1)


def elite_decode_paged_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                           block_tables, lengths, q_group: int, scale: float,
                           block_size: int) -> jnp.ndarray:
    """Paged EliteKV decode attention: gather pages, then the dense oracle.

    k_e_pages  [n_slots, nkv·2r]    paged elite-key rows (kv head h in
                                    columns [h·2r, (h+1)·2r))
    c_k_pages  [n_slots, dc]        flat paged latent stream (K side)
    c_v_pages  [n_slots, dc]        flat paged latent stream (V side)
    block_tables [B, max_blocks] int32   per-sequence block chains (pad = 0)
    lengths    [B] int32            live tokens per sequence (0 = empty slot)
    n_slots = num_blocks · block_size; token t of logical position p lives in
    flat slot  block_tables[b, p // block_size] · block_size + p % block_size.
    →          [B, nh, dc]
    """
    B, mb = block_tables.shape

    def gather(pages):
        paged = pages.reshape((-1, block_size) + pages.shape[1:])
        return paged[block_tables].reshape((B, mb * block_size) + pages.shape[1:])

    k_e_pages = _split_kv_heads(k_e_pages, q_e, q_group)
    return elite_decode_ref(q_e, q_lat, gather(k_e_pages), gather(c_k_pages),
                            gather(c_v_pages), lengths, q_group, scale)


def select_topk_blocks(q_lat, blk_mean, blk_max, block_tables, lengths,
                       block_size: int, num_sel: int, recent: int):
    """Score resident blocks in latent space, pick the winners + recent tail.

    q_lat   [B, nh, dc]            bk-absorbed query (ALL heads — selection
                                   must be shard-invariant under TP)
    blk_mean/blk_max [n_blocks, dc]  per-block latent summaries (valid-row
                                   masked mean / absmax, f32)
    block_tables [B, mb] int32; lengths [B] int32; ``num_sel`` = total
    selection width W (top-k + recent tail); ``recent`` newest resident
    blocks are always forced in.

    score_j = Σ_h q_lat·mean_j + |q_lat|·absmax_j — the mean term estimates
    the block's average logit, the absmax term upper-bounds its peak.

    Returns ``(sel_tables [B, W] int32 physical block ids,
    sel_counts [B, W] int32 valid rows per selected block)``.  Selected
    logical indices are sorted ASCENDING so the sparse kernels accumulate
    in dense chain order; with ``W >= n_chain`` the selection is exactly
    the full chain and sparse decode is bit-identical to dense.
    """
    B, mb = block_tables.shape
    bs = block_size
    n_chain = -(-lengths // bs)                              # ceil, [B]
    j = jnp.arange(mb, dtype=jnp.int32)[None, :]             # logical index
    mean = blk_mean[block_tables]                            # [B, mb, dc]
    amax = blk_max[block_tables]
    score = (jnp.einsum("bhc,bjc->bj", q_lat, mean,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhc,bjc->bj", jnp.abs(q_lat), amax,
                          preferred_element_type=jnp.float32))
    resident = j < n_chain[:, None]
    tail = resident & (j >= n_chain[:, None] - recent)
    score = jnp.where(resident, score, -1e30)
    score = jnp.where(tail, 1e30, score)                     # force recents
    sel = jax.lax.top_k(score, min(num_sel, mb))[1]          # [B, W]
    sel = jnp.sort(sel, axis=-1).astype(jnp.int32)
    sel_tables = jnp.take_along_axis(block_tables, sel, axis=1)
    sel_counts = jnp.clip(lengths[:, None] - sel * bs, 0, bs).astype(jnp.int32)
    return sel_tables, sel_counts


def _sparse_valid(sel_counts, block_size: int):
    """[B, W] per-block counts → [B, 1, W·bs] row-validity mask.  For the
    full chain this equals the dense ``pos < length`` mask elementwise."""
    B, W = sel_counts.shape
    offs = jnp.tile(jnp.arange(block_size, dtype=jnp.int32), W)   # [W·bs]
    counts = jnp.repeat(sel_counts, block_size, axis=1)           # [B, W·bs]
    return (offs[None, :] < counts)[:, None, :]


def elite_decode_sparse_paged_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                  sel_tables, sel_counts, q_group: int,
                                  scale: float, block_size: int) -> jnp.ndarray:
    """Sparse paged decode oracle: gather only the SELECTED blocks, then the
    shared masked core.  ``sel_tables/sel_counts [B, W]`` come from
    ``select_topk_blocks``; a count of 0 contributes nothing (pad = block 0).
    With the full chain selected the gathered arrays and mask equal the dense
    oracle's → bitwise-identical output."""
    B, W = sel_tables.shape

    def gather(pages):
        paged = pages.reshape((-1, block_size) + pages.shape[1:])
        return paged[sel_tables].reshape((B, W * block_size) + pages.shape[1:])

    valid = _sparse_valid(sel_counts, block_size)
    k_e_pages = _split_kv_heads(k_e_pages, q_e, q_group)
    return _decode_masked(q_e, q_lat, gather(k_e_pages), gather(c_k_pages),
                          gather(c_v_pages), valid, q_group, scale)


def elite_verify_ref(q_e, q_lat, k_e, c_k, c_v, q_offsets, lengths,
                     q_group: int, scale: float) -> jnp.ndarray:
    """Multi-query absorbed EliteKV *verify* attention (speculative decode).

    A verify window is a resumed chunk of ``W`` tokens: lane ``b``'s query
    row ``w`` sits at global position ``q_offsets[b] + w`` and sees cache key
    ``j`` iff  ``j <= q_offsets[b] + w``  and  ``j < lengths[b]`` — the
    offset-causal mask of ``flash_prefill_ref`` applied in the compressed
    latent space of ``elite_decode_ref``.

    q_e   [B, W, nh, 2r]   rotated elite queries (one per window position)
    q_lat [B, W, nh, dc]   bk-absorbed non-elite queries
    k_e   [B, S, nkv, 2r]; c_k/c_v [B, S, dc]; q_offsets/lengths [B] int32
    →     [B, W, nh, dc]   latent outputs.  ``W == 1`` with
    ``q_offsets == lengths - 1`` reduces exactly to ``elite_decode_ref``;
    ``lengths == 0`` lanes output exact zeros.
    """
    B, W, nh, r2 = q_e.shape
    S, nkv = k_e.shape[1], k_e.shape[2]
    qe_g = q_e.reshape(B, W, nkv, q_group, r2)
    ql_g = q_lat.reshape(B, W, nkv, q_group, -1)
    s_e = jnp.einsum("bwhge,bkhe->bhgwk", qe_g, k_e,
                     preferred_element_type=jnp.float32)
    s_lat = jnp.einsum("bwhgc,bkc->bhgwk", ql_g, c_k,
                       preferred_element_type=jnp.float32)
    s = (s_e + s_lat) * scale                                # [B,nkv,G,W,S]
    kpos = jnp.arange(S)[None, None, :]
    mask = (kpos <= jnp.arange(W)[None, :, None]
            + q_offsets[:, None, None]) \
        & (kpos < lengths[:, None, None])                    # [B,W,S]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1)[:, None, None, :, None], p, 0.0)
    o = jnp.einsum("bhgwk,bkc->bwhgc", p.astype(c_v.dtype), c_v)
    return o.reshape(B, W, nh, -1)


def elite_verify_paged_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                           block_tables, q_offsets, lengths, q_group: int,
                           scale: float, block_size: int) -> jnp.ndarray:
    """Paged verify attention: gather each lane's block chain, then the dense
    multi-query oracle.  Same page layout as ``elite_decode_paged_ref``;
    q_e/q_lat [B, W, nh, *], q_offsets/lengths [B] → [B, W, nh, dc]."""
    B, mb = block_tables.shape

    def gather(pages):
        paged = pages.reshape((-1, block_size) + pages.shape[1:])
        return paged[block_tables].reshape((B, mb * block_size) + pages.shape[1:])

    k_e_pages = _split_kv_heads(k_e_pages, q_e, q_group)
    return elite_verify_ref(q_e, q_lat, gather(k_e_pages), gather(c_k_pages),
                            gather(c_v_pages), q_offsets, lengths, q_group,
                            scale)


def dequantize_pages(k_e_pages, c_k_pages, c_v_pages,
                     k_e_scale, c_k_scale, c_v_scale):
    """Expand an int8 pool's streams to f32: ``row * per_slot_scale``
    (core/quant.py).  Pages [n_slots, ...], scales [n_slots] f32."""
    from repro.core.quant import dequantize
    return (dequantize(k_e_pages, k_e_scale),
            dequantize(c_k_pages, c_k_scale),
            dequantize(c_v_pages, c_v_scale))


def elite_decode_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              k_e_scale, c_k_scale, c_v_scale,
                              block_tables, lengths, q_group: int,
                              scale: float, block_size: int) -> jnp.ndarray:
    """Quantized-pool decode oracle: dequantize every slot, then the f32
    paged oracle.  The Pallas q8 kernel must match THIS exactly — its fused
    in-register dequant is algebraically the same multiply."""
    k_e, c_k, c_v = dequantize_pages(k_e_pages, c_k_pages, c_v_pages,
                                     k_e_scale, c_k_scale, c_v_scale)
    return elite_decode_paged_ref(q_e, q_lat, k_e, c_k, c_v, block_tables,
                                  lengths, q_group, scale, block_size)


def elite_decode_sparse_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, k_e_scale, c_k_scale,
                                     c_v_scale, sel_tables, sel_counts,
                                     q_group: int, scale: float,
                                     block_size: int) -> jnp.ndarray:
    """Quantized sparse decode oracle: dequantize every slot, then the f32
    sparse oracle — the same contract as ``elite_decode_paged_q8_ref``."""
    k_e, c_k, c_v = dequantize_pages(k_e_pages, c_k_pages, c_v_pages,
                                     k_e_scale, c_k_scale, c_v_scale)
    return elite_decode_sparse_paged_ref(q_e, q_lat, k_e, c_k, c_v,
                                         sel_tables, sel_counts, q_group,
                                         scale, block_size)


def elite_verify_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              k_e_scale, c_k_scale, c_v_scale,
                              block_tables, q_offsets, lengths, q_group: int,
                              scale: float, block_size: int) -> jnp.ndarray:
    """Quantized-pool verify oracle: dequantize, then the f32 paged verify
    oracle (same contract as ``elite_decode_paged_q8_ref``)."""
    k_e, c_k, c_v = dequantize_pages(k_e_pages, c_k_pages, c_v_pages,
                                     k_e_scale, c_k_scale, c_v_scale)
    return elite_verify_paged_ref(q_e, q_lat, k_e, c_k, c_v, block_tables,
                                  q_offsets, lengths, q_group, scale,
                                  block_size)


def flash_prefill_ref(q, k, v, q_group: int, scale: float,
                      q_offset=0, kv_lens=None) -> jnp.ndarray:
    """Causal attention oracle.  q [B,Sq,nh,dh], k/v [B,Sk,nkv,dh] → [B,Sq,nh,dh].

    ``q_offset`` shifts the causal diagonal (resumed prefill chunks): key j
    is visible to query i of lane b iff  j <= i + q_offset[b]  and
    j < kv_lens[b].  Scalars broadcast; per-lane [B] vectors let one batch
    hold chunks resumed from different sequences (batched chunked prefill).
    Queries with no visible key (kv_lens == 0 lanes) attend to nothing and
    output exact zeros, mirroring the length-0 decode semantics.
    """
    B, Sq, nh, dh = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    lens = (jnp.full((B,), Sk, jnp.int32) if kv_lens is None
            else jnp.asarray(kv_lens, jnp.int32))
    qg = q.reshape(B, Sq, nkv, q_group, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(Sk)[None, None, :]
    mask = (kpos <= jnp.arange(Sq)[None, :, None] + offs[:, None, None]) \
        & (kpos < lens[:, None, None])                       # [B,Sq,Sk]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1)[:, None, None, ..., None], p, 0.0)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, nh, dh)


def rope_elite_ref(x, positions, freqs) -> jnp.ndarray:
    """Per-head rotary on packed elite dims.

    x [B,S,H,2r], positions [S], freqs [H,r] → rotated x.
    """
    from repro.core.rope import apply_elite_rope
    return apply_elite_rope(x, positions, freqs)
