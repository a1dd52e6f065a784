"""Fused EliteKV decode-attention Pallas kernels (the paper's serving hot-spot).

One pass over the *compressed* cache computes, for every query head of a lane:

    s   = q_e · K_eᵀ + q_lat · C_kᵀ          (rotary-elite + latent scores)
    p   = online_softmax(s · scale)           (masked by per-sequence length)
    o   = p · C_v                             (latent output)

Decode attention is HBM-bandwidth-bound: the roofline is "read the cache
once".  Because this kernel reads only the 2r·n_kv + d_ckv compressed stream
(vs 2·d_h·n_kv uncompressed) its bandwidth roofline improves by exactly the
paper's compression ratio — and fusing both score paths means the latent C is
read once and serves s_lat *and* the output GEMM.

Layout (what the TPU lowering accepts): the grid is (B, cache blocks), and
one step holds ALL heads of a lane against one block, so every block's last
two dims are whole array dims and no step slices one kv head out of the
second-minor axis.
  - K_e blocks are [block, nkv·2r]: the pool's rows, kv head h in columns
    [h·2r, (h+1)·2r).
    The rotary query arrives block-diagonal, [nh, nkv·2r], with head n's 2r
    dims in the lanes of its kv head n // G and zeros elsewhere
    (``_block_diag_q_e``), so one dot scores every head's elite part.
  - C_k / C_v blocks are [block, d_c], read once per lane and block and
    shared by all nh heads (the latent is head-shared).
  - int8 scales are viewed as [n_blocks, 1, block]: they scale the score
    columns and the softmax weights along lanes, never a page's rows.
Per step the working set is the lane's queries [rows, nkv·2r + d_c], one
block [block, nkv·2r + 2·d_c] and the accumulators [rows, d_c] — tens of KiB
at published widths.  Lengths, block tables and selections arrive via
scalar prefetch (ragged serving batches).

Final stage of the docs/architecture.md pipeline: the streams these kernels
read are produced by RoPElite selection (core/ropelite.py) + J-LRD
factorization (core/lrd.py) and live in the paged pool docs/serving.md
describes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_diag_q_e(q_e, nkv: int):
    """[..., nh, 2r] → [..., nh, nkv·2r]: head n's rotary query sits in the
    lanes of its kv head n // G, zeros elsewhere.  A dot of this against a
    flat K_e block [block, nkv·2r] equals each head's dot with its own kv
    head (the extra terms are exact zeros)."""
    nh, r2 = q_e.shape[-2:]
    own = jnp.arange(nh)[:, None] // (nh // nkv) == jnp.arange(nkv)[None, :]
    q_bd = q_e[..., :, None, :] * own[:, :, None].astype(q_e.dtype)
    return q_bd.reshape(q_e.shape[:-2] + (nh, nkv * r2))


def _flat_pages(block_size: int, k_e_pages, c_k_pages, c_v_pages, *scales):
    """Pool streams [n_slots, width] → per-block views, a plain split of the
    slot axis: K_e [n_blocks, block, nkv·2r], C_k/C_v [n_blocks, block, d_c],
    int8 scales [n_blocks, 1, block]."""
    n_slots = k_e_pages.shape[0]
    assert n_slots % block_size == 0, (k_e_pages.shape, block_size)
    nb = n_slots // block_size
    return (*(p.reshape(nb, block_size, -1)
              for p in (k_e_pages, c_k_pages, c_v_pages)),
            *(s.reshape(nb, 1, block_size) for s in scales))


def _nt(a, b):
    """a [m, k] · b [n, k]ᵀ → [m, n] f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _scores(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, scale: float):
    """Scaled scores [rows, block] of the lane's query rows against one
    block of an f32/bf16 pool."""
    s = _nt(q_e_ref[0], k_e_ref[0])
    s += _nt(q_lat_ref[0], c_k_ref[0])
    return s * scale


def _scores_q8(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, k_s_ref, ck_s_ref,
               scale: float):
    """``_scores`` over int8 pages: dequantization is fused as a per-slot
    scale on each score column (``q·(k·s) = (q·k)·s``) — the HBM read stays
    one byte per element, the math stays f32."""
    s = _nt(q_e_ref[0], k_e_ref[0].astype(jnp.float32)) * k_s_ref[0]
    s += _nt(q_lat_ref[0], c_k_ref[0].astype(jnp.float32)) * ck_s_ref[0]
    return s * scale


def _accumulate(s, c_v, acc_ref, m_ref, l_ref, v_scale=None):
    """Online-softmax update of (acc, m, l) with one block of masked scores
    ``s [rows, block]`` and values ``c_v [block, d_c]``; ``v_scale [1, block]``
    (int8 pools) scales the weights of each value row."""
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    w = p.astype(c_v.dtype) if v_scale is None else p * v_scale
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        w, c_v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _finish(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kernel(lengths_ref,                  # scalar-prefetch [B] int32
            q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
            o_ref,
            acc_ref, m_ref, l_ref,
            *, block_s: int, scale: float, n_blocks: int):
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    length = lengths_ref[b]
    start = sb * block_s

    @pl.when(start < length)
    def _step():
        s = _scores(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, scale)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(jnp.where(pos < length, s, NEG_INF), c_v_ref[0],
                    acc_ref, m_ref, l_ref)

    @pl.when(sb == n_blocks - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def elite_decode(q_e, q_lat, k_e, c_k, c_v, lengths, q_group: int,
                 scale: float, block_s: int = 512, interpret: bool = False):
    """See kernels/ref.py::elite_decode_ref for exact semantics.

    q_e [B,nh,2r], q_lat [B,nh,d_c], k_e [B,S,nkv,2r], c_k/c_v [B,S,d_c],
    lengths [B] int32  →  o [B,nh,d_c]
    """
    B, nh, r2 = q_e.shape
    S, nkv = k_e.shape[1], k_e.shape[2]
    d_c = c_k.shape[-1]
    assert nh == nkv * q_group, (nh, nkv, q_group)
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    n_blocks = S // block_s
    K = nkv * r2

    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, scale=scale, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks),
            in_specs=[
                pl.BlockSpec((1, nh, K), lambda b, s, L: (b, 0, 0)),
                pl.BlockSpec((1, nh, d_c), lambda b, s, L: (b, 0, 0)),
                pl.BlockSpec((1, block_s, K), lambda b, s, L: (b, s, 0)),
                pl.BlockSpec((1, block_s, d_c), lambda b, s, L: (b, s, 0)),
                pl.BlockSpec((1, block_s, d_c), lambda b, s, L: (b, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, nh, d_c), lambda b, s, L: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((nh, d_c), jnp.float32),
                pltpu.VMEM((nh, 1), jnp.float32),
                pltpu.VMEM((nh, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, d_c), c_v.dtype),
        interpret=interpret,
        name="elite_decode",
    )(lengths, _block_diag_q_e(q_e, nkv), q_lat, k_e.reshape(B, S, K), c_k, c_v)


# ---------------------------------------------------------------------------
# paged kernels: the cache lives in a block pool, sequences own block chains
# ---------------------------------------------------------------------------

def _paged_call(kernel, name: str, scalars, q_e, q_lat, blocks,
                out_dtype, interpret: bool):
    """The ``pallas_call`` every paged kernel shares.  Grid (B, walk): step
    (b, s) holds lane b's query rows and pool block ``scalars[0][b, s]`` —
    the first scalar-prefetch operand is the block table (or the sparse
    selection table), so the index maps pull pages straight from the pool
    and no gather ever materializes a sequence.  ``blocks`` are the
    ``_flat_pages`` views; q_e [B, rows, nkv·2r] (block-diagonal), q_lat
    [B, rows, d_c] → o [B, rows, d_c]."""
    B, rows, K = q_e.shape
    d_c = q_lat.shape[-1]
    block_size = blocks[0].shape[1]

    def lane(w):
        return pl.BlockSpec((1, rows, w), lambda b, s, *_: (b, 0, 0))

    def page(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b, s, tbl, *_: (tbl[b, s], 0, 0))

    in_specs = [lane(K), lane(d_c), page((block_size, K)),
                page((block_size, d_c)), page((block_size, d_c))]
    in_specs += [page((1, block_size))] * (len(blocks) - 3)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, scalars[0].shape[1]),
            in_specs=in_specs,
            out_specs=lane(d_c),
            scratch_shapes=[
                pltpu.VMEM((rows, d_c), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, d_c), out_dtype),
        interpret=interpret,
        name=name,
    )(*scalars, q_e, q_lat, *blocks)


def _check_heads(q_e, k_e_pages, q_group: int) -> int:
    """kv heads of a paged call, from the query heads: k_e_pages rows are
    [nkv·2r] wide."""
    nh, r2 = q_e.shape[-2:]
    nkv = nh // q_group
    assert nh == nkv * q_group, (nh, q_group)
    assert k_e_pages.shape[1:] == (nkv * r2,), (k_e_pages.shape, nkv, r2)
    return nkv


def _paged_kernel(block_tables_ref,           # scalar-prefetch [B, mb] int32
                  *refs, block_size: int, scale: float, max_blocks: int):
    """``_kernel`` with grid dim 1 walking the *block table* instead of a
    contiguous S axis: only the BlockSpec index maps read the table, pulling
    page ``block_tables[b, sb]`` straight from the pool, so no gather ever
    materializes the sequence."""
    del block_tables_ref
    _kernel(*refs, block_s=block_size, scale=scale, n_blocks=max_blocks)


def elite_decode_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                       block_tables, lengths, q_group: int, scale: float,
                       block_size: int, interpret: bool = False):
    """See kernels/ref.py::elite_decode_paged_ref for exact semantics.

    q_e [B,nh,2r], q_lat [B,nh,d_c], k_e_pages [n_slots,nkv·2r],
    c_k/c_v_pages [n_slots,d_c], block_tables [B,mb] int32, lengths [B] int32
    →  o [B,nh,d_c].  Length-0 sequences (empty slots) produce zeros.
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, mb = block_tables.shape
    assert q_e.shape[0] == B and lengths.shape == (B,)
    return _paged_call(
        functools.partial(_paged_kernel, block_size=block_size, scale=scale,
                          max_blocks=mb),
        "elite_decode_paged", (block_tables, lengths),
        _block_diag_q_e(q_e, nkv), q_lat,
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages),
        c_v_pages.dtype, interpret)


# ---------------------------------------------------------------------------
# paged decode over an int8 pool: fused dequantization
# ---------------------------------------------------------------------------

def _paged_kernel_q8(block_tables_ref,        # scalar-prefetch [B, mb] int32
                     lengths_ref,             # scalar-prefetch [B] int32
                     q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
                     k_s_ref, ck_s_ref, cv_s_ref,
                     o_ref,
                     acc_ref, m_ref, l_ref,
                     *, block_size: int, scale: float, max_blocks: int):
    """``_paged_kernel`` over int8 pages: the same block-table walk also
    pulls each block's per-slot f32 scales (``_scores_q8``)."""
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    length = lengths_ref[b]
    start = sb * block_size

    @pl.when(start < length)
    def _step():
        s = _scores_q8(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, k_s_ref,
                       ck_s_ref, scale)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(jnp.where(pos < length, s, NEG_INF),
                    c_v_ref[0].astype(jnp.float32), acc_ref, m_ref, l_ref,
                    cv_s_ref[0])

    @pl.when(sb == max_blocks - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def elite_decode_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                          k_e_scale, c_k_scale, c_v_scale,
                          block_tables, lengths, q_group: int, scale: float,
                          block_size: int, interpret: bool = False):
    """See kernels/ref.py::elite_decode_paged_q8_ref for exact semantics.

    Pages as in ``elite_decode_paged`` but int8; ``*_scale`` [n_slots] f32
    per-slot quantization scales.  Output is always f32 (the int8 pages must
    never leak their dtype into the attention output).
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, mb = block_tables.shape
    assert q_e.shape[0] == B and lengths.shape == (B,)
    return _paged_call(
        functools.partial(_paged_kernel_q8, block_size=block_size,
                          scale=scale, max_blocks=mb),
        "elite_decode_paged_q8", (block_tables, lengths),
        _block_diag_q_e(q_e.astype(jnp.float32), nkv),
        q_lat.astype(jnp.float32),
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages,
                    k_e_scale, c_k_scale, c_v_scale),
        jnp.float32, interpret)


# ---------------------------------------------------------------------------
# sparse paged decode: walk a top-k SELECTION of blocks, not the whole chain
# ---------------------------------------------------------------------------

def _sparse_kernel(sel_tables_ref,            # scalar-prefetch [B, W] int32
                   sel_counts_ref,            # scalar-prefetch [B, W] int32
                   q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
                   o_ref,
                   acc_ref, m_ref, l_ref,
                   *, scale: float, num_sel: int):
    """``_paged_kernel`` where grid dim 1 walks ``sel_tables`` — the top-k
    block selection from ``ref.py::select_topk_blocks`` — instead of the full
    block chain.  The length mask becomes a per-block row count
    (``sel_counts[b, sb]``; 0 skips the block entirely), so the kernel does
    O(k·block) work per token.  Selected blocks arrive in ascending chain
    order; with the full chain selected the walk, mask, and accumulation
    order equal the dense kernel's exactly (the bit-identity wall)."""
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    count = sel_counts_ref[b, sb]

    @pl.when(count > 0)
    def _step():
        s = _scores(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, scale)
        off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(jnp.where(off < count, s, NEG_INF), c_v_ref[0],
                    acc_ref, m_ref, l_ref)

    @pl.when(sb == num_sel - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def elite_decode_sparse_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              sel_tables, sel_counts, q_group: int,
                              scale: float, block_size: int,
                              interpret: bool = False):
    """See kernels/ref.py::elite_decode_sparse_paged_ref for exact semantics.

    Pages as in ``elite_decode_paged``; ``sel_tables [B, W]`` int32 physical
    block ids and ``sel_counts [B, W]`` int32 valid rows per selected block
    (0 ⇒ skip; all-0 lanes produce zeros) come from
    ``ref.py::select_topk_blocks``.  →  o [B,nh,d_c].
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, W = sel_tables.shape
    assert q_e.shape[0] == B and sel_counts.shape == (B, W)
    return _paged_call(
        functools.partial(_sparse_kernel, scale=scale, num_sel=W),
        "elite_decode_sparse_paged", (sel_tables, sel_counts),
        _block_diag_q_e(q_e, nkv), q_lat,
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages),
        c_v_pages.dtype, interpret)


def _sparse_kernel_q8(sel_tables_ref,         # scalar-prefetch [B, W] int32
                      sel_counts_ref,         # scalar-prefetch [B, W] int32
                      q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
                      k_s_ref, ck_s_ref, cv_s_ref,
                      o_ref,
                      acc_ref, m_ref, l_ref,
                      *, scale: float, num_sel: int):
    """``_sparse_kernel`` over int8 pages: the selection walk also pulls each
    block's per-slot f32 scales, exactly like ``_paged_kernel_q8``."""
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    count = sel_counts_ref[b, sb]

    @pl.when(count > 0)
    def _step():
        s = _scores_q8(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, k_s_ref,
                       ck_s_ref, scale)
        off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(jnp.where(off < count, s, NEG_INF),
                    c_v_ref[0].astype(jnp.float32), acc_ref, m_ref, l_ref,
                    cv_s_ref[0])

    @pl.when(sb == num_sel - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def elite_decode_sparse_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                 k_e_scale, c_k_scale, c_v_scale,
                                 sel_tables, sel_counts, q_group: int,
                                 scale: float, block_size: int,
                                 interpret: bool = False):
    """See kernels/ref.py::elite_decode_sparse_paged_q8_ref for semantics.

    ``elite_decode_sparse_paged`` over int8 pages + per-slot f32 scales;
    output is always f32.
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, W = sel_tables.shape
    assert q_e.shape[0] == B and sel_counts.shape == (B, W)
    return _paged_call(
        functools.partial(_sparse_kernel_q8, scale=scale, num_sel=W),
        "elite_decode_sparse_paged_q8", (sel_tables, sel_counts),
        _block_diag_q_e(q_e.astype(jnp.float32), nkv),
        q_lat.astype(jnp.float32),
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages,
                    k_e_scale, c_k_scale, c_v_scale),
        jnp.float32, interpret)


# ---------------------------------------------------------------------------
# paged verify: k+1-token speculative windows, multi-query over the block table
# ---------------------------------------------------------------------------

def _verify_kernel(block_tables_ref,          # scalar-prefetch [B, mb] int32
                   q_offsets_ref,             # scalar-prefetch [B] int32
                   lengths_ref,               # scalar-prefetch [B] int32
                   q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
                   o_ref,
                   acc_ref, m_ref, l_ref,
                   *, block_size: int, scale: float, max_blocks: int,
                   n_heads: int):
    """``_paged_kernel`` generalized to ``window · nh`` query rows per lane:
    row ``r`` holds window position ``w = r // nh`` whose global query
    position is ``q_offsets[b] + w``, so the length mask gains the per-row
    offset-causal term of ``flash_prefill``'s diagonal — speculative verify
    scores all ``k+1`` window tokens in one block-table walk over the
    compressed cache."""
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    length = lengths_ref[b]
    q_offset = q_offsets_ref[b]
    start = sb * block_size

    @pl.when(start < length)
    def _step():
        s = _scores(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, scale)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qw = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_heads
        _accumulate(jnp.where((pos <= q_offset + qw) & (pos < length), s,
                              NEG_INF),
                    c_v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(sb == max_blocks - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def _window_rows(q_e, q_lat, nkv: int):
    """[B, W, nh, *] window queries → kernel rows [B, W·nh, *] in (w, n)
    order, with the rotary part block-diagonal."""
    B, W, nh, _ = q_e.shape
    return (_block_diag_q_e(q_e, nkv).reshape(B, W * nh, -1),
            q_lat.reshape(B, W * nh, -1))


def elite_verify_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                       block_tables, q_offsets, lengths, q_group: int,
                       scale: float, block_size: int,
                       interpret: bool = False):
    """See kernels/ref.py::elite_verify_paged_ref for exact semantics.

    q_e [B,W,nh,2r], q_lat [B,W,nh,d_c], pages as in ``elite_decode_paged``,
    q_offsets [B] int32 (global position of each lane's window row 0),
    lengths [B] int32 (live tokens *including* the window; 0 = dead lane)
    →  o [B,W,nh,d_c].  Length-0 lanes produce zeros.
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, W, nh, _ = q_e.shape
    mb = block_tables.shape[1]
    assert block_tables.shape == (B, mb)
    assert q_offsets.shape == (B,) and lengths.shape == (B,)
    out = _paged_call(
        functools.partial(_verify_kernel, block_size=block_size, scale=scale,
                          max_blocks=mb, n_heads=nh),
        "elite_verify_paged", (block_tables, q_offsets, lengths),
        *_window_rows(q_e, q_lat, nkv),
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages),
        c_v_pages.dtype, interpret)
    return out.reshape(B, W, nh, -1)


def _verify_kernel_q8(block_tables_ref,       # scalar-prefetch [B, mb] int32
                      q_offsets_ref,          # scalar-prefetch [B] int32
                      lengths_ref,            # scalar-prefetch [B] int32
                      q_e_ref, q_lat_ref, k_e_ref, c_k_ref, c_v_ref,
                      k_s_ref, ck_s_ref, cv_s_ref,
                      o_ref,
                      acc_ref, m_ref, l_ref,
                      *, block_size: int, scale: float, max_blocks: int,
                      n_heads: int):
    """``_verify_kernel`` over int8 pages with fused dequant — same W·nh
    query-row layout and offset-causal mask, same per-slot scale loads as
    ``_paged_kernel_q8``."""
    b = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    length = lengths_ref[b]
    q_offset = q_offsets_ref[b]
    start = sb * block_size

    @pl.when(start < length)
    def _step():
        s = _scores_q8(q_e_ref, q_lat_ref, k_e_ref, c_k_ref, k_s_ref,
                       ck_s_ref, scale)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qw = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_heads
        _accumulate(jnp.where((pos <= q_offset + qw) & (pos < length), s,
                              NEG_INF),
                    c_v_ref[0].astype(jnp.float32), acc_ref, m_ref, l_ref,
                    cv_s_ref[0])

    @pl.when(sb == max_blocks - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def elite_verify_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                          k_e_scale, c_k_scale, c_v_scale,
                          block_tables, q_offsets, lengths, q_group: int,
                          scale: float, block_size: int,
                          interpret: bool = False):
    """See kernels/ref.py::elite_verify_paged_q8_ref for exact semantics.

    ``elite_verify_paged`` over int8 pages + per-slot f32 scales; output is
    always f32.
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, W, nh, _ = q_e.shape
    mb = block_tables.shape[1]
    assert block_tables.shape == (B, mb)
    assert q_offsets.shape == (B,) and lengths.shape == (B,)
    out = _paged_call(
        functools.partial(_verify_kernel_q8, block_size=block_size,
                          scale=scale, max_blocks=mb, n_heads=nh),
        "elite_verify_paged_q8", (block_tables, q_offsets, lengths),
        *_window_rows(q_e.astype(jnp.float32), q_lat.astype(jnp.float32),
                      nkv),
        _flat_pages(block_size, k_e_pages, c_k_pages, c_v_pages,
                    k_e_scale, c_k_scale, c_v_scale),
        jnp.float32, interpret)
    return out.reshape(B, W, nh, -1)


def elite_verify_paged_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                           block_tables, q_offsets, lengths, q_group: int,
                           scale: float, block_size: int):
    """Gather-based XLA fallback for the verify kernel (CPU / rejected
    shapes) — one gather of the compressed stream, then the dense multi-query
    oracle; identical semantics to the Pallas block-table walk."""
    from repro.kernels.ref import elite_verify_paged_ref
    return elite_verify_paged_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                  block_tables, q_offsets, lengths, q_group,
                                  scale, block_size)


def elite_decode_paged_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                           block_tables, lengths, q_group: int, scale: float,
                           block_size: int):
    """Gather-based XLA fallback with identical semantics to the Pallas paged
    kernel (used on CPU and for shapes the TPU lowering rejects).  One gather
    materializes [B, mb·block_size] of the compressed stream — still only the
    2r·n_kv + d_ckv floats/token the paper pays for, never the full K/V."""
    from repro.kernels.ref import elite_decode_paged_ref
    return elite_decode_paged_ref(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                  block_tables, lengths, q_group, scale,
                                  block_size)


def elite_decode_paged_q8_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              k_e_scale, c_k_scale, c_v_scale,
                              block_tables, lengths, q_group: int,
                              scale: float, block_size: int):
    """XLA fallback for the int8 paged decode kernel: dequantize the pool
    (one multiply) then the gather-based f32 fallback — exact oracle match."""
    from repro.kernels.ref import elite_decode_paged_q8_ref
    return elite_decode_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, k_e_scale, c_k_scale,
                                     c_v_scale, block_tables, lengths,
                                     q_group, scale, block_size)


def elite_verify_paged_q8_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              k_e_scale, c_k_scale, c_v_scale,
                              block_tables, q_offsets, lengths, q_group: int,
                              scale: float, block_size: int):
    """XLA fallback for the int8 paged verify kernel."""
    from repro.kernels.ref import elite_verify_paged_q8_ref
    return elite_verify_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, k_e_scale, c_k_scale,
                                     c_v_scale, block_tables, q_offsets,
                                     lengths, q_group, scale, block_size)


def elite_decode_sparse_paged_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                  sel_tables, sel_counts, q_group: int,
                                  scale: float, block_size: int):
    """Gather-based XLA fallback for the sparse decode kernel: gather only
    the [B, W·block_size] selected slots, then the shared masked oracle."""
    from repro.kernels.ref import elite_decode_sparse_paged_ref
    return elite_decode_sparse_paged_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                         c_v_pages, sel_tables, sel_counts,
                                         q_group, scale, block_size)


def elite_decode_sparse_paged_q8_xla(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, k_e_scale, c_k_scale,
                                     c_v_scale, sel_tables, sel_counts,
                                     q_group: int, scale: float,
                                     block_size: int):
    """XLA fallback for the int8 sparse decode kernel."""
    from repro.kernels.ref import elite_decode_sparse_paged_q8_ref
    return elite_decode_sparse_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                            c_v_pages, k_e_scale, c_k_scale,
                                            c_v_scale, sel_tables, sel_counts,
                                            q_group, scale, block_size)
