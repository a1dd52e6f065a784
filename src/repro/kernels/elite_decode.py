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

Layout (what the TPU lowering accepts).  Every step holds ALL heads of a
lane, so no step slices one kv head out of the second-minor axis.
  - K_e rows are [nkv·2r]: kv head h in columns [h·2r, (h+1)·2r).  The
    rotary query arrives block-diagonal, [nh, nkv·2r], with head n's 2r
    dims in the lanes of its kv head n // G and zeros elsewhere
    (``_block_diag_q_e``), so one dot scores every head's elite part.
  - The latent rows are [d_c], head-shared.  Under J-LRD the pool holds one
    joint stream ``c``; the paged kernels fetch each of its pages once and
    use it for both the latent scores and the value product (``c_v_pages``
    ``None``).  S-LRD's separate ``c_k``/``c_v`` pools stay two streams.
The contiguous kernel's grid is (B, S blocks) with BlockSpec tiles.  The
paged kernels share one grouped live-page walk (``_walk``), grid (B,): lane
b walks only the ``cdiv(length, bs)`` pages it holds, ``P = 256 // bs``
pages per step (``pages_per_step``, clipped to the table), each page one
contiguous async copy from the pool into a double-buffered VMEM scratch,
driven by the scalar-prefetched block table.  A step scores
``[rows, P·bs]`` at once and runs one online-softmax update; the next
group, or the next live lane's first group, is in flight meanwhile.  Pad
table entries and lanes of length 0 are never fetched.  int8 scales arrive
as one 128-lane row per block and become the group's ``[1, P·bs]`` row
that scales score columns and softmax weights, never a page's rows.
Streams narrower than 128 lanes are zero-padded per call (``_pad_lanes``).
Per step the working set is the lane's queries [rows, nkv·2r + d_c], two
groups of pages [2, P·bs, nkv·2r + d_c (+ d_c)] and the accumulators
[rows, d_c]: under 1 MiB at published widths.  Lengths, tables and
selections arrive via scalar prefetch (ragged serving batches).

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


def _nt(a, b):
    """a [m, k] · b [n, k]ᵀ → [m, n] f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _scores(q_e_ref, q_lat_ref, k_e, c_k, scale: float):
    """Scaled scores [rows, n] of the lane's query rows against n cache
    rows of an f32/bf16 pool (k_e [n, nkv·2r], c_k [n, d_c])."""
    s = _nt(q_e_ref[0], k_e)
    s += _nt(q_lat_ref[0], c_k)
    return s * scale


def _scores_q8(q_e_ref, q_lat_ref, k_e, c_k, k_s, ck_s, scale: float):
    """``_scores`` over int8 rows: dequantization is fused as a per-slot
    scale on each score column (``q·(k·s) = (q·k)·s``, scales [1, n]) — the
    HBM read stays one byte per element, the math stays f32."""
    s = _nt(q_e_ref[0], k_e.astype(jnp.float32)) * k_s
    s += _nt(q_lat_ref[0], c_k.astype(jnp.float32)) * ck_s
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
        s = _scores(q_e_ref, q_lat_ref, k_e_ref[0], c_k_ref[0], scale)
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

_GROUP_TOKENS = 256         # tokens one step of the paged walk covers
_LANES = 128                # a page copy moves whole rows of 128 lanes


def pages_per_step(block_size: int, n_table: int) -> int:
    """Pages ``P`` one step of the paged walk fetches: about 256 tokens,
    ``max(1, 256 // block_size)``, clipped to the table's ``n_table``
    entries.  Derived from shapes only; the scheduler's page counters use
    the same rule."""
    return max(1, min(_GROUP_TOKENS // block_size, n_table))


def _pad_lanes(x):
    """Zero-pad the last dim to a multiple of 128: a DMA moves whole
    128-lane rows, so narrower streams (TinyLlama at 25%, TP shards) are
    widened here, one copy per call; the zero lanes add exact zeros to
    every dot."""
    pad = -x.shape[-1] % _LANES
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _scale_rows(scale, block_size: int):
    """Per-slot int8 scales [n_slots] → one 128-lane row per block, the
    block's scales tiled across it, so ``_scale_row`` needs only selects."""
    assert _LANES % block_size == 0, block_size
    return jnp.tile(scale.reshape(-1, block_size), (1, _LANES // block_size))


def _scale_row(buf, P: int, bs: int):
    """A group's fetched scale rows [P, 1, 128] → the page-major row
    [1, P·bs] that scales its score columns: page i's scales sit in lanes
    [i·bs, (i+1)·bs)."""
    per = _LANES // bs
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // bs
    chunks = []
    for h in range(pl.cdiv(P, per)):
        row = jnp.zeros((1, _LANES), jnp.float32)
        for i in range(h * per, min(P, (h + 1) * per)):
            row = jnp.where(lane == i - h * per, buf[i], row)
        chunks.append(row)
    row = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return row[:, :P * bs]


def _group_rows(buf, P: int, bs: int):
    """A group's fetched pages [P, bs, W] → their rows [P·bs, W] (int8
    pages widened to f32 first; the f32 tiling accepts the merge)."""
    x = buf[...]
    if x.dtype == jnp.int8:
        x = x.astype(jnp.float32)
    return x.reshape(P * bs, x.shape[-1])


def _walk(table_ref, n_pages_ref, after_ref, *refs, body, n_extra: int,
          n_page_streams: int, roles, P: int, bs: int):
    """The grouped live-page walk every paged kernel shares; grid (B,).

    Lane b walks ``cdiv(n_pages[b], P)`` groups of ``P`` table entries.
    Each group's pages are fetched from the pool (``memory_space=ANY``) by
    one async copy per page and stream, straight from the scalar-prefetched
    ``table`` — a page is ``bs`` consecutive pool rows, one contiguous DMA —
    into a double-buffered VMEM scratch; entries at or past ``n_pages[b]``
    are never fetched, so a lane of length 0 fetches nothing and writes
    zeros.  While group g computes, group g+1 is in flight; the last group
    of lane b prefetches the first group of the next live lane
    (``after[b + 1]``), so lanes do not each start on a DMA bubble.

    ``body(b, g, views, *extra_refs, q_e_ref, q_lat_ref, acc, m, l)``
    runs one online-softmax update per group; ``views`` holds the group's
    rows ``[P·bs, W]`` per page stream and the page-major row ``[1, P·bs]``
    per scale stream, in the order ``roles`` names (a joint latent is one
    stream serving two roles, fetched once)."""
    ns = max(roles) + 1
    extra = refs[:n_extra]
    q_e_ref, q_lat_ref = refs[n_extra:n_extra + 2]
    pools = refs[n_extra + 2:n_extra + 2 + ns]
    o_ref = refs[n_extra + 2 + ns]
    bufs = refs[n_extra + 3 + ns:n_extra + 3 + 2 * ns]
    sem, slot_ref, acc_ref, m_ref, l_ref = refs[n_extra + 3 + 2 * ns:]
    b = pl.program_id(0)
    rows = [bs if k < n_page_streams else 1 for k in range(ns)]

    def fetch(lane, g, slot, wait: bool):
        n = n_pages_ref[lane]
        for i in range(P):
            @pl.when(g * P + i < n)
            def _():
                blk = table_ref[lane, g * P + i]
                for pool, buf, r in zip(pools, bufs, rows):
                    cp = pltpu.make_async_copy(pool.at[pl.ds(blk * r, r)],
                                               buf.at[slot, i], sem.at[slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _():
        # pages a last group leaves unfetched keep finite contents: their
        # masked weights are exact zeros, and 0 · (uninitialized NaN) is not
        slot_ref[0] = 0
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    _init(acc_ref, m_ref, l_ref)
    first = slot_ref[0]
    n_groups = pl.cdiv(n_pages_ref[b], P)

    @pl.when(after_ref[0] == b)                 # the first live lane
    def _():
        fetch(b, 0, first, wait=False)

    def group(g, carry):
        slot = (first + g) % 2
        nxt = after_ref[b + 1]

        @pl.when(g + 1 < n_groups)
        def _():
            fetch(b, g + 1, 1 - slot, wait=False)

        @pl.when((g + 1 == n_groups) & (nxt < pl.num_programs(0)))
        def _():
            fetch(nxt, 0, 1 - slot, wait=False)

        fetch(b, g, slot, wait=True)
        views = [_group_rows(buf.at[slot], P, bs) if k < n_page_streams
                 else _scale_row(buf.at[slot], P, bs)
                 for k, buf in enumerate(bufs)]
        body(b, g, [views[k] for k in roles], *extra, q_e_ref, q_lat_ref,
             acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    slot_ref[0] = (first + n_groups) % 2
    _finish(o_ref, acc_ref, l_ref)


def _streams(block_size: int, k_e_pages, c_k_pages, c_v_pages, scales=()):
    """The distinct pool arrays a walk fetches and the stream serving each
    role (k_e, c_k, c_v[, their scales]).  ``c_v_pages is None`` is the
    joint latent: one ``c`` stream, fetched once, serves both roles (and
    likewise a ``None`` c_v scale)."""
    pages = [k_e_pages, c_k_pages] + ([] if c_v_pages is None else [c_v_pages])
    roles = [0, 1, len(pages) - 1]
    arrays = [_pad_lanes(p) for p in pages]
    if scales:
        k_s, ck_s, cv_s = scales
        n = len(arrays)
        arrays += [_scale_rows(s, block_size)
                   for s in (k_s, ck_s, cv_s) if s is not None]
        roles += [n, n + 1, len(arrays) - 1]
    return arrays, len(pages), tuple(roles)


def _paged_call(body, name: str, table, n_pages, extra, q_e, q_lat, pools,
                block_size: int, out_dtype, interpret: bool):
    """The ``pallas_call`` every paged kernel shares: ``_walk`` over
    ``table [B, n_table]`` (the block table, or the sparse selection
    table), lane b walking its first ``n_pages[b]`` entries.  ``extra``
    are the body's scalar-prefetch operands; ``pools`` the ``_streams``
    triple.  q_e [B, rows, nkv·2r] (block-diagonal), q_lat [B, rows, d_c]
    → o [B, rows, d_c]."""
    arrays, n_page_streams, roles = pools
    B, rows, _ = q_e.shape
    d_c = q_lat.shape[-1]
    P = pages_per_step(block_size, table.shape[1])
    q_e, q_lat = _pad_lanes(q_e), _pad_lanes(q_lat)
    K, D = q_e.shape[-1], q_lat.shape[-1]
    # after[b]: the first lane >= b with pages to walk (B when none)
    idx = jnp.arange(B, dtype=jnp.int32)
    after = jax.lax.cummin(jnp.where(n_pages > 0, idx, B), reverse=True)
    after = jnp.concatenate([after, jnp.full((1,), B, jnp.int32)])

    def lane(w):
        return pl.BlockSpec((1, rows, w), lambda b, *_: (b, 0, 0))

    bufs = [pltpu.VMEM((2, P, block_size if k < n_page_streams else 1,
                        a.shape[1]), a.dtype) for k, a in enumerate(arrays)]
    out = pl.pallas_call(
        functools.partial(_walk, body=body, n_extra=len(extra),
                          n_page_streams=n_page_streams, roles=roles, P=P,
                          bs=block_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(extra),
            grid=(B,),
            in_specs=[lane(K), lane(D)]
                     + [pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=lane(D),
            scratch_shapes=bufs + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((rows, D), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(table, n_pages.astype(jnp.int32), after, *extra, q_e, q_lat, *arrays)
    return out[..., :d_c]


def _chain_pages(lengths, block_size: int, n_table: int):
    """Table entries a lane of each length walks: ``cdiv(length, bs)``."""
    return jnp.minimum(-(-lengths // block_size), n_table).astype(jnp.int32)


def _check_heads(q_e, k_e_pages, q_group: int) -> int:
    """kv heads of a paged call, from the query heads: k_e_pages rows are
    [nkv·2r] wide."""
    nh, r2 = q_e.shape[-2:]
    nkv = nh // q_group
    assert nh == nkv * q_group, (nh, q_group)
    assert k_e_pages.shape[1:] == (nkv * r2,), (k_e_pages.shape, nkv, r2)
    return nkv


def _positions(g, span: int, shape):
    """Cache positions of a group's score columns."""
    return g * span + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _paged_body(b, g, views, lengths_ref, q_e_ref, q_lat_ref,
                acc_ref, m_ref, l_ref, *, scale: float, span: int):
    """One group of the dense walk: scores against ``P`` pages at once,
    masked by ``pos < length``."""
    k_e, c_k, c_v = views
    s = _scores(q_e_ref, q_lat_ref, k_e, c_k, scale)
    pos = _positions(g, span, s.shape)
    _accumulate(jnp.where(pos < lengths_ref[b], s, NEG_INF), c_v,
                acc_ref, m_ref, l_ref)


def elite_decode_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                       block_tables, lengths, q_group: int, scale: float,
                       block_size: int, interpret: bool = False):
    """See kernels/ref.py::elite_decode_paged_ref for exact semantics.

    q_e [B,nh,2r], q_lat [B,nh,d_c], k_e_pages [n_slots,nkv·2r],
    c_k/c_v_pages [n_slots,d_c] (``c_v_pages=None``: the joint latent,
    c_k_pages serves both roles and is read once), block_tables [B,mb]
    int32, lengths [B] int32 →  o [B,nh,d_c].  Length-0 sequences (empty
    slots) produce zeros.
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, mb = block_tables.shape
    assert q_e.shape[0] == B and lengths.shape == (B,)
    span = pages_per_step(block_size, mb) * block_size
    return _paged_call(
        functools.partial(_paged_body, scale=scale, span=span),
        "elite_decode_paged", block_tables,
        _chain_pages(lengths, block_size, mb), (lengths,),
        _block_diag_q_e(q_e, nkv), q_lat,
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages), block_size,
        c_k_pages.dtype, interpret)


# ---------------------------------------------------------------------------
# paged decode over an int8 pool: fused dequantization
# ---------------------------------------------------------------------------

def _paged_body_q8(b, g, views, lengths_ref, q_e_ref, q_lat_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, span: int):
    """``_paged_body`` over int8 pages: each group's per-slot f32 scales
    scale its score columns and softmax weights (``_scores_q8``)."""
    k_e, c_k, c_v, k_s, ck_s, cv_s = views
    s = _scores_q8(q_e_ref, q_lat_ref, k_e, c_k, k_s, ck_s, scale)
    pos = _positions(g, span, s.shape)
    _accumulate(jnp.where(pos < lengths_ref[b], s, NEG_INF), c_v,
                acc_ref, m_ref, l_ref, cv_s)


def elite_decode_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                          k_e_scale, c_k_scale, c_v_scale,
                          block_tables, lengths, q_group: int, scale: float,
                          block_size: int, interpret: bool = False):
    """See kernels/ref.py::elite_decode_paged_q8_ref for exact semantics.

    Pages as in ``elite_decode_paged`` but int8; ``*_scale`` [n_slots] f32
    per-slot quantization scales (``c_v_pages``/``c_v_scale`` ``None``:
    the joint latent and its scale, read once).  Output is always f32 (the
    int8 pages must never leak their dtype into the attention output).
    """
    nkv = _check_heads(q_e, k_e_pages, q_group)
    B, mb = block_tables.shape
    assert q_e.shape[0] == B and lengths.shape == (B,)
    span = pages_per_step(block_size, mb) * block_size
    return _paged_call(
        functools.partial(_paged_body_q8, scale=scale, span=span),
        "elite_decode_paged_q8", block_tables,
        _chain_pages(lengths, block_size, mb), (lengths,),
        _block_diag_q_e(q_e.astype(jnp.float32), nkv),
        q_lat.astype(jnp.float32),
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages,
                 (k_e_scale, c_k_scale, c_v_scale)),
        block_size, jnp.float32, interpret)


# ---------------------------------------------------------------------------
# sparse paged decode: walk a top-k SELECTION of blocks, not the whole chain
# ---------------------------------------------------------------------------

def _selected_pages(sel_counts):
    """Selection entries a lane walks: through its last non-empty one
    (``select_topk_blocks`` puts empty entries last)."""
    W = sel_counts.shape[1]
    pos = jnp.arange(1, W + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(sel_counts > 0, pos, 0), axis=1)


def _count_mask(b, g, counts_ref, shape, P: int, bs: int):
    """A sparse group's validity mask: column ``i·bs + t`` is valid iff
    ``t < sel_counts[b, g·P + i]`` (entries past the table count 0).  For
    the full chain this equals the dense ``pos < length`` elementwise."""
    W = counts_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    count = jnp.zeros(shape, jnp.int32)
    for i in range(P):
        e = g * P + i
        c = jnp.where(e < W, counts_ref[b, jnp.minimum(e, W - 1)], 0)
        count = jnp.where(col // bs == i, c, count)
    return col % bs < count


def _sparse_body(b, g, views, counts_ref, q_e_ref, q_lat_ref,
                 acc_ref, m_ref, l_ref, *, scale: float, P: int, bs: int):
    """``_paged_body`` over the selection table: the length mask becomes a
    per-entry row count (``sel_counts``; 0 contributes nothing), so the
    walk does O(k·block) work per token.  Selected blocks arrive in
    ascending chain order; with the full chain selected the walk, the
    groups, the mask and the accumulation order equal the dense kernel's
    exactly (the bit-identity wall)."""
    k_e, c_k, c_v = views
    s = _scores(q_e_ref, q_lat_ref, k_e, c_k, scale)
    ok = _count_mask(b, g, counts_ref, s.shape, P, bs)
    _accumulate(jnp.where(ok, s, NEG_INF), c_v, acc_ref, m_ref, l_ref)


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
    P = pages_per_step(block_size, W)
    return _paged_call(
        functools.partial(_sparse_body, scale=scale, P=P, bs=block_size),
        "elite_decode_sparse_paged", sel_tables, _selected_pages(sel_counts),
        (sel_counts,), _block_diag_q_e(q_e, nkv), q_lat,
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages), block_size,
        c_k_pages.dtype, interpret)


def _sparse_body_q8(b, g, views, counts_ref, q_e_ref, q_lat_ref,
                    acc_ref, m_ref, l_ref, *, scale: float, P: int, bs: int):
    """``_sparse_body`` over int8 pages, scaled like ``_paged_body_q8``."""
    k_e, c_k, c_v, k_s, ck_s, cv_s = views
    s = _scores_q8(q_e_ref, q_lat_ref, k_e, c_k, k_s, ck_s, scale)
    ok = _count_mask(b, g, counts_ref, s.shape, P, bs)
    _accumulate(jnp.where(ok, s, NEG_INF), c_v, acc_ref, m_ref, l_ref, cv_s)


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
    P = pages_per_step(block_size, W)
    return _paged_call(
        functools.partial(_sparse_body_q8, scale=scale, P=P, bs=block_size),
        "elite_decode_sparse_paged_q8", sel_tables,
        _selected_pages(sel_counts), (sel_counts,),
        _block_diag_q_e(q_e.astype(jnp.float32), nkv),
        q_lat.astype(jnp.float32),
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages,
                 (k_e_scale, c_k_scale, c_v_scale)),
        block_size, jnp.float32, interpret)


# ---------------------------------------------------------------------------
# paged verify: k+1-token speculative windows, multi-query over the block table
# ---------------------------------------------------------------------------

def _window_mask(b, g, q_offsets_ref, lengths_ref, shape, span: int,
                 n_heads: int):
    """Row ``r`` holds window position ``w = r // nh`` at global position
    ``q_offsets[b] + w``: it sees cache positions up to its own (the
    offset-causal term of ``flash_prefill``'s diagonal) below the length."""
    pos = _positions(g, span, shape)
    qw = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // n_heads
    return (pos <= q_offsets_ref[b] + qw) & (pos < lengths_ref[b])


def _verify_body(b, g, views, q_offsets_ref, lengths_ref, q_e_ref,
                 q_lat_ref, acc_ref, m_ref, l_ref, *, scale: float,
                 span: int, n_heads: int):
    """``_paged_body`` generalized to ``window · nh`` query rows per lane,
    so speculative verify scores all ``k+1`` window tokens in one walk over
    the compressed cache."""
    k_e, c_k, c_v = views
    s = _scores(q_e_ref, q_lat_ref, k_e, c_k, scale)
    ok = _window_mask(b, g, q_offsets_ref, lengths_ref, s.shape, span,
                      n_heads)
    _accumulate(jnp.where(ok, s, NEG_INF), c_v, acc_ref, m_ref, l_ref)


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
    span = pages_per_step(block_size, mb) * block_size
    out = _paged_call(
        functools.partial(_verify_body, scale=scale, span=span, n_heads=nh),
        "elite_verify_paged", block_tables,
        _chain_pages(lengths, block_size, mb), (q_offsets, lengths),
        *_window_rows(q_e, q_lat, nkv),
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages), block_size,
        c_k_pages.dtype, interpret)
    return out.reshape(B, W, nh, -1)


def _verify_body_q8(b, g, views, q_offsets_ref, lengths_ref, q_e_ref,
                    q_lat_ref, acc_ref, m_ref, l_ref, *, scale: float,
                    span: int, n_heads: int):
    """``_verify_body`` over int8 pages, scaled like ``_paged_body_q8``."""
    k_e, c_k, c_v, k_s, ck_s, cv_s = views
    s = _scores_q8(q_e_ref, q_lat_ref, k_e, c_k, k_s, ck_s, scale)
    ok = _window_mask(b, g, q_offsets_ref, lengths_ref, s.shape, span,
                      n_heads)
    _accumulate(jnp.where(ok, s, NEG_INF), c_v, acc_ref, m_ref, l_ref, cv_s)


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
    span = pages_per_step(block_size, mb) * block_size
    out = _paged_call(
        functools.partial(_verify_body_q8, scale=scale, span=span,
                          n_heads=nh),
        "elite_verify_paged_q8", block_tables,
        _chain_pages(lengths, block_size, mb), (q_offsets, lengths),
        *_window_rows(q_e.astype(jnp.float32), q_lat.astype(jnp.float32),
                      nkv),
        _streams(block_size, k_e_pages, c_k_pages, c_v_pages,
                 (k_e_scale, c_k_scale, c_v_scale)),
        block_size, jnp.float32, interpret)
    return out.reshape(B, W, nh, -1)


def _joint(c_k, c_v):
    """``c_v`` for the XLA fallbacks: ``None`` names the joint latent."""
    return c_k if c_v is None else c_v


def elite_verify_paged_xla(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                           block_tables, q_offsets, lengths, q_group: int,
                           scale: float, block_size: int):
    """Gather-based XLA fallback for the verify kernel (CPU / rejected
    shapes) — one gather of the compressed stream, then the dense multi-query
    oracle; identical semantics to the Pallas block-table walk."""
    c_v_pages = _joint(c_k_pages, c_v_pages)
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
    c_v_pages = _joint(c_k_pages, c_v_pages)
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
    c_v_pages, c_v_scale = (_joint(c_k_pages, c_v_pages),
                            _joint(c_k_scale, c_v_scale))
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
    c_v_pages, c_v_scale = (_joint(c_k_pages, c_v_pages),
                            _joint(c_k_scale, c_v_scale))
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
    c_v_pages = _joint(c_k_pages, c_v_pages)
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
    c_v_pages, c_v_scale = (_joint(c_k_pages, c_v_pages),
                            _joint(c_k_scale, c_v_scale))
    from repro.kernels.ref import elite_decode_sparse_paged_q8_ref
    return elite_decode_sparse_paged_q8_ref(q_e, q_lat, k_e_pages, c_k_pages,
                                            c_v_pages, k_e_scale, c_k_scale,
                                            c_v_scale, sel_tables, sel_counts,
                                            q_group, scale, block_size)
