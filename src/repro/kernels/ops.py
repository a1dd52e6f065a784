"""jit'd public wrappers for the Pallas kernels.

``_interpret()`` picks the path from the platform.  On a TPU the kernels
compile through Mosaic; elsewhere the paged wrappers take their XLA gather
fallback and the others run with ``interpret=True`` (the kernel body runs in
Python per grid step).  Lowered and run on a TPU v5e (``chip_smoke.py``,
within its stated tolerance of ``kernels/ref.py``): ``elite_decode_paged``,
``elite_decode_paged_q8``, ``elite_decode_sparse_paged``,
``elite_decode_sparse_paged_q8``, ``elite_verify_paged`` and
``elite_verify_paged_q8``.  ``elite_decode``, ``flash_prefill`` and
``rope_elite`` compile for a described v5e (``tests/test_tpu_compile.py``)
but have not run on one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import elite_decode as _ed
from repro.kernels import flash_prefill as _fp
from repro.kernels import rope_elite as _re

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("q_group", "scale", "block_s"))
def _elite_decode_jit(q_e, q_lat, k_e, c_k, c_v, lengths, q_group: int,
                      scale: float, block_s: int = 512):
    return _ed.elite_decode(q_e, q_lat, k_e, c_k, c_v, lengths, q_group,
                            scale, block_s=block_s, interpret=_interpret())


def elite_decode(q_e, q_lat, k_e, c_k, c_v, lengths, q_group: int,
                 scale: float, block_s: int = 512):
    return _elite_decode_jit(q_e, q_lat, k_e, c_k, c_v, lengths, q_group,
                             scale, block_s)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_decode_paged_jit(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                            block_tables, lengths, q_group: int, scale: float,
                            block_size: int, force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_decode_paged_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, block_tables,
            lengths, q_group, scale, block_size)
    return _ed.elite_decode_paged(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, block_tables, lengths,
        q_group, scale, block_size, interpret=False)


def elite_decode_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                       block_tables, lengths, q_group: int, scale: float,
                       block_size: int, force_xla: bool = False):
    """Paged decode attention over the block pool.

    TPU: Pallas kernel walking the prefetched block table (zero gather).
    CPU / ``force_xla``: gather-based XLA fallback with identical semantics —
    interpret-mode Pallas loops the grid in Python, far too slow to serve with.
    """
    return _elite_decode_paged_jit(q_e, q_lat, k_e_pages, c_k_pages,
                                   c_v_pages, block_tables, lengths,
                                   q_group, scale, block_size, force_xla)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_decode_paged_q8_jit(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                               k_e_scale, c_k_scale, c_v_scale,
                               block_tables, lengths, q_group: int,
                               scale: float, block_size: int,
                               force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_decode_paged_q8_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
            c_k_scale, c_v_scale, block_tables, lengths, q_group, scale,
            block_size)
    return _ed.elite_decode_paged_q8(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale, c_k_scale,
        c_v_scale, block_tables, lengths, q_group, scale, block_size,
        interpret=False)


def elite_decode_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                          k_e_scale, c_k_scale, c_v_scale,
                          block_tables, lengths, q_group: int, scale: float,
                          block_size: int, force_xla: bool = False):
    """``elite_decode_paged`` over an int8 pool: the same block-table walk
    also loads each slot's f32 quantization scale and dequantizes in-register
    (core/quant.py).  Output is f32 regardless of page dtype.

    TPU: fused Pallas kernel.  CPU / ``force_xla``: dequantize-then-gather
    XLA fallback with identical semantics.
    """
    return _elite_decode_paged_q8_jit(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
        c_k_scale, c_v_scale, block_tables, lengths, q_group, scale,
        block_size, force_xla)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "num_sel", "recent"))
def select_topk_blocks(q_lat, blk_mean, blk_max, block_tables, lengths,
                       block_size: int, num_sel: int, recent: int):
    """Latent-space block selection for sparse decode — see
    kernels/ref.py::select_topk_blocks.  Runs OUTSIDE any tensor-parallel
    shard_map on the full-head ``q_lat`` so the selection is shard-invariant;
    its [B, W] outputs feed the sparse kernels as scalar prefetch."""
    from repro.kernels.ref import select_topk_blocks as _sel
    return _sel(q_lat, blk_mean, blk_max, block_tables, lengths, block_size,
                num_sel, recent)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_decode_sparse_paged_jit(q_e, q_lat, k_e_pages, c_k_pages,
                                   c_v_pages, sel_tables, sel_counts,
                                   q_group: int, scale: float,
                                   block_size: int, force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_decode_sparse_paged_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, sel_tables,
            sel_counts, q_group, scale, block_size)
    return _ed.elite_decode_sparse_paged(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, sel_tables, sel_counts,
        q_group, scale, block_size, interpret=False)


def elite_decode_sparse_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                              sel_tables, sel_counts, q_group: int,
                              scale: float, block_size: int,
                              force_xla: bool = False):
    """Sparse paged decode attention: walk only the ``[B, W]`` selected
    blocks (``select_topk_blocks``) instead of the full chain — O(k·block)
    per token.  With the full chain selected the output is bit-identical to
    ``elite_decode_paged`` (the sparse recall wall, docs/serving.md).

    TPU: Pallas kernel walking the prefetched selection table.
    CPU / ``force_xla``: gather-based XLA fallback with identical semantics.
    """
    return _elite_decode_sparse_paged_jit(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, sel_tables,
        sel_counts, q_group, scale, block_size, force_xla)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_decode_sparse_paged_q8_jit(q_e, q_lat, k_e_pages, c_k_pages,
                                      c_v_pages, k_e_scale, c_k_scale,
                                      c_v_scale, sel_tables, sel_counts,
                                      q_group: int, scale: float,
                                      block_size: int, force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_decode_sparse_paged_q8_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
            c_k_scale, c_v_scale, sel_tables, sel_counts, q_group, scale,
            block_size)
    return _ed.elite_decode_sparse_paged_q8(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale, c_k_scale,
        c_v_scale, sel_tables, sel_counts, q_group, scale, block_size,
        interpret=False)


def elite_decode_sparse_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                 k_e_scale, c_k_scale, c_v_scale,
                                 sel_tables, sel_counts, q_group: int,
                                 scale: float, block_size: int,
                                 force_xla: bool = False):
    """``elite_decode_sparse_paged`` over an int8 pool with fused in-register
    dequant; output is f32 regardless of page dtype."""
    return _elite_decode_sparse_paged_q8_jit(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
        c_k_scale, c_v_scale, sel_tables, sel_counts, q_group, scale,
        block_size, force_xla)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_verify_paged_jit(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                            block_tables, q_offsets, lengths, q_group: int,
                            scale: float, block_size: int,
                            force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_verify_paged_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, block_tables,
            q_offsets, lengths, q_group, scale, block_size)
    return _ed.elite_verify_paged(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, block_tables, q_offsets,
        lengths, q_group, scale, block_size, interpret=False)


def elite_verify_paged(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                       block_tables, q_offsets, lengths, q_group: int,
                       scale: float, block_size: int, force_xla: bool = False):
    """Speculative-verify attention over the block pool: all ``k+1`` window
    positions of every lane scored in one pass of the compressed cache.

    ``q_offsets``/``lengths`` are per-lane scalar-prefetch vectors (the same
    machinery as ``flash_prefill``'s resumed chunks): lane ``b``'s window row
    ``w`` sits at global position ``q_offsets[b] + w`` and sees cache
    positions ``<= q_offsets[b] + w`` (offset-causal) below ``lengths[b]``.

    TPU: Pallas kernel walking the prefetched block table (zero gather).
    CPU / ``force_xla``: gather-based XLA fallback with identical semantics.
    """
    return _elite_verify_paged_jit(q_e, q_lat, k_e_pages, c_k_pages,
                                   c_v_pages, block_tables, q_offsets,
                                   lengths, q_group, scale, block_size,
                                   force_xla)


@functools.partial(jax.jit,
                   static_argnames=("q_group", "scale", "block_size", "force_xla"))
def _elite_verify_paged_q8_jit(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                               k_e_scale, c_k_scale, c_v_scale,
                               block_tables, q_offsets, lengths, q_group: int,
                               scale: float, block_size: int,
                               force_xla: bool = False):
    if force_xla or _interpret():
        return _ed.elite_verify_paged_q8_xla(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
            c_k_scale, c_v_scale, block_tables, q_offsets, lengths, q_group,
            scale, block_size)
    return _ed.elite_verify_paged_q8(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale, c_k_scale,
        c_v_scale, block_tables, q_offsets, lengths, q_group, scale,
        block_size, interpret=False)


def elite_verify_paged_q8(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                          k_e_scale, c_k_scale, c_v_scale,
                          block_tables, q_offsets, lengths, q_group: int,
                          scale: float, block_size: int,
                          force_xla: bool = False):
    """``elite_verify_paged`` over an int8 pool with fused in-register
    dequant — the speculative verify analogue of ``elite_decode_paged_q8``;
    output is f32 regardless of page dtype."""
    return _elite_verify_paged_q8_jit(
        q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, k_e_scale,
        c_k_scale, c_v_scale, block_tables, q_offsets, lengths, q_group,
        scale, block_size, force_xla)


@functools.partial(jax.jit, static_argnames=("q_group", "scale", "block_q",
                                             "block_k"))
def _flash_prefill_jit(q, k, v, q_offsets, kv_lens, q_group: int, scale: float,
                       block_q: int, block_k: int):
    return _fp.flash_prefill(q, k, v, q_group, scale, block_q=block_q,
                             block_k=block_k, q_offset=q_offsets,
                             kv_lens=kv_lens, interpret=_interpret())


def flash_prefill(q, k, v, q_group: int, scale: float,
                  block_q: int = 256, block_k: int = 512, q_offset=0,
                  kv_lens=None):
    """``q_offset`` resumes prefill chunks against a longer key context
    (chunked prefill, see docs/serving.md): a python int applies one offset
    to every lane, a per-lane [B] vector packs chunks resumed from different
    sequences into one call.  ``kv_lens`` [B] masks per-lane key tails.
    Offsets/lengths are traced (scalar-prefetch), so one compile covers every
    batch composition."""
    B, Sk = q.shape[0], k.shape[1]
    if isinstance(q_offset, int):           # static path: validate the contract
        assert q_offset >= 0 and Sk >= q.shape[1] + q_offset, \
            (q.shape[1], Sk, q_offset)
    q_offsets = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    kv_lens = (jnp.full((B,), Sk, jnp.int32) if kv_lens is None
               else jnp.asarray(kv_lens, jnp.int32))
    bq, bk = min(block_q, q.shape[1]), min(block_k, Sk)
    return _flash_prefill_jit(q, k, v, q_offsets, kv_lens, q_group, scale,
                              bq, bk)


@functools.partial(jax.jit, static_argnames=("block_s",))
def _rope_elite_jit(x, positions, freqs, block_s: int = 1024):
    return _re.rope_elite(x, positions, freqs, block_s=block_s,
                          interpret=_interpret())


def rope_elite(x, positions, freqs, block_s: int = 1024):
    return _rope_elite_jit(x, positions, freqs, block_s)


# ---------------------------------------------------------------------------
# tensor-parallel shard_map wrappers (multi-device serving)
# ---------------------------------------------------------------------------
#
# The paged kernels treat heads as *batch* dims of their grid — no reduction
# ever crosses a head.  That makes head-sharding exact: each shard runs the
# ordinary dispatch on its head slice (and its kv heads' columns of the
# k_e rows; the head-shared latent pages, per-token scales, block table and
# lengths are replicated), producing per-head outputs bitwise identical to
# the single-device call.  A tiled ``all_gather`` over the head axis then
# replicates the full pre-epilogue output ``o [..., nh, d_c]`` so the
# absorbed ``bv``/``wo`` epilogue — the only cross-head reduction in the
# decode path — runs replicated with single-device summation order.  (A
# ``psum_scatter`` epilogue fused into a head-sharded ``wo`` would halve the
# collective bytes but sums shard partials in a different float order;
# bit-identity to single-device is the serving wall, so the gather wins.
# docs/architecture.md#sharded-decode diagrams the data flow.)

from jax.sharding import PartitionSpec as _P


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off: the epilogue
    all_gather makes outputs replicated by construction, which the static
    checker cannot see through the inner jit call."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _rep(x) -> _P:
    """Replicated spec of ``x`` (``None``, the joint latent's absent
    ``c_v`` stream or scale, has no leaves to place)."""
    return _P() if x is None else _P(*([None] * x.ndim))


def elite_decode_paged_tp(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, scales,
                          block_tables, lengths, q_group: int, scale: float,
                          block_size: int, mesh, tp_axis: str = "model",
                          force_xla: bool = False):
    """Tensor-parallel paged decode: ``q_e``/``q_lat [B, nh, *]`` sharded on
    heads, ``k_e_pages [n_slots, nkv·2r]`` sharded on its row (each shard
    holds the contiguous columns of its kv heads), everything else
    replicated; returns the replicated full-head ``o [B, nh, d_c]``.

    ``scales`` is ``None`` for an f32 pool or the
    ``(k_e_scale, c_k_scale, c_v_scale)`` triple for int8 — quantization is
    exact under head sharding because scales are per-token and dequant is
    elementwise."""
    if mesh.shape[tp_axis] == 1:
        if scales is None:
            return elite_decode_paged(q_e, q_lat, k_e_pages, c_k_pages,
                                      c_v_pages, block_tables, lengths,
                                      q_group, scale, block_size, force_xla)
        return elite_decode_paged_q8(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, *scales, block_tables,
                                     lengths, q_group, scale, block_size,
                                     force_xla)

    heads = _P(None, tp_axis, None)
    args = [q_e, q_lat, k_e_pages, c_k_pages, c_v_pages]
    specs = [heads, heads, _P(None, tp_axis),
             _rep(c_k_pages), _rep(c_v_pages)]
    if scales is not None:
        args += list(scales)
        specs += [_rep(s) for s in scales]
    args += [block_tables, lengths]
    specs += [_rep(block_tables), _rep(lengths)]

    def body(*xs):
        if scales is None:
            bq_e, bq_lat, k_e, c_k, c_v, bt, ln = xs
            o = elite_decode_paged(bq_e, bq_lat, k_e, c_k, c_v, bt, ln,
                                   q_group, scale, block_size, force_xla)
        else:
            bq_e, bq_lat, k_e, c_k, c_v, ks, cks, cvs, bt, ln = xs
            o = elite_decode_paged_q8(bq_e, bq_lat, k_e, c_k, c_v, ks, cks,
                                      cvs, bt, ln, q_group, scale, block_size,
                                      force_xla)
        return jax.lax.all_gather(o, tp_axis, axis=1, tiled=True)

    return _shard_map(body, mesh, tuple(specs), _P(None, None, None))(*args)


def elite_decode_sparse_paged_tp(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages,
                                 scales, sel_tables, sel_counts, q_group: int,
                                 scale: float, block_size: int, mesh,
                                 tp_axis: str = "model",
                                 force_xla: bool = False):
    """Tensor-parallel sparse paged decode.  Identical head-sharding contract
    to :func:`elite_decode_paged_tp`; ``sel_tables``/``sel_counts`` replace
    the block table + lengths and are REPLICATED — the selection was computed
    once on the full-head query (``select_topk_blocks``), so every shard
    walks the same blocks and the gathered output is bitwise identical to the
    single-device sparse call."""
    if mesh.shape[tp_axis] == 1:
        if scales is None:
            return elite_decode_sparse_paged(
                q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, sel_tables,
                sel_counts, q_group, scale, block_size, force_xla)
        return elite_decode_sparse_paged_q8(
            q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, *scales, sel_tables,
            sel_counts, q_group, scale, block_size, force_xla)

    heads = _P(None, tp_axis, None)
    args = [q_e, q_lat, k_e_pages, c_k_pages, c_v_pages]
    specs = [heads, heads, _P(None, tp_axis),
             _rep(c_k_pages), _rep(c_v_pages)]
    if scales is not None:
        args += list(scales)
        specs += [_rep(s) for s in scales]
    args += [sel_tables, sel_counts]
    specs += [_rep(sel_tables), _rep(sel_counts)]

    def body(*xs):
        if scales is None:
            bq_e, bq_lat, k_e, c_k, c_v, st, ct = xs
            o = elite_decode_sparse_paged(bq_e, bq_lat, k_e, c_k, c_v, st, ct,
                                          q_group, scale, block_size,
                                          force_xla)
        else:
            bq_e, bq_lat, k_e, c_k, c_v, ks, cks, cvs, st, ct = xs
            o = elite_decode_sparse_paged_q8(bq_e, bq_lat, k_e, c_k, c_v, ks,
                                             cks, cvs, st, ct, q_group, scale,
                                             block_size, force_xla)
        return jax.lax.all_gather(o, tp_axis, axis=1, tiled=True)

    return _shard_map(body, mesh, tuple(specs), _P(None, None, None))(*args)


def elite_verify_paged_tp(q_e, q_lat, k_e_pages, c_k_pages, c_v_pages, scales,
                          block_tables, q_offsets, lengths, q_group: int,
                          scale: float, block_size: int, mesh,
                          tp_axis: str = "model", force_xla: bool = False):
    """Tensor-parallel speculative verify: like :func:`elite_decode_paged_tp`
    but queries carry a window dim — ``q_e``/``q_lat [B, W, nh, *]`` shard on
    head axis 2 and the gather reassembles ``o [B, W, nh, d_c]``."""
    if mesh.shape[tp_axis] == 1:
        if scales is None:
            return elite_verify_paged(q_e, q_lat, k_e_pages, c_k_pages,
                                      c_v_pages, block_tables, q_offsets,
                                      lengths, q_group, scale, block_size,
                                      force_xla)
        return elite_verify_paged_q8(q_e, q_lat, k_e_pages, c_k_pages,
                                     c_v_pages, *scales, block_tables,
                                     q_offsets, lengths, q_group, scale,
                                     block_size, force_xla)

    heads = _P(None, None, tp_axis, None)
    args = [q_e, q_lat, k_e_pages, c_k_pages, c_v_pages]
    specs = [heads, heads, _P(None, tp_axis),
             _rep(c_k_pages), _rep(c_v_pages)]
    if scales is not None:
        args += list(scales)
        specs += [_rep(s) for s in scales]
    args += [block_tables, q_offsets, lengths]
    specs += [_rep(block_tables), _rep(q_offsets), _rep(lengths)]

    def body(*xs):
        if scales is None:
            bq_e, bq_lat, k_e, c_k, c_v, bt, qo, ln = xs
            o = elite_verify_paged(bq_e, bq_lat, k_e, c_k, c_v, bt, qo, ln,
                                   q_group, scale, block_size, force_xla)
        else:
            bq_e, bq_lat, k_e, c_k, c_v, ks, cks, cvs, bt, qo, ln = xs
            o = elite_verify_paged_q8(bq_e, bq_lat, k_e, c_k, c_v, ks, cks,
                                      cvs, bt, qo, ln, q_group, scale,
                                      block_size, force_xla)
        return jax.lax.all_gather(o, tp_axis, axis=2, tiled=True)

    return _shard_map(body, mesh, tuple(specs), _P(None, None, None, None))(*args)
