"""Causal flash-attention Pallas kernel (prefill / training forward).

Standard online-softmax tiling (FlashAttention adapted to TPU VMEM/MXU):
grid (B, n_heads, Sq/block_q, Sk/block_k), sequential over the kv axis with
fp32 accumulators in VMEM scratch.  Causal block-skipping via ``pl.when`` —
blocks strictly above the diagonal are never touched, halving HBM traffic.

GQA is handled by mapping each q-head to its kv head in the BlockSpec index
map (no materialized K/V repeat — the repeat would multiply HBM reads by the
group size).  The kernel runs on head-major views ([B, heads, S, dh]) so each
block is a [rows, dh] tile of one head: the TPU lowering refuses a block of 1
on the head axis when that axis is second-minor ([B, S, heads, dh]).

Used at prefill for EliteKV models *after* the latent up-projection
materializes K = [K_e | c·bk] and V = c·bv for the current chunk; training
uses the same kernel via the materialized path.

Resumed chunks (chunked prefill, see docs/serving.md): a chunk of queries at
global positions ``q_offsets[b] .. q_offsets[b]+Sq`` attends to keys at
positions ``0 .. Sk`` — the mask becomes ``kpos <= qpos + q_offsets[b]`` and
the causal block skip shifts by the same offset.  Offsets and key lengths are
**per-lane** scalar-prefetch vectors, so one call (and one compile) serves a
batch of chunks resumed from *different* sequences at different depths —
the batched-prefill contract of the serving scheduler.  ``kv_lens[b]`` masks
each lane's padded key tail (keys at ``kpos >= kv_lens[b]`` are invisible).
NOTE: the paged serving loop currently resumes chunks through the XLA gather
path (``elite_attention._attend_resumed``); wiring this kernel to the paged
prefix via a contiguous gather scratch is the TPU follow-up tracked in
ROADMAP.md.  With an int8 pool (``--pool-dtype int8``) that prefix gather
dequantizes each slot by its stored scale (``core/quant.py``) before the
bk/bv up-projection, so this kernel always sees f32/bf16 inputs — the
quantized representation never crosses the materialized-K/V boundary.

The same per-lane offset-causal contract powers speculative decode's verify
windows: a ``k+1``-token window is a resumed chunk whose queries sit at
``q_offsets[b] + w`` — ``kernels/elite_decode.py::elite_verify_paged``
applies exactly this mask in the *absorbed* latent space, walking the block
table directly instead of gathering (see docs/serving.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_offsets_ref, kv_lens_ref,     # scalar-prefetch [B] int32 each
            q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, block_q: int, block_k: int, scale: float, n_kb: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    q_offset = q_offsets_ref[b]
    kv_len = kv_lens_ref[b]

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal skip: kv block strictly above the (per-lane offset) diagonal,
    # or entirely past this lane's live keys
    visible = (jk * block_k <= iq * block_q + block_q - 1 + q_offset) \
        & (jk * block_k < kv_len)

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0]                                      # [bq, dh]
        k = k_ref[0, 0]                                      # [bk, dh]
        v = v_ref[0, 0]                                      # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kpos <= qpos + q_offset) & (kpos < kv_len), s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == n_kb - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill(q, k, v, q_group: int, scale: float,
                  block_q: int = 256, block_k: int = 512,
                  q_offset=0, kv_lens=None, interpret: bool = False):
    """Causal attention.  q [B,Sq,nh,dh], k/v [B,Sk,nkv,dh] → [B,Sq,nh,dh].

    ``q_offset`` shifts the causal diagonal: key ``j`` is visible to query
    ``i`` of lane ``b`` iff ``j <= i + q_offset[b]`` and ``j < kv_lens[b]``.
    It is a python int (every lane shares the offset — ordinary causal
    attention at 0) or a per-lane [B] int32 vector: a *batch* of prefill
    chunks resumed from different sequences each passes its own start
    position.  ``kv_lens`` [B] (default Sk) masks per-lane padded key tails.
    Both ride scalar prefetch — one compile covers every offset/length mix.
    """
    B, Sq, nh, dh = q.shape
    Sk = k.shape[1]
    nkv = k.shape[2]
    assert nh == nkv * q_group
    if isinstance(q_offset, int):
        assert q_offset >= 0 and Sk >= Sq + q_offset, (Sq, Sk, q_offset)
    q_offsets = jnp.broadcast_to(
        jnp.asarray(q_offset, jnp.int32), (B,))
    kv_lens = (jnp.full((B,), Sk, jnp.int32) if kv_lens is None
               else jnp.asarray(kv_lens, jnp.int32))
    assert q_offsets.shape == (B,) and kv_lens.shape == (B,)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_qb, n_kb = Sq // block_q, Sk // block_k

    heads_major = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, n_kb=n_kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nh, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, dh),
                             lambda b, h, i, j, off, kl: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, dh),
                             lambda b, h, i, j, off, kl, g=q_group: (b, h // g, j, 0)),
                pl.BlockSpec((1, 1, block_k, dh),
                             lambda b, h, i, j, off, kl, g=q_group: (b, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, dh),
                                   lambda b, h, i, j, off, kl: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, dh), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, Sq, dh), v.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(q_offsets, kv_lens, heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)
