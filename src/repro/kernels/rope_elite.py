"""Per-head elite-RoPE Pallas kernel.

Rotates the packed elite dims of q/k with *per-head* frequency tables
(RoPElite permutes each head's elite chunks to the front, so the rotation is
a dense elementwise op on [S, 2r] — no gathers at runtime; the gather was
baked into the projection weights at conversion).

Grid (B, S/S_blk); one step rotates every head of an S block.  The wrapper
splits the interleaved (even, odd) pairs into two [B, S, H·r] planes, so the
kernel is pure VPU work on lane-dense tiles whose last two dims are whole
array dims (cos/sin computed in-kernel from positions — no HBM-resident
cos/sin tables).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(even_ref, odd_ref, pos_ref, freq_ref, o_even_ref, o_odd_ref):
    even = even_ref[0].astype(jnp.float32)            # [Sb, H·r]
    odd = odd_ref[0].astype(jnp.float32)
    ang = pos_ref[...] * freq_ref[...]                # [Sb, 1] · [1, H·r]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    o_even_ref[0] = (even * cos - odd * sin).astype(o_even_ref.dtype)
    o_odd_ref[0] = (even * sin + odd * cos).astype(o_odd_ref.dtype)


def rope_elite(x, positions, freqs, block_s: int = 1024, interpret: bool = False):
    """x [B,S,H,2r], positions [S] int32, freqs [H,r] → rotated x."""
    B, S, H, r2 = x.shape
    r = r2 // 2
    assert freqs.shape == (H, r)
    block_s = min(block_s, S)
    assert S % block_s == 0
    pairs = x.reshape(B, S, H * r, 2)
    pos2d = positions.reshape(S, 1).astype(jnp.float32)
    plane = pl.BlockSpec((1, block_s, H * r), lambda b, s: (b, s, 0))

    o_even, o_odd = pl.pallas_call(
        _kernel,
        grid=(B, S // block_s),
        in_specs=[
            plane, plane,
            pl.BlockSpec((block_s, 1), lambda b, s: (s, 0)),
            pl.BlockSpec((1, H * r), lambda b, s: (0, 0)),
        ],
        out_specs=[plane, plane],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * r), x.dtype)] * 2,
        interpret=interpret,
        name="rope_elite",
    )(pairs[..., 0], pairs[..., 1], pos2d, freqs.reshape(1, H * r))
    return jnp.stack([o_even, o_odd], axis=-1).reshape(x.shape)
