"""Operations and bytes, counted from shapes — the yardstick for roofline
shares and MFU.

Counts are of the algorithm's work, not of what today's kernels do: the
decode attention reads each cached token's rotary key ``k_e`` and latent
``c`` once and scores every query head against them, whatever block walk
or padding an implementation adds.  A roofline share therefore reads the
same work whoever implements it.

Model FLOPs per token (for MFU): two per weight a token meets in the layer
stack, plus attention in the form the phase computes — absorbed over the
latent for decode (rotary 2r dims + ``d_ckv`` for scores, ``d_ckv`` for the
output, per head and cached token), materialized K/V for prefill
(``4·d_head`` per head and key).  The LM head counts only where a token is
served from its logits: once per decode token, once per finished prompt.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from .weights import Dims


def decode_attn_call(m: Dims, lengths: Iterable[int],
                     elem_bytes: int = 4) -> Tuple[float, float]:
    """(flops, bytes) of one decode-attention call (one layer) over lanes of
    context ``lengths`` (each including the token being decoded)."""
    r2, dc, nh, nkv = 2 * m.elite_r, m.d_ckv, m.n_heads, m.n_kv
    flops = bytes_ = 0.0
    for n in lengths:
        if n <= 0:
            continue
        flops += 2.0 * nh * n * (r2 + dc) + 2.0 * nh * n * dc
        bytes_ += n * (nkv * r2 + dc) * elem_bytes          # cache, read once
        bytes_ += nh * (r2 + dc) * 4 + nh * dc * 4           # queries, output
    return flops, bytes_


def _dense_layer_flops(m: Dims) -> float:
    """Per token per layer: the projections every phase shares."""
    d, nh, dh, nkv, dc = m.d_model, m.n_heads, m.d_head, m.n_kv, m.d_ckv
    r2 = 2 * m.elite_r
    return 2.0 * (d * nh * dh + d * nkv * r2 + d * dc + nh * dh * d
                  + 3 * d * m.d_ff)


def decode_token_flops(m: Dims, n: int) -> float:
    """Model FLOPs of one decode token at context ``n`` (LM head included)."""
    nh, dh, dc = m.n_heads, m.d_head, m.d_ckv
    r2 = 2 * m.elite_r
    absorb = 2.0 * nh * (dh - r2) * dc + 2.0 * nh * dc * dh
    attn = 2.0 * nh * n * (r2 + dc) + 2.0 * nh * n * dc
    return m.layers * (_dense_layer_flops(m) + absorb + attn) + head_flops(m)


def prefill_token_flops(m: Dims, pos: int) -> float:
    """Model FLOPs of one prompt token at position ``pos`` (no LM head)."""
    nh, dh, dc, nkv = m.n_heads, m.d_head, m.d_ckv, m.n_kv
    r2 = 2 * m.elite_r
    up = 2.0 * dc * nkv * (dh - r2) + 2.0 * dc * nkv * dh
    attn = 4.0 * nh * dh * (pos + 1)
    return m.layers * (_dense_layer_flops(m) + up + attn)


def prefill_flops(m: Dims, start: int, n: int) -> float:
    """Σ ``prefill_token_flops`` over positions ``start .. start+n-1``."""
    nh, dh = m.n_heads, m.d_head
    per = prefill_token_flops(m, 0) - m.layers * 4.0 * nh * dh
    pos_sum = n * start + n * (n + 1) / 2                   # Σ (pos + 1)
    return n * per + m.layers * 4.0 * nh * dh * pos_sum


def head_flops(m: Dims) -> float:
    return 2.0 * m.d_model * m.vocab


def roofline_seconds(flops: float, bytes_: float, peaks: Dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["bytes_per_s"])
