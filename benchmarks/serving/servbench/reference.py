"""The plain reference: a dense EliteKV decoder in straightforward
``jax.numpy``, imported from nothing of the serving program.

Per layer, with ``x = rmsnorm(h)``:

    q   = x·Wq                     [T, nh, dh]; its first 2r dims rotate
    k_e = rope(x·Wk_e)             [T, nkv, 2r]   (interleaved pairs, each kv
                                                  head at its own r elite
                                                  frequencies; query head n
                                                  uses kv head n // G)
    c   = x·A_kv                   [T, d_ckv]     (the joint latent)
    k   = [k_e, c·B_k]   v = c·B_v               (K and V materialized)
    h  += softmax_causal(q·kᵀ / √dh)·v·Wo
    h  += (silu(x'·Wg) ⊙ x'·Wu)·Wd, x' = rmsnorm(h)

then ``rmsnorm`` and the head (the embedding transposed when tied); logits
cover the true vocabulary.  This is the model the serving program computes
through its paged, absorbed path; the reference has no cache, no pool, no
batching and no kernel.

Precision: float32 at ``Precision.HIGHEST``.  The control
(``control=True``) is the same forward one precision step below what the
configuration states: the serving program keeps float32 weights and
activations but runs its matmuls at the TPU's default precision, one
bfloat16 pass, so every matmul operand is rounded to float8 (e4m3, scaled
per tensor to its absolute maximum) and the products are summed in float32.
Attention runs in blocks of query rows and the head in blocks of positions,
so a 4k-token sequence fits beside the weights.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import Dims

Q_BLOCK = 256
HEAD_BLOCK = 256


def _fp8(x):
    """Round to float8 e4m3 on a per-tensor scale (absmax → 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(control: bool):
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    if not control:
        return hi
    return lambda spec, a, b: hi(spec, _fp8(a), _fp8(b))


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, freqs):
    """Rotate interleaved pairs of x [T, H, 2r] at positions pos [T] by the
    per-head frequencies freqs [H, r]."""
    ang = pos[:, None, None].astype(jnp.float32) * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos], axis=-1)
    return out.reshape(x.shape)


def _layer(m: Dims, control: bool, pos, h, wl):
    p, freqs = wl
    ein = _einsum(control)
    T = h.shape[0]
    r2, G = 2 * m.elite_r, m.q_group
    x = _rmsnorm(h, p["attn_norm"]["scale"], m.norm_eps)
    a = p["attn"]
    q = ein("td,dhe->the", x, a["wq"])
    q_e = _rope(q[..., :r2], pos, jnp.repeat(freqs, G, axis=0))
    q = jnp.concatenate([q_e, q[..., r2:]], axis=-1)
    k_e = _rope(ein("td,dhe->the", x, a["wk_e"]), pos, freqs)
    c = ein("td,dc->tc", x, a["a_kv"])
    k = jnp.concatenate([k_e, ein("tc,che->the", c, a["bk"])], axis=-1)
    v = ein("tc,che->the", c, a["bv"])

    qb = min(Q_BLOCK, T)
    qg = q.reshape(T // qb, qb, m.n_kv, G, m.d_head)
    starts = jnp.arange(T // qb) * qb

    def block(args):
        q_blk, s0 = args
        s = ein("qkgd,tkd->kgqt", q_blk, k) * (m.d_head ** -0.5)
        qpos = s0 + jnp.arange(qb)
        s = jnp.where(jnp.arange(T)[None, :] <= qpos[:, None], s, -1e30)
        return ein("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qg, starts)).reshape(T, m.n_heads, m.d_head)
    h = h + ein("the,hed->td", o, a["wo"])
    x = _rmsnorm(h, p["ffn_norm"]["scale"], m.norm_eps)
    f = p["ffn"]
    g = jax.nn.silu(ein("td,df->tf", x, f["w_gate"])) * ein(
        "td,df->tf", x, f["w_up"])
    return h + ein("tf,fd->td", g, f["w_down"]), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hidden(m: Dims, control: bool, params, buffers, tokens):
    """Final-normed hidden states [T, d] of one sequence."""
    pos = jnp.arange(tokens.shape[0])
    h = params["embed"]["table"][tokens]
    body = functools.partial(_layer, m, control, pos)
    h, _ = jax.lax.scan(body, h, (params["blocks"]["p0"],
                                  buffers["blocks"]["p0"]["elite_freqs"]))
    return _rmsnorm(h, params["final_norm"]["scale"], m.norm_eps)


def _head(m: Dims, params):
    w = (params["embed"]["table"].T if m.tied else params["lm_head"]["w"])
    return w[:, :m.vocab]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stats(m: Dims, control: bool, params, hidden, rows, targets):
    """Per row: (best logit, logit of ``targets``, argmax) of the logits at
    hidden[rows], in blocks of HEAD_BLOCK rows."""
    w = _head(m, params)
    ein = _einsum(control)

    def block(args):
        r, t = args
        lg = ein("td,dv->tv", hidden[r], w)
        return (lg.max(-1), jnp.take_along_axis(lg, t[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    hb = min(HEAD_BLOCK, rows.shape[0])
    n = rows.shape[0] // hb
    out = jax.lax.map(block, (rows.reshape(n, hb), targets.reshape(n, hb)))
    return tuple(o.reshape(-1) for o in out)


def _pad(x: Sequence[int], n: int) -> np.ndarray:
    out = np.zeros((n,), np.int32)
    out[:len(x)] = x
    return out


def _rows(prompt: np.ndarray, served: Sequence[int]):
    """Sequence (prompt + served tokens but the last) and the rows whose
    logits choose each served token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, rows


def served_gaps(m: Dims, params, buffers, requests: List[Tuple],
                max_len: int) -> List[np.ndarray]:
    """For each ``(prompt, served tokens)``: how far each served token's
    reference logit lies below the reference's best logit at its row."""
    out = []
    for prompt, served in requests:
        seq, rows = _rows(prompt, served)
        h = _hidden(m, False, params, buffers, jnp.asarray(
            _pad(seq, max_len)))
        best, got, _ = _stats(m, False, params, h,
                              jnp.asarray(_pad(rows, max_len)),
                              jnp.asarray(_pad(served, max_len)))
        n = len(served)
        out.append(np.asarray(best)[:n] - np.asarray(got)[:n])
    return out


def control_gaps(m: Dims, params, buffers, requests: List[Tuple],
                 max_len: int) -> List[np.ndarray]:
    """The control: at the same rows of the same sequences, the token the
    float8-operand forward puts first, and how far its float32 reference
    logit lies below the best."""
    out = []
    for prompt, served in requests:
        seq, rows = _rows(prompt, served)
        tok = jnp.asarray(_pad(seq, max_len))
        r = jnp.asarray(_pad(rows, max_len))
        h8 = _hidden(m, True, params, buffers, tok)
        _, _, pick = _stats(m, True, params, h8, r, r)
        h = _hidden(m, False, params, buffers, tok)
        best, got, _ = _stats(m, False, params, h, r, pick)
        n = len(served)
        out.append(np.asarray(best)[:n] - np.asarray(got)[:n])
    return out
