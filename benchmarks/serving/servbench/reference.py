"""The pieces every plain reference shares, imported from nothing of the
serving program: float32 matmuls at ``Precision.HIGHEST`` and their float8
control, RMSNorm, interleaved RoPE, the LM head in blocks of rows, and the
comparison of served tokens against the reference's logits.

A model module (``servbench/models/<name>.py``) writes its own forward to
final-normed hidden states and its head matrix on these, and hands both to
``served_gaps``.

Precision: float32 at ``Precision.HIGHEST``.  The control (``control=True``)
is the same forward one precision step below what the configuration states:
the serving program keeps float32 weights and activations but runs its
matmuls at the TPU's default precision, one bfloat16 pass, so every matmul
operand is rounded to float8 (e4m3, scaled per tensor to its absolute
maximum) and the products are summed in float32.  The head runs in blocks of
positions, so a long sequence fits beside the weights.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 256


def fp8(x):
    """Round to float8 e4m3 on a per-tensor scale (absmax → 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def einsum(control: bool):
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    if not control:
        return hi
    return lambda spec, a, b: hi(spec, fp8(a), fp8(b))


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, pos, freqs):
    """Rotate interleaved pairs of x [T, H, 2r] at positions pos [T] by the
    per-head frequencies freqs [H, r]."""
    ang = pos[:, None, None].astype(jnp.float32) * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos], axis=-1)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def stats(head: Callable, m, control: bool, params, hidden, rows, targets):
    """Per row: (best logit, logit of ``targets``, argmax) of the logits at
    hidden[rows], in blocks of HEAD_BLOCK rows; ``head(m, params)`` is the
    head matrix [d, vocab] over the true vocabulary."""
    w = head(m, params)
    ein = einsum(control)

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


def served_gaps(hidden: Callable, head: Callable, m, params, buffers,
                requests: List[Tuple], max_len: int,
                control: bool = False) -> List[np.ndarray]:
    """For each ``(prompt, served tokens)``: how far each served token's
    reference logit lies below the reference's best logit at its row.

    ``hidden(m, control, params, buffers, tokens)`` is the module's jitted
    forward to final-normed hidden states [max_len, d].  With ``control``
    the token judged at each row is not the served one but the one the
    float8-operand forward puts first there."""
    out = []
    for prompt, served in requests:
        seq, rows = _rows(prompt, served)
        tok = jnp.asarray(_pad(seq, max_len))
        r = jnp.asarray(_pad(rows, max_len))
        if control:
            h8 = hidden(m, True, params, buffers, tok)
            _, _, judged = stats(head, m, True, params, h8, r, r)
        else:
            judged = jnp.asarray(_pad(served, max_len))
        h = hidden(m, False, params, buffers, tok)
        best, got, _ = stats(head, m, False, params, h, r, judged)
        n = len(served)
        out.append(np.asarray(best)[:n] - np.asarray(got)[:n])
    return out
