"""A dense decoder with EliteKV attention: GQA whose keys keep ``elite_r``
RoPE frequencies per kv head and take the rest, and the values, from one
joint low-rank latent ``c``; a SwiGLU FFN.  Yi-6B and Granite-3.0-2B run
as this.

**Configuration** (``configs/<config>.json``): ``arch``, ``hidden_size``,
``intermediate_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (default ``hidden_size / num_attention_heads``),
``num_hidden_layers``, ``vocab_size``, ``rope_theta``, ``rms_norm_eps``,
``tie_word_embeddings``, ``dtype`` and ``elitekv`` (``elite_r``, ``d_ckv``,
``lrd``).

**Weights.**  The tree the serving program takes (``models/lm.py`` with an
EliteKV attention), stacked over layers in one block ``p0``.  The embedding
and an untied head use scale 0.02.  Each layer and kv head keeps
``elite_r`` rotary chunks drawn at random from the ``d_head/2`` RoPE
frequencies — the shape a RoPElite search leaves behind.

**Reference.**  Per layer, with ``x = rmsnorm(h)``:

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
batching and no kernel.  Attention runs in blocks of query rows.

**Work.**  Counts are of the algorithm's work, not of what today's kernels
do: the decode attention reads each cached token's rotary key ``k_e`` and
latent ``c`` once and scores every query head against them, whatever block
walk or padding an implementation adds.  Model FLOPs per token: two per
weight a token meets in the layer stack, plus attention in the form the
phase computes — absorbed over the latent for decode (rotary 2r dims +
``d_ckv`` for scores, ``d_ckv`` for the output, per head and cached token),
materialized K/V for prefill (``4·d_head`` per head and key).  The LM head
counts only where a token is served from its logits: once per decode token,
once per finished prompt.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from .. import reference as ref
from .. import weights

Q_BLOCK = 256

TINY = dict(model="dense_elitekv", arch="tiny", hidden_size=64,
            intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=300,
            rope_theta=10000.0, rms_norm_eps=1e-5, tie_word_embeddings=False,
            elitekv=dict(elite_r=2, d_ckv=16, lrd="joint"),
            dtype="float32", pool_dtype="float32", block_size=16,
            num_blocks=64)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes a configuration file states, as one hashable value."""
    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    elite_r: int
    d_ckv: int

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @property
    def q_group(self) -> int:
        return self.n_heads // self.n_kv


def dims(conf: Dict[str, Any]) -> Dims:
    d, nh = conf["hidden_size"], conf["num_attention_heads"]
    e = conf["elitekv"]
    return Dims(layers=conf["num_hidden_layers"], d_model=d, n_heads=nh,
                n_kv=conf["num_key_value_heads"],
                d_head=conf.get("head_dim", d // nh),
                d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                rope_theta=float(conf["rope_theta"]),
                norm_eps=float(conf["rms_norm_eps"]),
                tied=bool(conf["tie_word_embeddings"]),
                elite_r=e["elite_r"], d_ckv=e["d_ckv"])


def program_config(conf: Dict[str, Any]):
    """The program's model configuration for a configuration file."""
    from repro.configs.base import EliteKVConfig, ModelConfig
    e = conf["elitekv"]
    return ModelConfig(
        name=conf["arch"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=jnp.dtype(conf["dtype"]),
        elitekv=EliteKVConfig(enabled=True, elite_r=e["elite_r"],
                              d_ckv=e["d_ckv"], lrd=e["lrd"]))


# -- weights ------------------------------------------------------------------

def _leaves(m: Dims) -> List[weights.Leaf]:
    """(path, shape, scale) of every weight; scale None marks a norm."""
    L, d, nh, nkv, dh, dc = (m.layers, m.d_model, m.n_heads, m.n_kv,
                             m.d_head, m.d_ckv)
    r2 = 2 * m.elite_r
    out = [(("embed", "table"), (m.padded_vocab, d), 0.02),
           (("final_norm", "scale"), (d,), None)]
    if not m.tied:
        out.append((("lm_head", "w"), (d, m.padded_vocab), 0.02))
    blk = ("blocks", "p0")
    out += [
        (blk + ("attn_norm", "scale"), (L, d), None),
        (blk + ("attn", "wq"), (L, d, nh, dh), d ** -0.5),
        (blk + ("attn", "wk_e"), (L, d, nkv, r2), d ** -0.5),
        (blk + ("attn", "a_kv"), (L, d, dc), d ** -0.5),
        (blk + ("attn", "bk"), (L, dc, nkv, dh - r2), dc ** -0.5),
        (blk + ("attn", "bv"), (L, dc, nkv, dh), dc ** -0.5),
        (blk + ("attn", "wo"), (L, nh, dh, d), (nh * dh) ** -0.5),
        (blk + ("ffn_norm", "scale"), (L, d), None),
        (blk + ("ffn", "w_gate"), (L, d, m.d_ff), d ** -0.5),
        (blk + ("ffn", "w_up"), (L, d, m.d_ff), d ** -0.5),
        (blk + ("ffn", "w_down"), (L, m.d_ff, d), m.d_ff ** -0.5),
    ]
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(m: Dims, key, dtype) -> Tuple[Dict, Dict]:
    leaves = _leaves(m)
    keys = jax.random.split(key, len(leaves) + 1)
    params = weights.draw(leaves, keys, dtype)
    # per (layer, kv head): r distinct RoPE chunks, in a random order
    n_chunks = m.d_head // 2
    order = jnp.argsort(jax.random.uniform(keys[-1], (m.layers, m.n_kv,
                                                      n_chunks)), axis=-1)
    idx = order[..., :m.elite_r].astype(jnp.float32)
    freqs = m.rope_theta ** (-2.0 * idx / m.d_head)
    buffers = {"blocks": {"p0": {"elite_freqs": freqs}}}
    return params, buffers


def make(m: Dims, seed: int, dtype=jnp.float32) -> Tuple[Dict, Dict]:
    """(params, buffers) for ``seed``; elite frequencies stay float32."""
    return _make(m, weights.seed_key(seed), jnp.dtype(dtype))


def param_count(m: Dims) -> int:
    return weights.count(_leaves(m))


# -- the plain reference ------------------------------------------------------

def _layer(m: Dims, control: bool, pos, h, wl):
    p, freqs = wl
    ein = ref.einsum(control)
    T = h.shape[0]
    r2, G = 2 * m.elite_r, m.q_group
    x = ref.rmsnorm(h, p["attn_norm"]["scale"], m.norm_eps)
    a = p["attn"]
    q = ein("td,dhe->the", x, a["wq"])
    q_e = ref.rope(q[..., :r2], pos, jnp.repeat(freqs, G, axis=0))
    q = jnp.concatenate([q_e, q[..., r2:]], axis=-1)
    k_e = ref.rope(ein("td,dhe->the", x, a["wk_e"]), pos, freqs)
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
    x = ref.rmsnorm(h, p["ffn_norm"]["scale"], m.norm_eps)
    f = p["ffn"]
    g = jax.nn.silu(ein("td,df->tf", x, f["w_gate"])) * ein(
        "td,df->tf", x, f["w_up"])
    return h + ein("tf,fd->td", g, f["w_down"]), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def hidden(m: Dims, control: bool, params, buffers, tokens):
    """Final-normed hidden states [T, d] of one sequence."""
    pos = jnp.arange(tokens.shape[0])
    h = params["embed"]["table"][tokens]
    body = functools.partial(_layer, m, control, pos)
    h, _ = jax.lax.scan(body, h, (params["blocks"]["p0"],
                                  buffers["blocks"]["p0"]["elite_freqs"]))
    return ref.rmsnorm(h, params["final_norm"]["scale"], m.norm_eps)


def head(m: Dims, params):
    """The head matrix [d, vocab] over the true vocabulary."""
    w = (params["embed"]["table"].T if m.tied else params["lm_head"]["w"])
    return w[:, :m.vocab]


def served_gaps(m: Dims, params, buffers, checked, max_len: int,
                control: bool = False):
    """``reference.served_gaps`` of this model (see there)."""
    return ref.served_gaps(hidden, head, m, params, buffers, checked,
                           max_len, control)


# -- work ---------------------------------------------------------------------

def decode_attn_work(m: Dims, lengths: Iterable[int],
                     elem_bytes: int = 4) -> Tuple[float, float]:
    """(flops, bytes) of one decode step's attention over lanes of context
    ``lengths`` (each including the token being decoded), summed over the
    stack's layers, which all attend alike."""
    r2, dc, nh, nkv = 2 * m.elite_r, m.d_ckv, m.n_heads, m.n_kv
    flops = bytes_ = 0.0
    for n in lengths:
        if n <= 0:
            continue
        flops += 2.0 * nh * n * (r2 + dc) + 2.0 * nh * n * dc
        bytes_ += n * (nkv * r2 + dc) * elem_bytes          # cache, read once
        bytes_ += nh * (r2 + dc) * 4 + nh * dc * 4           # queries, output
    return m.layers * flops, m.layers * bytes_


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
