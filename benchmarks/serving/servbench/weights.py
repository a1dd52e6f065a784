"""Seeded random weights of a dense EliteKV decoder, made on the device.

One jitted call turns ``--seed`` into every weight, already stacked over
layers and in the type they are served in, so no host copy and no second
stacked copy is ever held.  The tree has the layout the serving program
takes (``models/lm.py`` with an EliteKV attention); the plain reference
(``reference.py``) calls the same function to make its own copy.

Scales follow the usual fan-in rule (``N(0, 1/fan_in)`` truncated at 3σ),
the embedding and an untied head use 0.02, and norm scales are
``1 + 0.1·N(0, 1)``.  Each layer and kv head keeps ``elite_r`` rotary
chunks drawn at random from the ``d_head/2`` RoPE frequencies — the shape a
RoPElite search leaves behind.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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

    @classmethod
    def from_config(cls, conf: Dict[str, Any]) -> "Dims":
        d, nh = conf["hidden_size"], conf["num_attention_heads"]
        e = conf["elitekv"]
        return cls(layers=conf["num_hidden_layers"], d_model=d, n_heads=nh,
                   n_kv=conf["num_key_value_heads"],
                   d_head=conf.get("head_dim", d // nh),
                   d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]),
                   tied=bool(conf["tie_word_embeddings"]),
                   elite_r=e["elite_r"], d_ckv=e["d_ckv"])


def seed_key(seed: int) -> jnp.ndarray:
    """A threefry key from a seed of any size (``PRNGKey`` keeps only its
    low 32 bits)."""
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray([hi, lo], jnp.uint32)


def _leaves(m: Dims):
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


def _put(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(m: Dims, key, dtype) -> Tuple[Dict, Dict]:
    leaves = _leaves(m)
    keys = jax.random.split(key, len(leaves) + 1)
    params: Dict[str, Any] = {}
    for k, (path, shape, scale) in zip(keys, leaves):
        if scale is None:
            v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            v = jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                            jnp.float32) * scale
        _put(params, path, v.astype(dtype))
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
    return _make(m, seed_key(seed), jnp.dtype(dtype))


def param_count(m: Dims) -> int:
    return sum(int(np.prod(s)) for _, s, _ in _leaves(m))
