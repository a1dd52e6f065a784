"""Pallas kernels vs ref.py oracles — shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import elite_decode as ed
from repro.kernels import flash_prefill as fp
from repro.kernels import rope_elite as re_k


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("nkv,G,r2,dc,S,bs", [
    (2, 4, 8, 64, 128, 32),
    (1, 8, 16, 128, 256, 64),
    (4, 1, 4, 32, 64, 64),       # MHA-like, single block
    (2, 2, 8, 96, 96, 32),       # dc not 128-aligned, S==3 blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_elite_decode_sweep(nkv, G, r2, dc, S, bs, dtype):
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 5)
    B = 2
    nh = nkv * G
    q_e = jax.random.normal(ks[0], (B, nh, r2), dtype)
    q_lat = jax.random.normal(ks[1], (B, nh, dc), dtype)
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2), dtype)
    c = jax.random.normal(ks[3], (B, S, dc), dtype)
    lengths = jnp.array([S, max(1, S // 3)], jnp.int32)
    o_k = ed.elite_decode(q_e, q_lat, k_e, c, c, lengths, G, 0.1,
                          block_s=bs, interpret=True)
    o_r = ref.elite_decode_ref(q_e, q_lat, k_e, c, c, lengths, G, 0.1)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), **_tol(dtype))


def test_elite_decode_separate_cv():
    """S-LRD: distinct c_k / c_v caches."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    B, nkv, G, r2, dc, S = 1, 2, 2, 4, 32, 64
    nh = nkv * G
    q_e = jax.random.normal(ks[0], (B, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, nh, dc))
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2))
    c_k = jax.random.normal(ks[3], (B, S, dc))
    c_v = jax.random.normal(ks[4], (B, S, dc))
    lengths = jnp.array([40], jnp.int32)
    o_k = ed.elite_decode(q_e, q_lat, k_e, c_k, c_v, lengths, G, 0.2,
                          block_s=16, interpret=True)
    o_r = ref.elite_decode_ref(q_e, q_lat, k_e, c_k, c_v, lengths, G, 0.2)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,nh,nkv,dh,bq,bk", [
    (64, 4, 2, 32, 16, 16),
    (128, 2, 2, 64, 32, 64),
    (96, 8, 2, 16, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_sweep(S, nh, nkv, dh, bq, bk, dtype):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    B = 2
    q = jax.random.normal(ks[0], (B, S, nh, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, nkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, nkv, dh), dtype)
    o_k = fp.flash_prefill(q, k, v, nh // nkv, dh ** -0.5,
                           block_q=bq, block_k=bk, interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, nh // nkv, dh ** -0.5)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("off,Sq,Sk,bq,bk", [
    (32, 32, 64, 16, 16),        # resume mid-sequence
    (48, 16, 64, 16, 32),        # last chunk, chunk < block_k
    (0, 64, 64, 32, 32),         # offset 0 == ordinary causal
])
def test_flash_prefill_resumed_chunk(off, Sq, Sk, bq, bk):
    """q_offset parity: a resumed chunk must equal the same rows of one-shot
    causal attention over the full sequence."""
    nh, nkv, dh = 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, Sk, nh, dh))
    k = jax.random.normal(ks[1], (B, Sk, nkv, dh))
    v = jax.random.normal(ks[2], (B, Sk, nkv, dh))
    o_full = ref.flash_prefill_ref(q, k, v, nh // nkv, dh ** -0.5)
    q_chunk = q[:, off:off + Sq]
    o_k = fp.flash_prefill(q_chunk, k, v, nh // nkv, dh ** -0.5,
                           block_q=bq, block_k=bk, q_offset=off,
                           interpret=True)
    o_r = ref.flash_prefill_ref(q_chunk, k, v, nh // nkv, dh ** -0.5,
                                q_offset=off)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_full[:, off:off + Sq]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("offs,lens,bq,bk", [
    ([0, 16, 48], None, 16, 16),           # mixed fresh + resumed lanes
    ([32, 8, 0], [64, 24, 16], 16, 32),    # per-lane padded key tails
    ([48, 48, 48], [64, 64, 0], 16, 16),   # a dead lane (kv_len 0 → zeros)
])
def test_flash_prefill_per_lane_vectors(offs, lens, bq, bk):
    """Per-lane q_offsets/kv_lens: each lane of one packed call must equal a
    separate single-lane call with that lane's scalar offset — the batched
    chunked-prefill contract (chunks of different sequences, one forward)."""
    nh, nkv, dh, Sq, Sk = 4, 2, 32, 16, 64
    B = len(offs)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, Sq, nh, dh))
    k = jax.random.normal(ks[1], (B, Sk, nkv, dh))
    v = jax.random.normal(ks[2], (B, Sk, nkv, dh))
    offs_a = jnp.asarray(offs, jnp.int32)
    lens_a = None if lens is None else jnp.asarray(lens, jnp.int32)
    o_k = fp.flash_prefill(q, k, v, nh // nkv, dh ** -0.5, block_q=bq,
                           block_k=bk, q_offset=offs_a, kv_lens=lens_a,
                           interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, nh // nkv, dh ** -0.5,
                                q_offset=offs_a, kv_lens=lens_a)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        if lens is not None and lens[b] < Sq + offs[b]:
            continue                       # scalar int path asserts Sk bounds
        o_b = fp.flash_prefill(q[b:b + 1], k[b:b + 1], v[b:b + 1], nh // nkv,
                               dh ** -0.5, block_q=bq, block_k=bk,
                               q_offset=offs[b], interpret=True)
        if lens is None or lens[b] == Sk:
            np.testing.assert_allclose(np.asarray(o_k[b]), np.asarray(o_b[0]),
                                       atol=2e-5, rtol=2e-5)
    if lens is not None and lens[-1] == 0:
        assert float(jnp.max(jnp.abs(o_k[-1]))) == 0.0


def _verify_inputs(B, W, nkv, G, r2, dc, n_blocks, bs, seed=4):
    """Random paged pool + window queries for the verify kernel tests."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    nh = nkv * G
    q_e = jax.random.normal(ks[0], (B, W, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, W, nh, dc))
    k_e_p = jax.random.normal(ks[2], (n_blocks * bs, nkv * r2))
    c_p = jax.random.normal(ks[3], (n_blocks * bs, dc))
    # disjoint block chains in scrambled physical order
    perm = np.random.default_rng(seed).permutation(n_blocks)
    mb = n_blocks // B
    bt = jnp.asarray(perm[:B * mb].reshape(B, mb), jnp.int32)
    return q_e, q_lat, k_e_p, c_p, bt


@pytest.mark.parametrize("W,offs,lens,bs", [
    (3, [10, 0], [13, 3], 8),      # windows crossing block boundaries
    (5, [6, 30], [11, 35], 8),     # off + W spans 2–3 blocks, uneven lanes
    (2, [0, 0], [2, 0], 4),        # fresh lane + a dead kv_len==0 lane
])
def test_elite_verify_paged_kernel_vs_oracle(W, offs, lens, bs):
    """The k+1-token verify window vs the paged oracle: the Pallas block-
    table walk must reproduce the gather-based reference for windows that
    cross block boundaries, start at position 0 (fresh lane), or are dead
    (kv_len == 0 → exact zeros)."""
    B, nkv, G, r2, dc = 2, 2, 2, 4, 16
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, W, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs)
    offs_a = jnp.asarray(offs, jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    o_r = ref.elite_verify_paged_ref(q_e, q_lat, k_e_p, c_p, c_p, bt, offs_a,
                                     lens_a, G, 0.2, bs)
    o_k = ed.elite_verify_paged(q_e, q_lat, k_e_p, c_p, c_p, bt, offs_a,
                                lens_a, G, 0.2, bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    for b in range(B):
        if lens[b] == 0:               # dead lane: exact zeros, no uniform-p
            assert float(jnp.max(jnp.abs(o_k[b]))) == 0.0
            assert float(jnp.max(jnp.abs(o_r[b]))) == 0.0


def test_elite_verify_window_matches_flash_mask():
    """Cross-oracle check: the verify window's offset-causal mask is exactly
    ``flash_prefill``'s resumed-chunk diagonal — scoring the same window in
    materialized K/V space (keys = [k_e | c·I], values = c) must agree."""
    B, nkv, G, r2, dc, W = 2, 2, 2, 4, 16, 3
    S = 32
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    nh = nkv * G
    q_e = jax.random.normal(ks[0], (B, W, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, W, nh, dc))
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2))
    c = jax.random.normal(ks[3], (B, S, dc))
    offs = jnp.asarray([10, 4], jnp.int32)
    lens = jnp.asarray([13, 7], jnp.int32)
    o_v = ref.elite_verify_ref(q_e, q_lat, k_e, c, c, offs, lens, G, 0.2)
    # materialized equivalent: q = [q_e | q_lat] per query head against
    # k = [k_e(kv head) | c] (latent shared across heads); the value carries
    # the latent in its last dc dims (flash keeps one head width throughout)
    q_full = jnp.concatenate([q_e, q_lat], axis=-1)          # [B,W,nh,r2+dc]
    c_h = jnp.broadcast_to(c[:, :, None], (B, S, nkv, dc))
    k_full = jnp.concatenate([k_e, c_h], axis=-1)
    v_full = jnp.concatenate([jnp.zeros((B, S, nkv, r2)), c_h], axis=-1)
    o_f = ref.flash_prefill_ref(q_full, k_full, v_full, G, 0.2,
                                q_offset=offs, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(o_v), np.asarray(o_f[..., r2:]),
                               atol=2e-5, rtol=2e-5)


def test_elite_verify_mixed_decode_lanes():
    """Mixed verify/decode lanes in ONE batched call: a plain decode lane is
    the degenerate window whose row 0 sits at position length-1 (rows past
    the live length produce defined-but-ignored values); its row 0 must
    equal the single-query paged decode oracle while a full verify lane
    rides alongside."""
    B, nkv, G, r2, dc, W, bs = 2, 2, 2, 4, 16, 3, 8
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, W, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=9)
    dec_len = 14                        # lane 0: plain decode of token 14
    offs = jnp.asarray([dec_len - 1, 5], jnp.int32)    # lane 1: verify window
    lens = jnp.asarray([dec_len, 5 + W], jnp.int32)
    o_v = ed.elite_verify_paged(q_e, q_lat, k_e_p, c_p, c_p, bt, offs, lens,
                                G, 0.2, bs, interpret=True)
    o_r = ref.elite_verify_paged_ref(q_e, q_lat, k_e_p, c_p, c_p, bt, offs,
                                     lens, G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_v), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    # decode lane row 0 == the single-query decode kernel's answer
    o_d = ref.elite_decode_paged_ref(q_e[:, 0], q_lat[:, 0], k_e_p, c_p, c_p,
                                     bt, jnp.asarray([dec_len, 0], jnp.int32),
                                     G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_v[0, 0]), np.asarray(o_d[0]),
                               atol=3e-5, rtol=3e-5)


def test_verify_kernel_matches_model_attention(tiny_elite_cfg, tiny_elite_model):
    """End-to-end: lm.apply_verify_paged's logits row for a 1-token window
    equal lm.apply_decode_paged's for the same state (the W=1 degenerate
    case the scheduler relies on for mixed accounting)."""
    from repro.core.cache import PagedKVPool
    from repro.models import lm
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    sp, bsz, mb = 9, 4, 8
    pool = PagedKVPool(cfg, num_blocks=16, block_size=bsz)
    pool.ensure_capacity(0, sp)
    prompt = (np.arange(sp) * 3 % cfg.vocab_size).astype(np.int32)
    toks = np.zeros((1, 12), np.int32)
    toks[0, :sp] = prompt
    sm = pool.prefill_slot_mapping(0, 0, sp, 12)[None]
    _, pool.pages = lm.apply_prefill_paged(
        params, buffers, cfg, {"tokens": jnp.asarray(toks)}, pool.pages,
        jnp.asarray(sm))
    pool.ensure_capacity(0, sp + 1)
    bt = jnp.asarray(pool.block_table_array([0], mb))
    nxt = np.asarray([[17]], np.int32)
    sm1 = jnp.asarray(pool.slot_mapping([0], [sp]))
    dec_logits, _ = lm.apply_decode_paged(
        params, buffers, cfg, {"tokens": jnp.asarray(nxt)}, pool.pages, sm1,
        bt, jnp.asarray([sp + 1], jnp.int32), block_size=bsz)
    ver_logits, _ = lm.apply_verify_paged(
        params, buffers, cfg, {"tokens": jnp.asarray(nxt)}, pool.pages,
        sm1[:, None], bt, jnp.asarray([sp], jnp.int32),
        jnp.asarray([sp + 1], jnp.int32), block_size=bsz)
    np.testing.assert_allclose(np.asarray(ver_logits[0, 0]),
                               np.asarray(dec_logits[0, 0]),
                               atol=2e-4, rtol=2e-4)


def _quantize_pool(k_e_p, c_k_p, c_v_p):
    """Per-slot symmetric absmax int8 pool (core/quant.py layout)."""
    from repro.core import quant
    k_q, k_s = quant.quantize_rows(k_e_p)
    ck_q, ck_s = quant.quantize_rows(c_k_p)
    cv_q, cv_s = quant.quantize_rows(c_v_p)
    return k_q, ck_q, cv_q, k_s, ck_s, cv_s


@pytest.mark.parametrize("lens,bs", [
    ([13, 3], 8),                  # lengths crossing block boundaries
    ([16, 8], 8),                  # lengths exactly on block boundaries
    ([11, 0], 4),                  # live lane + dead kv_len==0 lane
])
def test_elite_decode_paged_q8_kernel_vs_oracle(lens, bs):
    """Fused-dequant paged decode vs the quantized oracle: the in-register
    ``int8 * scale`` multiply must reproduce dequantize-then-attend exactly
    (same matrix as the f32 kernel: boundary kv_lens, dead lanes)."""
    B, nkv, G, r2, dc = 2, 2, 2, 4, 16
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, 1, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=13)
    q_e, q_lat = q_e[:, 0], q_lat[:, 0]
    k_q, ck_q, cv_q, k_s, ck_s, cv_s = _quantize_pool(k_e_p, c_p, c_p)
    lens_a = jnp.asarray(lens, jnp.int32)
    o_r = ref.elite_decode_paged_q8_ref(q_e, q_lat, k_q, ck_q, cv_q,
                                        k_s, ck_s, cv_s, bt, lens_a,
                                        G, 0.2, bs)
    o_k = ed.elite_decode_paged_q8(q_e, q_lat, k_q, ck_q, cv_q,
                                   k_s, ck_s, cv_s, bt, lens_a,
                                   G, 0.2, bs, interpret=True)
    assert o_k.dtype == jnp.float32        # int8 pages never leak their dtype
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    for b in range(B):
        if lens[b] == 0:
            assert float(jnp.max(jnp.abs(o_k[b]))) == 0.0


@pytest.mark.parametrize("W,offs,lens,bs", [
    (3, [10, 0], [13, 3], 8),      # windows crossing block boundaries
    (5, [6, 30], [11, 35], 8),     # off + W spans 2–3 blocks, uneven lanes
    (2, [0, 0], [2, 0], 4),        # fresh lane + a dead kv_len==0 lane
])
def test_elite_verify_paged_q8_kernel_vs_oracle(W, offs, lens, bs):
    """Quantized verify windows vs the quantized oracle — the exact f32
    verify matrix re-run over an int8 pool with per-slot scales."""
    B, nkv, G, r2, dc = 2, 2, 2, 4, 16
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, W, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs)
    k_q, ck_q, cv_q, k_s, ck_s, cv_s = _quantize_pool(k_e_p, c_p, c_p)
    offs_a = jnp.asarray(offs, jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    o_r = ref.elite_verify_paged_q8_ref(q_e, q_lat, k_q, ck_q, cv_q,
                                        k_s, ck_s, cv_s, bt, offs_a, lens_a,
                                        G, 0.2, bs)
    o_k = ed.elite_verify_paged_q8(q_e, q_lat, k_q, ck_q, cv_q,
                                   k_s, ck_s, cv_s, bt, offs_a, lens_a,
                                   G, 0.2, bs, interpret=True)
    assert o_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    for b in range(B):
        if lens[b] == 0:
            assert float(jnp.max(jnp.abs(o_k[b]))) == 0.0
            assert float(jnp.max(jnp.abs(o_r[b]))) == 0.0


def test_elite_verify_paged_q8_mixed_decode_lanes():
    """Mixed verify/decode lanes over the int8 pool: a W=1-style decode lane
    (row 0 at position length-1) inside a verify call must equal the
    single-query q8 decode oracle — the scheduler's mixed-lane contract
    holds under quantization."""
    B, nkv, G, r2, dc, W, bs = 2, 2, 2, 4, 16, 3, 8
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, W, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=9)
    k_q, ck_q, cv_q, k_s, ck_s, cv_s = _quantize_pool(k_e_p, c_p, c_p)
    dec_len = 14
    offs = jnp.asarray([dec_len - 1, 5], jnp.int32)
    lens = jnp.asarray([dec_len, 5 + W], jnp.int32)
    o_v = ed.elite_verify_paged_q8(q_e, q_lat, k_q, ck_q, cv_q,
                                   k_s, ck_s, cv_s, bt, offs, lens,
                                   G, 0.2, bs, interpret=True)
    o_r = ref.elite_verify_paged_q8_ref(q_e, q_lat, k_q, ck_q, cv_q,
                                        k_s, ck_s, cv_s, bt, offs, lens,
                                        G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_v), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    o_d = ref.elite_decode_paged_q8_ref(q_e[:, 0], q_lat[:, 0], k_q, ck_q,
                                        cv_q, k_s, ck_s, cv_s, bt,
                                        jnp.asarray([dec_len, 0], jnp.int32),
                                        G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_v[0, 0]), np.asarray(o_d[0]),
                               atol=3e-5, rtol=3e-5)


def test_elite_verify_paged_q8_w1_equals_decode():
    """W=1 quantized verify ≡ quantized decode: the degenerate one-token
    window must be the same computation through both fused-dequant kernels
    (the contract plain decode and speculative verify share)."""
    B, nkv, G, r2, dc, bs = 2, 2, 2, 4, 16, 8
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, 1, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=5)
    k_q, ck_q, cv_q, k_s, ck_s, cv_s = _quantize_pool(k_e_p, c_p, c_p)
    lens = jnp.asarray([13, 6], jnp.int32)
    o_v = ed.elite_verify_paged_q8(q_e, q_lat, k_q, ck_q, cv_q,
                                   k_s, ck_s, cv_s, bt, lens - 1, lens,
                                   G, 0.2, bs, interpret=True)
    o_d = ed.elite_decode_paged_q8(q_e[:, 0], q_lat[:, 0], k_q, ck_q, cv_q,
                                   k_s, ck_s, cv_s, bt, lens,
                                   G, 0.2, bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_v[:, 0]), np.asarray(o_d),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_elite_decode_sparse_paged_kernel_vs_oracle(quantized):
    """The sparse kernels' selection walk vs the sparse oracles: a top-k
    selection with partial blocks and a dead lane; and with the whole chain
    selected, the sparse kernel equals the dense paged kernel bit for bit."""
    B, nkv, G, r2, dc, bs = 2, 2, 2, 4, 16, 4
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, 1, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=17)
    q_e, q_lat = q_e[:, 0], q_lat[:, 0]
    pages = (k_e_p, c_p, c_p)
    if quantized:
        pages = _quantize_pool(k_e_p, c_p, c_p)
    sfx = "_q8" if quantized else ""
    kernel = getattr(ed, "elite_decode_sparse_paged" + sfx)
    oracle = getattr(ref, "elite_decode_sparse_paged" + sfx + "_ref")
    lens = jnp.asarray([27, 0], jnp.int32)
    blk = c_p.reshape(-1, bs, dc)
    sel = ref.select_topk_blocks(q_lat, blk.mean(1), jnp.abs(blk).max(1), bt,
                                 lens, bs, num_sel=3, recent=1)
    o_k = kernel(q_e, q_lat, *pages, *sel, G, 0.2, bs, interpret=True)
    o_r = oracle(q_e, q_lat, *pages, *sel, G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    assert float(jnp.max(jnp.abs(o_k[1]))) == 0.0
    full = ref.select_topk_blocks(q_lat, blk.mean(1), jnp.abs(blk).max(1), bt,
                                  lens, bs, num_sel=bt.shape[1], recent=1)
    dense = getattr(ed, "elite_decode_paged" + sfx)
    np.testing.assert_array_equal(
        np.asarray(kernel(q_e, q_lat, *pages, *full, G, 0.2, bs,
                          interpret=True)),
        np.asarray(dense(q_e, q_lat, *pages, bt, lens, G, 0.2, bs,
                         interpret=True)))


def test_q8_oracle_tracks_f32_oracle():
    """Quality sanity at the kernel level: the quantized oracle's outputs
    stay close to the f32 oracle over the same pool (int8 absmax keeps
    ~2 decimal digits — the serving-level wall is tests/test_quant.py)."""
    B, nkv, G, r2, dc, bs = 2, 2, 2, 4, 16, 8
    q_e, q_lat, k_e_p, c_p, bt = _verify_inputs(B, 1, nkv, G, r2, dc,
                                                n_blocks=16, bs=bs, seed=21)
    q_e, q_lat = q_e[:, 0], q_lat[:, 0]
    k_q, ck_q, cv_q, k_s, ck_s, cv_s = _quantize_pool(k_e_p, c_p, c_p)
    lens = jnp.asarray([13, 9], jnp.int32)
    o_f = ref.elite_decode_paged_ref(q_e, q_lat, k_e_p, c_p, c_p, bt, lens,
                                     G, 0.2, bs)
    o_q = ref.elite_decode_paged_q8_ref(q_e, q_lat, k_q, ck_q, cv_q,
                                        k_s, ck_s, cv_s, bt, lens, G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_f),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("S,H,r,bs", [(64, 4, 4, 16), (32, 2, 8, 32), (128, 1, 2, 64)])
def test_rope_elite_sweep(S, H, r, bs):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, S, H, 2 * r))
    freqs = jnp.exp(-jax.random.uniform(jax.random.PRNGKey(3), (H, r)) * 4)
    pos = jnp.arange(S, dtype=jnp.int32)
    o_k = re_k.rope_elite(x, pos, freqs, block_s=bs, interpret=True)
    o_r = ref.rope_elite_ref(x, pos, freqs)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5, rtol=1e-5)


def test_kernel_matches_model_decode(tiny_elite_cfg, tiny_elite_model):
    """elite_decode kernel output == the model's XLA absorbed-decode internals."""
    from repro.configs import make_inputs
    from repro.models import lm
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    B, S = 2, 16
    batch = make_inputs(cfg, B, S, "train", seed=11)
    cache = lm.init_cache(cfg, B, S, dtype=jnp.float32)
    _, cache = lm.apply_prefill(params, buffers, cfg,
                                {"tokens": batch["tokens"][:, :S - 1]}, cache)
    # layer-0 decode internals
    from repro.core import elite_attention as ea
    from repro.models.layers import rmsnorm
    p0 = jax.tree.map(lambda t: t[0], params["blocks"]["p0"])
    b0 = jax.tree.map(lambda t: t[0], buffers["blocks"]["p0"])
    h = params["embed"]["table"][batch["tokens"][:, S - 1:S]].astype(cfg.dtype)
    hn = rmsnorm(p0["attn_norm"], h, cfg.norm_eps)
    idx = cache["index"]
    c0 = jax.tree.map(lambda t: t[0], cache["blocks"]["p0"])
    out_ref, newc = ea.apply_decode(p0["attn"], cfg, b0, hn, idx, c0)

    # kernel path: rebuild q_e/q_lat exactly as apply_decode does
    from repro.core import rope as rope_lib
    pos = jnp.full((B, 1), idx, jnp.int32)
    q_e, q_ne = ea._project_q(p0["attn"], cfg, hn, pos)
    q_e = ea._rot_q(cfg, b0, q_e, pos)
    G = cfg.q_group
    bk_q = rope_lib.expand_kv_to_q(jnp.moveaxis(p0["attn"]["bk"], 1, 0), G)
    q_lat = jnp.einsum("bshn,hcn->bshc", q_ne, bk_q)
    K_e = newc["k_e"].astype(jnp.float32)
    C = newc["c"].astype(jnp.float32)
    lengths = jnp.full((B,), idx + 1, jnp.int32)
    o_lat = ed.elite_decode(q_e[:, 0], q_lat[:, 0], K_e, C, C, lengths, G,
                            cfg.head_dim ** -0.5, block_s=8, interpret=True)
    bv_q = rope_lib.expand_kv_to_q(jnp.moveaxis(p0["attn"]["bv"], 1, 0), G)
    o_heads = jnp.einsum("bhc,hcd->bhd", o_lat, bv_q)
    out_kernel = jnp.einsum("bhe,hed->bd", o_heads, p0["attn"]["wo"])[:, None]
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_ref),
                               atol=5e-5, rtol=5e-5)
