"""Paged compressed-cache serving: kernel parity + pool + scheduler.

Golden tier: the paged decode (Pallas interpret mode and the XLA gather
fallback) must match ``kernels/ref.py`` to fp32 tolerance on ragged batches —
including empty (length-0) lanes and exact block-boundary lengths — across
GQA group sizes and block sizes.  Scheduler tier: paged continuous batching
must produce token-identical output to the contiguous lockstep path, and
retired sequences' blocks must actually be recycled.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache import BlockAllocator, OutOfBlocks, PagedKVPool
from repro.kernels import elite_decode as ed
from repro.kernels import ref
from repro.models import lm
from repro.runtime import serve_loop


def _paged_case(seed, B, nkv, G, r2, dc, bs, pool_blocks, mb, lengths):
    """Random pool + per-sequence block chains for the given lengths."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    nh = nkv * G
    q_e = jax.random.normal(ks[0], (B, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, nh, dc))
    k_pages = jax.random.normal(ks[2], (pool_blocks * bs, nkv * r2))
    c_pages = jax.random.normal(ks[3], (pool_blocks * bs, dc))
    # distinct random chains per sequence (disjoint, arbitrary order)
    perm = rng.permutation(pool_blocks)
    bt = np.zeros((B, mb), np.int32)
    used = 0
    for b, length in enumerate(lengths):
        n = -(-length // bs)
        assert used + n <= pool_blocks
        bt[b, :n] = perm[used:used + n]
        used += n
    return (q_e, q_lat, k_pages, c_pages, jnp.asarray(bt),
            jnp.asarray(np.asarray(lengths, np.int32)))


# ---------------------------------------------------------------------------
# golden parity: paged Pallas + XLA fallback vs the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nkv,G,dc,bs", [
    (2, 2, 32, 8),        # GQA
    (1, 4, 64, 16),       # MQA-like, bigger blocks
    (2, 1, 16, 4),        # MHA-like, tiny blocks
])
def test_paged_decode_vs_ref_ragged(nkv, G, dc, bs):
    """Ragged lengths: empty lane, mid-block, exact block boundary, full."""
    mb = 4
    lengths = [0, 1, bs, 2 * bs - 1, 3 * bs, mb * bs]
    B = len(lengths)
    case = _paged_case(0, B, nkv, G, 8, dc, bs, pool_blocks=32, mb=mb,
                       lengths=lengths)
    q_e, q_lat, k_pages, c_pages, bt, lens = case
    o_ref = ref.elite_decode_paged_ref(q_e, q_lat, k_pages, c_pages, c_pages,
                                       bt, lens, G, 0.2, bs)
    o_pal = ed.elite_decode_paged(q_e, q_lat, k_pages, c_pages, c_pages,
                                  bt, lens, G, 0.2, bs, interpret=True)
    o_xla = ed.elite_decode_paged_xla(q_e, q_lat, k_pages, c_pages, c_pages,
                                      bt, lens, G, 0.2, bs)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)
    # empty lane is exactly zero, not a uniform-softmax average
    assert float(jnp.max(jnp.abs(o_ref[0]))) == 0.0
    assert float(jnp.max(jnp.abs(o_pal[0]))) == 0.0


def test_paged_decode_separate_cv():
    """S-LRD: distinct c_k / c_v page streams."""
    nkv, G, r2, dc, bs, mb = 2, 2, 4, 32, 8, 3
    lengths = [bs + 3, 2 * bs]
    q_e, q_lat, k_pages, c_k, bt, lens = _paged_case(
        1, 2, nkv, G, r2, dc, bs, pool_blocks=16, mb=mb, lengths=lengths)
    c_v = jax.random.normal(jax.random.PRNGKey(99), c_k.shape)
    o_ref = ref.elite_decode_paged_ref(q_e, q_lat, k_pages, c_k, c_v,
                                       bt, lens, G, 0.3, bs)
    o_pal = ed.elite_decode_paged(q_e, q_lat, k_pages, c_k, c_v,
                                  bt, lens, G, 0.3, bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)


# The grouped walk: 16-token pages, P = 256 // 16 = 16 pages per step, a
# 40-entry table (not a multiple of P: groups of 16, 16 and 8 entries).
GROUP_BS, GROUP_MB = 16, 40
GROUP_LENGTHS = [
    0,                           # dead lane: fetches nothing, writes zeros
    1,
    256,                         # exactly one group
    0,                           # the next live lane's prefetch skips it
    256 + 17,                    # ends mid-way through the second group
    512,                         # exactly on the second group's edge
    300,
    GROUP_MB * GROUP_BS,         # fills the whole table (short last group)
    0,                           # dead last lane: no prefetch past the end
]


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "q8"])
@pytest.mark.parametrize("joint", [True, False], ids=["joint_c", "c_k_c_v"])
def test_grouped_walk_vs_ref(joint, quantized):
    """The grouped live-page walk against the paged oracle: lengths ending
    mid-group and on a group edge, an empty lane, a lane filling the whole
    table, a table that is not a multiple of P; a joint latent (one stream,
    ``c_v_pages=None``) and separate c_k/c_v streams; f32 and int8 pools."""
    from repro.core import quant
    bs, mb = GROUP_BS, GROUP_MB
    assert ed.pages_per_step(bs, mb) == 16 and mb % 16
    B = len(GROUP_LENGTHS)
    q_e, q_lat, k_pages, c_k, bt, lens = _paged_case(
        7, B, 2, 2, 4, 16, bs, pool_blocks=160, mb=mb, lengths=GROUP_LENGTHS)
    c_v = c_k if joint else jax.random.normal(jax.random.PRNGKey(8),
                                              c_k.shape)
    if quantized:
        (k_q, k_s), (ck_q, ck_s), (cv_q, cv_s) = (
            quant.quantize_rows(x) for x in (k_pages, c_k, c_v))
        o_ref = ref.elite_decode_paged_q8_ref(q_e, q_lat, k_q, ck_q, cv_q,
                                              k_s, ck_s, cv_s, bt, lens, 2,
                                              0.2, bs)
        if joint:
            cv_q = cv_s = None
        o_pal = ed.elite_decode_paged_q8(q_e, q_lat, k_q, ck_q, cv_q, k_s,
                                         ck_s, cv_s, bt, lens, 2, 0.2, bs,
                                         interpret=True)
    else:
        o_ref = ref.elite_decode_paged_ref(q_e, q_lat, k_pages, c_k, c_v,
                                           bt, lens, 2, 0.2, bs)
        o_pal = ed.elite_decode_paged(q_e, q_lat, k_pages, c_k,
                                      None if joint else c_v, bt, lens, 2,
                                      0.2, bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)
    for b in (0, 3, 8):
        assert float(jnp.max(jnp.abs(o_pal[b]))) == 0.0
    if joint and not quantized:
        # one stream read once serves both roles: the same numbers as the
        # same array passed as two streams
        np.testing.assert_array_equal(
            np.asarray(o_pal),
            np.asarray(ed.elite_decode_paged(q_e, q_lat, k_pages, c_k, c_k,
                                             bt, lens, 2, 0.2, bs,
                                             interpret=True)))


def test_paged_matches_contiguous_kernel():
    """A paged layout whose chain is the identity must equal the contiguous
    dense kernel on the same data — the layouts describe the same cache."""
    nkv, G, r2, dc, bs = 2, 2, 8, 32, 8
    S = 4 * bs
    lengths = [S - 3, bs]
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, nh = 2, nkv * G
    q_e = jax.random.normal(ks[0], (B, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, nh, dc))
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2))
    c = jax.random.normal(ks[3], (B, S, dc))
    lens = jnp.asarray(lengths, jnp.int32)
    o_dense = ed.elite_decode(q_e, q_lat, k_e, c, c, lens, G, 0.2,
                              block_s=bs, interpret=True)
    # lay each sequence's cache out in its own pages, identity chains
    nb = S // bs
    k_pages = k_e.reshape(B * S, nkv * r2)
    c_pages = c.reshape(B * S, dc)
    bt = jnp.asarray([[b * nb + i for i in range(nb)] for b in range(B)],
                     jnp.int32)
    o_paged = ed.elite_decode_paged(q_e, q_lat, k_pages, c_pages, c_pages,
                                    bt, lens, G, 0.2, bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=3e-5, rtol=3e-5)


def test_dense_decode_length_zero():
    """The contiguous kernel and oracle agree on empty lanes too."""
    nkv, G, r2, dc, S = 2, 2, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    B, nh = 2, nkv * G
    q_e = jax.random.normal(ks[0], (B, nh, r2))
    q_lat = jax.random.normal(ks[1], (B, nh, dc))
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2))
    c = jax.random.normal(ks[3], (B, S, dc))
    lens = jnp.asarray([0, S // 2], jnp.int32)
    o_k = ed.elite_decode(q_e, q_lat, k_e, c, c, lens, G, 0.25,
                          block_s=8, interpret=True)
    o_r = ref.elite_decode_ref(q_e, q_lat, k_e, c, c, lens, G, 0.25)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=3e-5, rtol=3e-5)
    assert float(jnp.max(jnp.abs(o_r[0]))) == 0.0


# ---------------------------------------------------------------------------
# pool bookkeeping
# ---------------------------------------------------------------------------

def test_block_allocator_exhaustion_and_reuse():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert a.num_free == 1 and a.high_water == 3
    with pytest.raises(OutOfBlocks):
        a.alloc(2)
    a.free(got[:2])
    assert a.num_free == 3
    again = a.alloc(2)
    assert set(again) <= set(got[:2])      # freed blocks come back first
    assert a.high_water == 3               # peak unchanged by churn


def test_pool_accounting(tiny_elite_cfg):
    pool = PagedKVPool(tiny_elite_cfg, num_blocks=8, block_size=4)
    pool.ensure_capacity(0, 6)             # 2 blocks
    pool.ensure_capacity(1, 4)             # 1 block
    s = pool.stats()
    assert s.blocks_in_use == 3 and s.live_tokens == 10
    assert s.allocated_tokens == 12        # internal fragmentation visible
    assert s.live_bytes < s.allocated_bytes
    fpt = pool.floats_per_token()
    assert s.live_bytes == 10 * fpt * 4    # fp32 pool
    pool.free_seq(0)
    assert pool.stats().blocks_in_use == 1
    pool.reset()
    assert pool.stats().blocks_in_use == 0 and pool.length(1) == 0


def test_pool_slot_mapping_chain_order(tiny_elite_cfg):
    pool = PagedKVPool(tiny_elite_cfg, num_blocks=8, block_size=4)
    pool.ensure_capacity(7, 9)             # 3 blocks
    table = pool.block_table(7)
    sm = pool.slot_mapping([7, None], [5, 0])
    assert sm[0] == table[1] * 4 + 1       # position 5 → block 1, offset 1
    assert sm[1] == pool.oob_slot          # inactive lane → sentinel
    pm = pool.prefill_slot_mapping(7, 0, 9, pad_to=12)
    assert pm[8] == table[2] * 4 and (pm[9:] == pool.oob_slot).all()


# ---------------------------------------------------------------------------
# scheduler: paged == contiguous, blocks recycle
# ---------------------------------------------------------------------------

def test_scheduler_matches_contiguous_generation(tiny_elite_cfg, tiny_elite_model):
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    B, Sp, new = 3, 9, 6
    prompts = jax.random.randint(jax.random.PRNGKey(11), (B, Sp), 0,
                                 cfg.vocab_size, jnp.int32)
    out_dense, _ = serve_loop.generate(params, buffers, cfg, prompts, new)
    out_paged, report = serve_loop.generate_paged(params, buffers, cfg,
                                                  prompts, new)
    np.testing.assert_array_equal(out_dense, out_paged)
    assert report.completed == B
    assert report.decoded_tokens == B * new


def test_scheduler_ragged_stream_reuses_blocks(tiny_elite_cfg, tiny_elite_model):
    """Mixed-length staggered workload: drains fully, peak pool usage stays
    below the naive sum of per-request worst cases, and every block returns."""
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    scfg = serve_loop.SchedulerConfig(max_slots=2, block_size=4,
                                      num_blocks=48, max_len=32,
                                      prefill_bucket=4)
    sched = serve_loop.Scheduler(params, buffers, cfg, scfg)
    rng = np.random.default_rng(2)
    reqs = [serve_loop.Request(
        uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                   int(rng.integers(3, 12))).astype(np.int32),
        max_new_tokens=int(rng.integers(3, 10)), arrival=i * 1.0)
        for i in range(6)]
    report = sched.run(reqs)
    assert report.completed == 6
    assert {r.finish_reason for r in sched.finished} <= {"eos", "budget"}
    # block reuse: the acceptance quantity — peak < Σ worst-case
    assert report.pool_high_water_blocks < report.naive_blocks
    assert sched.pool.allocator.num_free == scfg.num_blocks  # all recycled
    # per-request latency metrics exist and are ordered
    assert report.ttft_wall_p95_ms >= report.ttft_wall_p50_ms
    assert report.step_ms_p95 >= report.step_ms_p50


def test_freed_blocks_are_physically_reused(tiny_elite_cfg, tiny_elite_model):
    """With one slot, request B must be served out of the exact physical
    blocks request A returned."""
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    scfg = serve_loop.SchedulerConfig(max_slots=1, block_size=4,
                                      num_blocks=6, max_len=16,
                                      prefill_bucket=4)
    sched = serve_loop.Scheduler(params, buffers, cfg, scfg)
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    a = serve_loop.Request(uid=0, prompt=prompt, max_new_tokens=4)
    b = serve_loop.Request(uid=1, prompt=prompt.copy(), max_new_tokens=4)
    sched.submit(a)
    sched.submit(b)
    tables = {}
    for _ in range(200):
        alive = sched.step()
        for s in sched.slots:
            if s is not None:
                tables[s.uid] = sched.pool.block_table(s.uid)
        if not alive:
            break
    assert len(sched.finished) == 2
    assert set(tables[1]) & set(tables[0]), (tables, "no physical block reuse")
    # identical prompts with one slot ⇒ identical greedy continuations
    assert sched.finished[0].generated == sched.finished[1].generated


def test_scheduler_eos_retires_early(tiny_elite_cfg, tiny_elite_model):
    """Forcing eos_id to the model's first greedy token retires requests after
    one token and recycles their blocks for the queue."""
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    prompts = jax.random.randint(jax.random.PRNGKey(4), (1, 6), 0,
                                 cfg.vocab_size, jnp.int32)
    out, _ = serve_loop.generate(params, buffers, cfg, prompts, 1)
    eos = int(out[0, 0])
    scfg = serve_loop.SchedulerConfig(max_slots=1, block_size=4, num_blocks=8,
                                      max_len=16, prefill_bucket=8, eos_id=eos)
    sched = serve_loop.Scheduler(params, buffers, cfg, scfg)
    req = serve_loop.Request(uid=0, prompt=np.asarray(prompts[0]),
                             max_new_tokens=8)
    report = sched.run([req])
    assert report.completed == 1
    assert sched.finished[0].finish_reason == "eos"
    assert len(sched.finished[0].generated) == 1
    assert sched.pool.allocator.num_free == scfg.num_blocks


def test_paged_prefill_writes_only_real_tokens(tiny_elite_cfg, tiny_elite_model):
    """Prompt padding lands on the sentinel slot and is dropped — pages
    outside the sequence's chain stay zero."""
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    pool = PagedKVPool(cfg, num_blocks=8, block_size=4)
    sp, pad = 5, 8
    pool.ensure_capacity(0, sp)
    tokens = np.zeros((1, pad), np.int32)
    tokens[0, :sp] = np.arange(sp) % cfg.vocab_size
    sm = pool.prefill_slot_mapping(0, 0, sp, pad)[None]
    _, pages = lm.apply_prefill_paged(params, buffers, cfg,
                                      {"tokens": jnp.asarray(tokens)},
                                      pool.pages, jnp.asarray(sm))
    owned = set()
    for blk in pool.block_table(0):
        owned.update(range(blk * 4, blk * 4 + 4))
    k_e = np.asarray(pages["p0"]["k_e"][0])     # layer 0 stream [n_slots,...]
    unowned = np.setdiff1d(np.arange(k_e.shape[0]), sorted(owned))
    assert np.all(k_e[unowned] == 0.0)
    # the sp real tokens did land
    live = pool.slot_mapping([0] * sp, list(range(sp)))
    assert np.all(np.abs(k_e[live]).max(axis=1) > 0)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_pool_k_e_is_flat_rows(tiny_elite_cfg, tiny_elite_model, dtype):
    """The pool keeps ``k_e`` as flat rows ``[n_super, n_slots, nkv·2r]``:
    a written slot's row is that token's rotated elite key with kv head
    ``h`` in columns ``[h·2r, (h+1)·2r)``.  An int8 pool stores exactly the
    codes and scales of quantizing the token's ``[nkv, 2r]`` key."""
    from repro.core import quant, rope as rope_lib
    from repro.models.layers import rmsnorm
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    nkv, r2 = cfg.n_kv_heads, 2 * cfg.elitekv.elite_r
    bs, sp = 4, 6

    def prefill(pool_dtype):
        pool = PagedKVPool(cfg, num_blocks=8, block_size=bs, dtype=pool_dtype)
        assert pool.pages["p0"]["k_e"].shape == (
            cfg.num_layers // cfg.block_period, 8 * bs, nkv * r2)
        pool.ensure_capacity(0, sp)
        tokens = (np.arange(sp, dtype=np.int32) * 7 + 3) % cfg.vocab_size
        sm = pool.prefill_slot_mapping(0, 0, sp, sp)[None]
        _, pages = lm.apply_prefill_paged(params, buffers, cfg,
                                          {"tokens": jnp.asarray(tokens[None])},
                                          pool.pages, jnp.asarray(sm))
        return pages["p0"], sm[0], tokens

    pages, slots, tokens = prefill("float32")
    # layer 0's rotated elite keys, recomputed outside the pool
    h = params["embed"]["table"][jnp.asarray(tokens)[None]].astype(cfg.dtype)
    p0 = jax.tree.map(lambda t: t[0], params["blocks"]["p0"])
    b0 = jax.tree.map(lambda t: t[0], buffers["blocks"]["p0"])
    hn = rmsnorm(p0["attn_norm"], h, cfg.norm_eps)
    k_e = jnp.einsum("bsd,dhe->bshe", hn, p0["attn"]["wk_e"])
    k_e = np.asarray(rope_lib.apply_elite_rope(k_e, jnp.arange(sp),
                                               b0["elite_freqs"]))[0]
    rows = np.asarray(pages["k_e"][0])[slots]                 # [sp, nkv·2r]
    for kv in range(nkv):
        np.testing.assert_allclose(rows[:, kv * r2:(kv + 1) * r2], k_e[:, kv],
                                   atol=1e-5)
    if dtype == "int8":
        q_pages, _, _ = prefill("int8")
        # jitted, as in the pool write (eager division may round differently)
        want_q, want_s = jax.jit(quant.quantize_rows)(
            jnp.asarray(rows).reshape(sp, nkv, r2))
        got_q = np.asarray(q_pages["k_e"][0])[slots]
        got_s = np.asarray(q_pages["k_e_scale"][0])[slots]
        assert got_q.dtype == np.int8
        np.testing.assert_array_equal(got_q, np.asarray(want_q).reshape(sp, -1))
        np.testing.assert_array_equal(got_s, np.asarray(want_s))
