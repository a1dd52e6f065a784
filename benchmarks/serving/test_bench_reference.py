"""The plain reference agrees with the serving program at a small size on
the CPU: chunked paged prefill (fresh and resumed chunks, a prefix-cache
hit), then paged decode, through ``Scheduler``; every decode step's logits
and every served token are compared."""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import harness  # noqa: E402
from servbench.models import dense_elitekv as dense  # noqa: E402

CONF = dict(model="dense_elitekv",
            arch="tiny", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=2, vocab_size=300, rope_theta=10000.0,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            elitekv=dict(elite_r=2, d_ckv=16, lrd="joint"),
            dtype="float32", pool_dtype="float32", block_size=16,
            num_blocks=64)
MIX = {"loop": "closed", "scheduler": dict(
    max_slots=3, max_len=128, prefill_chunk_tokens=32, prefill_batch_lanes=2,
    prefix_cache=True, eviction="recompute")}


def _reference_logits(m, params, buffers, seq, max_len):
    pad = np.zeros((max_len,), np.int32)
    pad[:len(seq)] = seq
    h = dense.hidden(m, False, params, buffers, jnp.asarray(pad))
    w = dense.head(m, params)
    return np.asarray(jnp.dot(h, w, precision="highest"))


@pytest.mark.parametrize("tied", [False, True])
def test_scheduler_matches_reference(tied):
    from repro.runtime.serve_loop import Request, Scheduler
    conf = dict(CONF, tie_word_embeddings=tied)
    m = dense.dims(conf)
    params, buffers = dense.make(m, 12345)
    sched = Scheduler(params, buffers, dense.program_config(conf),
                      harness.scheduler_config(conf, MIX))
    seen = []                    # (uid, position, logits row) per decode lane
    decode = sched._decode

    def recording_decode(*args):
        logits, pages = decode(*args)
        lengths = np.asarray(args[-1])
        for lane, n in enumerate(lengths):
            if n > 0:
                seen.append((sched.slots[lane].uid, int(n) - 1,
                             np.asarray(logits[lane, -1])))
        return logits, pages

    sched._decode = recording_decode
    rng = np.random.default_rng(0)
    shared = rng.integers(0, m.vocab, 70).astype(np.int32)
    prompts = [shared, np.concatenate([shared, rng.integers(
        0, m.vocab, 9).astype(np.int32)]), rng.integers(
        0, m.vocab, 41).astype(np.int32)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    sched.submit(reqs[0])
    while not sched.finished:
        sched.step()
    for r in reqs[1:]:
        sched.submit(r)
    while sched.step():
        pass
    assert sched.bm.prefix.hits >= 1          # request 1 reused request 0's
    assert sched.prefill_chunks >= 4          # fresh and resumed chunks

    ref = {r.uid: _reference_logits(
        m, params, buffers, np.concatenate([r.prompt, r.generated[:-1]]),
        MIX["scheduler"]["max_len"]) for r in reqs}
    assert len(seen) >= 30
    for uid, pos, row in seen:
        want = ref[uid][pos, :m.vocab]
        np.testing.assert_allclose(row[:m.vocab], want, atol=2e-4, rtol=0)
    gaps = dense.served_gaps(m, params, buffers,
                                 [(r.prompt, r.generated) for r in reqs],
                                 MIX["scheduler"]["max_len"])
    assert max(g.max() for g in gaps) == 0.0


def test_reference_is_causal_and_blocked():
    """Padding after the sequence changes no row, and the query blocks agree
    with one block over the whole sequence."""
    m = dense.dims(CONF)
    params, buffers = dense.make(m, 7)
    seq = np.random.default_rng(1).integers(0, m.vocab, 50).astype(np.int32)
    a = _reference_logits(m, params, buffers, seq, 64)[:50]
    b = _reference_logits(m, params, buffers, seq, 128)[:50]
    np.testing.assert_allclose(a, b, atol=1e-5)
    old = dense.Q_BLOCK
    try:
        dense.Q_BLOCK = 16                   # a new shape: traced anew
        c = _reference_logits(m, params, buffers, seq, 96)[:50]
    finally:
        dense.Q_BLOCK = old
    np.testing.assert_allclose(a, c, atol=1e-5)


def test_weights_deterministic_for_large_seeds():
    m = dense.dims(CONF)
    a, _ = dense.make(m, 2 ** 40 + 3)
    b, _ = dense.make(m, 2 ** 40 + 3)
    c, _ = dense.make(m, 3)
    wa = np.asarray(a["blocks"]["p0"]["attn"]["wq"])
    assert np.array_equal(wa, np.asarray(b["blocks"]["p0"]["attn"]["wq"]))
    assert not np.array_equal(wa, np.asarray(c["blocks"]["p0"]["attn"]["wq"]))
