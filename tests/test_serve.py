"""Serving loop: greedy generation correctness + cache accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import make_inputs
from repro.core.cache import (attn_cache_floats_per_token, cache_ratio,
                              measured_cache_bytes, model_cache_floats_per_token)
from repro.models import lm
from repro.obs import MetricsRegistry
from repro.runtime import serve_loop


@pytest.mark.slow
def test_generate_matches_manual_greedy(tiny_elite_cfg, tiny_elite_model):
    params, buffers = tiny_elite_model
    cfg = tiny_elite_cfg
    B, Sp, new = 2, 10, 6
    prompts = jax.random.randint(jax.random.PRNGKey(3), (B, Sp), 0,
                                 cfg.vocab_size, jnp.int32)
    out, stats = serve_loop.generate(params, buffers, cfg, prompts, new)
    assert out.shape == (B, new)
    assert stats.decoded_tokens == B * new

    # manual reference: rerun full forward over prompt+generated prefix
    toks = prompts
    for t in range(new):
        logits, _ = lm.apply_train(params, buffers, cfg, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(nxt), out[:, t])
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)


def test_cache_bytes_elite_vs_baseline(tiny_cfg, tiny_elite_cfg):
    """Measured cache ratio == the paper's 2rn_kv + d_ckv formula."""
    B, L = 2, 16
    base_cache = lm.init_cache(tiny_cfg, B, L, dtype=jnp.bfloat16)
    elite_cache = lm.init_cache(tiny_elite_cfg, B, L, dtype=jnp.bfloat16)
    mb = measured_cache_bytes(base_cache, B, L)
    me = measured_cache_bytes(elite_cache, B, L)
    want = cache_ratio(tiny_elite_cfg, tiny_cfg)
    got = me["attn_bytes"] / mb["attn_bytes"]
    assert got == pytest.approx(want, rel=1e-6)
    # and the formula itself
    e = tiny_elite_cfg.elitekv
    assert attn_cache_floats_per_token(tiny_elite_cfg) == \
        2 * e.elite_r * tiny_elite_cfg.n_kv_heads + e.d_ckv


def test_serve_driver_runs(capsys):
    from repro.launch import serve
    serve.main(["--arch", "tinyllama_1_1b", "--reduced", "--elitekv",
                "--batch", "2", "--prompt-len", "8", "--new-tokens", "4"])
    out = capsys.readouterr().out
    assert "ratio" in out


def test_train_driver_runs(capsys):
    from repro.launch import train as train_mod
    hist = train_mod.main(["--arch", "tinyllama_1_1b", "--reduced", "--steps", "3",
                           "--batch", "2", "--seq", "32", "--log-every", "1"])
    assert len(hist) >= 1


# ---------------------------------------------------------------------------
# per-step phase breakdown (docs/observability.md)
# ---------------------------------------------------------------------------

def _stream_reqs(cfg):
    rng = np.random.default_rng(9)
    return [serve_loop.Request(
        uid=i,
        prompt=rng.integers(0, cfg.vocab_size,
                            int(rng.integers(6, 14))).astype(np.int32),
        max_new_tokens=6, arrival=i * 0.5) for i in range(3)]


def _stream_scfg(prefill_chunk_tokens=4, **kw):
    return serve_loop.SchedulerConfig(
        max_slots=2, block_size=4, num_blocks=64, max_len=32,
        prefill_bucket=4, prefill_chunk_tokens=prefill_chunk_tokens, **kw)


def _stream_run(params, buffers, cfg, speculate=0):
    sched = serve_loop.Scheduler(params, buffers, cfg,
                                 _stream_scfg(speculate_k=speculate))
    return sched.run(_stream_reqs(cfg))


def test_phase_breakdown_plain_decode(tiny_elite_cfg, tiny_elite_model):
    """Plain decode: phase keys are exactly PHASES, the phases that ran are
    positive, speculative phases are exactly zero, and the self times sum to
    the measured step wall time (the step's own remainder is "other")."""
    rep = _stream_run(*tiny_elite_model, tiny_elite_cfg)
    assert set(rep.phase_ms) == set(serve_loop.PHASES)
    for phase in ("prefill", "decode", "sample", "admit", "grow", "pack",
                  "other"):
        assert rep.phase_ms[phase] > 0.0, phase
    for phase in ("draft", "verify", "accept"):
        assert rep.phase_ms[phase] == 0.0, phase   # never ran ⇒ exactly 0
    assert rep.phase_ms["swap"] == 0.0             # ample pool: no eviction
    total = rep.step_wall_ms_total
    assert total > 0.0
    assert abs(sum(rep.phase_ms.values()) - total) <= 0.02 * total + 1.0
    assert rep.phase_ms["other"] >= 0.0            # residual never negative
    table = rep.phase_table()
    assert "decode=" in table and "draft=" not in table


def test_sample_tokens_temp0_is_exact_argmax():
    """``temps[i] <= 0`` must take the argmax path STRUCTURALLY: a greedy
    lane in a mixed batch never routes through the temperature division, so
    its token is bitwise argmax — not softmax-at-clamped-temperature — even
    when adjacent logits differ by less than the 1e-6 clamp would resolve."""
    rng = np.random.default_rng(0)
    B, V = 6, 64
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32))
    # near-ties: a clamped-temperature softmax draw could pick either one
    logits = logits.at[:, 1].set(logits[:, 0] + 1e-7)
    temps = jnp.asarray([0.0, 0.8, -1.0, 0.0, 1.3, 0.0], jnp.float32)
    top_ps = jnp.full((B,), 0.9, jnp.float32)
    seeds = jnp.arange(B, dtype=jnp.int32)
    counts = jnp.arange(B, dtype=jnp.int32)
    got = np.asarray(serve_loop.sample_tokens(logits, temps, top_ps,
                                              seeds, counts))
    want = np.asarray(jnp.argmax(logits, axis=-1))
    greedy = np.asarray(temps) <= 0.0
    np.testing.assert_array_equal(got[greedy], want[greedy])
    # and an all-greedy batch is the full argmax vector
    got_all = np.asarray(serve_loop.sample_tokens(
        logits, jnp.zeros((B,), jnp.float32), top_ps, seeds, counts))
    np.testing.assert_array_equal(got_all, want)


def test_phase_breakdown_speculative(tiny_elite_cfg, tiny_elite_model):
    """Speculative decode routes steps through draft/verify/accept instead
    of the plain decode phase; the sum invariant must still hold."""
    rep = _stream_run(*tiny_elite_model, tiny_elite_cfg, speculate=2)
    for phase in ("draft", "verify", "accept", "grow", "pack"):
        assert rep.phase_ms[phase] > 0.0, phase
    assert rep.phase_ms["decode"] == 0.0           # no plain decode steps ran
    total = rep.step_wall_ms_total
    assert abs(sum(rep.phase_ms.values()) - total) <= 0.02 * total + 1.0


def _assert_phases_sum_to_wall(phase_ms, wall_ms):
    assert wall_ms > 0.0
    assert abs(sum(phase_ms.values()) - wall_ms) <= 0.02 * wall_ms


def test_step_driven_directly_times_itself(tiny_elite_cfg, tiny_elite_model):
    """A caller that drives ``step()`` itself (no ``run()``), as the serving
    benchmark's harness does, still gets the step wall and the phase self times, in
    the report and in the registry, and they agree."""
    params, buffers = tiny_elite_model
    reg = MetricsRegistry()
    sched = serve_loop.Scheduler(params, buffers, tiny_elite_cfg,
                                 _stream_scfg(), metrics=reg)
    for r in _stream_reqs(tiny_elite_cfg):
        sched.submit(r)
    calls = 1
    while sched.step():
        calls += 1
    rep = sched.report(1.0)
    _assert_phases_sum_to_wall(rep.phase_ms, rep.step_wall_ms_total)
    assert rep.phase_ms["other"] > 0.0
    assert reg.get("serve_steps_total").value == calls
    assert reg.get("serve_step_wall_ms_total").value == pytest.approx(
        rep.step_wall_ms_total)
    for p in serve_loop.PHASES:
        assert reg.get(f"serve_phase_{p}_ms_total").value == pytest.approx(
            rep.phase_ms[p]), p


@pytest.mark.parametrize("chunk", [4, 0], ids=["chunked", "oneshot"])
def test_prefill_call_counters(tiny_elite_cfg, tiny_elite_model, chunk):
    """Every prefill call counts once, with the token rows it ran at:
    lanes x chunk for a chunked call, the padded bucket for a one-shot
    call; live tokens never exceed them."""
    params, buffers = tiny_elite_model
    reg = MetricsRegistry()
    scfg = _stream_scfg(prefill_chunk_tokens=chunk)
    sched = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, scfg,
                                 metrics=reg)
    reqs = _stream_reqs(tiny_elite_cfg)
    rep = sched.run(reqs)
    calls = reg.get("serve_prefill_calls_total").value
    capacity = reg.get("serve_prefill_capacity_tokens_total").value
    live = reg.get("serve_prefill_tokens_total").value
    assert calls == rep.prefill_chunks > 0
    if chunk:
        assert capacity == calls * scfg.chunk_lanes * chunk
    else:
        assert capacity == sum(-(-len(r.prompt) // scfg.prefill_bucket)
                               * scfg.prefill_bucket for r in reqs)
    assert live == sum(len(r.prompt) for r in reqs) <= capacity


def test_decode_page_counters(tiny_elite_cfg, tiny_elite_model):
    """The decode page counters follow the lanes' lengths of every decode
    call: pages walked = Σ cdiv(len, P·bs)·P (live groups rounded up to P),
    table pages = Σ B·max_blocks; their ratio is the share of the table
    the grouped walk touches.  Prompts of 250–258 tokens put lanes in one
    group and then two as they decode."""
    from repro.kernels.elite_decode import pages_per_step
    params, buffers = tiny_elite_model
    reg = MetricsRegistry()
    scfg = serve_loop.SchedulerConfig(
        max_slots=2, block_size=4, num_blocks=160, max_len=512,
        prefill_bucket=64, prefill_chunk_tokens=64)
    sched = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, scfg,
                                 metrics=reg)
    calls = []
    decode = sched._decode

    def recording(*args):
        calls.append(np.asarray(args[-1]))          # the lanes' lengths
        return decode(*args)

    sched._decode = recording
    rng = np.random.default_rng(3)
    sched.run([serve_loop.Request(
        uid=i, prompt=rng.integers(0, tiny_elite_cfg.vocab_size,
                                   250 + 8 * i).astype(np.int32),
        max_new_tokens=6, arrival=0.0) for i in range(2)])
    mb, bs = scfg.max_blocks_per_seq, scfg.block_size
    P = pages_per_step(bs, mb)
    assert (mb, P) == (128, 64)
    groups = [-(-ln // (P * bs)) for ln in calls]
    assert {1, 2} <= {int(g) for ln, g in zip(calls, groups)
                      for g in g[ln > 0]}
    walked = sum(int(np.sum(g)) * P for g in groups)
    assert reg.get("serve_decode_pages_walked_total").value == walked
    assert (reg.get("serve_decode_table_pages_total").value
            == len(calls) * scfg.max_slots * mb)


def test_router_replicas_time_their_own_steps(tiny_elite_cfg,
                                              tiny_elite_model):
    """The router drives each replica's ``step()`` with no accounting of
    its own; each replica's phase self times still sum to its step wall,
    and the shared registry holds the fleet's totals."""
    from repro.runtime.router import Router
    params, buffers = tiny_elite_model
    reg = MetricsRegistry()
    router = Router(params, buffers, tiny_elite_cfg, _stream_scfg(),
                    num_replicas=2, metrics=reg)
    router.run(_stream_reqs(tiny_elite_cfg))
    reps = [r.report(1.0) for r in router.replicas]
    for rep in reps:
        _assert_phases_sum_to_wall(rep.phase_ms, rep.step_wall_ms_total)
    assert reg.get("serve_step_wall_ms_total").value == pytest.approx(
        sum(rep.step_wall_ms_total for rep in reps))
