"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything (CSV)
    PYTHONPATH=src python -m benchmarks.run table1     # one table

Paper-scale experiments (LLaMA2-7B, RefinedWeb, lm-eval) are out of reach on
one CPU core; every benchmark reproduces the corresponding table's *mechanism*
at miniature scale with held-out synthetic perplexity as the metric, and the
orderings the paper reports are asserted in the derived column.

Output rows: ``name,us_per_call,derived``.

``serving`` additionally writes ``BENCH_serving.json`` at the repo root —
one structured row per scenario (throughput, TTFT percentiles, occupancy,
acceptance, phase breakdown) for machine consumption; docs/observability.md
documents the schema.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.configs.base import EliteKVConfig
from repro.core import convert, lrd, ropelite
from repro.core.cache import cache_ratio, model_cache_floats_per_token
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import compile_cache
from repro.models import lm
from repro.runtime import train_loop

ROWS = []


def emit(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# shared miniature setup
# ---------------------------------------------------------------------------

def _base_cfg():
    return get_config("llama2_7b").reduced(
        num_layers=2, d_model=96, n_heads=8, n_kv_heads=8, d_head=16,
        d_ff=256, vocab_size=256)


def _data(cfg, seed, batch=8, seq=48):
    return TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    batch_size=batch, seed=seed))


def _eval_ppl(params, buffers, cfg, seed=991, batches=3):
    d = _data(cfg, seed, batch=4)
    tot = 0.0
    for _ in range(batches):
        tot += float(lm.loss_fn(params, buffers, cfg, next(d))[0])
    return float(np.exp(tot / batches))


_PRETRAINED = {}


def pretrained(steps=240):
    if "m" not in _PRETRAINED:
        cfg = _base_cfg()
        params, buffers = lm.init(jax.random.PRNGKey(0), cfg)
        tc = train_loop.TrainConfig(lr=3e-3)
        params, _, _ = train_loop.train(params, buffers, cfg, tc,
                                        iter(_data(cfg, 0)), steps, log_every=0)
        _PRETRAINED["m"] = (params, buffers, cfg)
    return _PRETRAINED["m"]


def _uptrain(params, buffers, cfg, steps=120, lr=1e-3, data=None):
    tc = train_loop.TrainConfig(lr=lr)
    params, _, _ = train_loop.train(params, buffers, cfg, tc,
                                    data or iter(_data(cfg, 1)), steps,
                                    log_every=0)
    return params


def _elite_at_ratio(params, buffers, cfg, ratio, method="greedy",
                    lrd_kind="joint", r=None):
    full = 2 * cfg.n_kv_heads * cfg.head_dim
    budget = int(ratio * full)
    if r is None:
        r = max(1, min(budget // (4 * cfg.n_kv_heads), cfg.head_dim // 2 - 1))
    rest = max(8, budget - 2 * r * cfg.n_kv_heads)
    ek = EliteKVConfig(enabled=True, elite_r=r, d_ckv=rest, lrd=lrd_kind,
                       d_ck=max(4, rest // 2), d_cv=max(4, rest - rest // 2))
    calib = next(_data(cfg, 77, batch=2))
    return convert.elitekv_from_baseline(
        params, buffers, cfg, {"tokens": calib["tokens"]}, ek, method=method)


# ---------------------------------------------------------------------------
# paper Table 1: EliteKV vs GQA across cache ratios
# ---------------------------------------------------------------------------

def table1():
    params, buffers, cfg = pretrained()
    base_ppl = _eval_ppl(params, buffers, cfg)
    emit("table1/baseline", 0, f"ppl={base_ppl:.2f};cache=1.000")
    for ratio, n_kv in [(0.5, 4), (0.25, 2), (0.125, 1)]:
        t0 = time.time()
        # GQA mean-pool baseline (Ainslie) at the same cache ratio
        gp, gcfg = convert.to_gqa(params, cfg, n_kv)
        gp = _uptrain(gp, buffers, gcfg)
        gqa_ppl = _eval_ppl(gp, buffers, gcfg)
        # EliteKV at the same ratio
        ep, eb, ecfg = _elite_at_ratio(params, buffers, cfg, ratio)
        ep = _uptrain(ep, eb, ecfg)
        e_ppl = _eval_ppl(ep, eb, ecfg)
        win = "elitekv" if e_ppl <= gqa_ppl else "gqa"
        emit(f"table1/ratio_{ratio}", (time.time() - t0) * 1e6,
             f"gqa_ppl={gqa_ppl:.2f};elitekv_ppl={e_ppl:.2f};"
             f"ratio={cache_ratio(ecfg, cfg):.3f};winner={win}")


# ---------------------------------------------------------------------------
# paper Table 2: Uniform vs Contribution vs RoPElite
# ---------------------------------------------------------------------------

def table2():
    params, buffers, cfg = pretrained()
    for r in (4, 2):
        res = {}
        t0 = time.time()
        for method in ("uniform", "contribution", "greedy"):
            ep, eb, ecfg = _elite_at_ratio(params, buffers, cfg, 0.5,
                                           method=method, r=r)
            ep = _uptrain(ep, eb, ecfg, steps=80)
            res[method] = _eval_ppl(ep, eb, ecfg)
        order = sorted(res, key=res.get)
        emit(f"table2/r_{r}", (time.time() - t0) * 1e6,
             ";".join(f"{m}={res[m]:.2f}" for m in res) + f";best={order[0]}")


# ---------------------------------------------------------------------------
# paper Fig. 5: S-LRD vs J-LRD at matched cache size
# ---------------------------------------------------------------------------

def fig5():
    params, buffers, cfg = pretrained()
    for ratio in (0.5, 0.25):
        t0 = time.time()
        ppls = {}
        for kind in ("joint", "separate"):
            ep, eb, ecfg = _elite_at_ratio(params, buffers, cfg, ratio,
                                           lrd_kind=kind)
            ppls[kind] = _eval_ppl(ep, eb, ecfg)   # conversion ppl, no uptrain
        emit(f"fig5/ratio_{ratio}", (time.time() - t0) * 1e6,
             f"jlrd_ppl={ppls['joint']:.2f};slrd_ppl={ppls['separate']:.2f};"
             f"jlrd_wins={ppls['joint'] <= ppls['separate']}")


# ---------------------------------------------------------------------------
# paper Fig. 6: recovery speed vs cache ratio
# ---------------------------------------------------------------------------

def fig6():
    params, buffers, cfg = pretrained()
    base_ppl = _eval_ppl(params, buffers, cfg)
    for ratio in (0.5, 0.25, 0.125):
        ep, eb, ecfg = _elite_at_ratio(params, buffers, cfg, ratio)
        curve = [_eval_ppl(ep, eb, ecfg)]
        t0 = time.time()
        stream = iter(_data(ecfg, 1))   # ONE continuing stream across rounds
        for _ in range(3):
            ep = _uptrain(ep, eb, ecfg, steps=40, data=stream)
            curve.append(_eval_ppl(ep, eb, ecfg))
        emit(f"fig6/ratio_{ratio}", (time.time() - t0) * 1e6,
             "curve=" + "|".join(f"{p:.2f}" for p in curve)
             + f";base={base_ppl:.2f}")


# ---------------------------------------------------------------------------
# kernel micro-bench (interpret-mode correctness + XLA-path wall time on CPU)
# ---------------------------------------------------------------------------

def kernels():
    from repro.kernels import ref as kref
    key = jax.random.PRNGKey(0)
    B, nkv, G, r2, dc, S = 4, 4, 4, 16, 128, 1024
    nh = nkv * G
    ks = jax.random.split(key, 4)
    q_e = jax.random.normal(ks[0], (B, nh, r2), jnp.float32)
    q_lat = jax.random.normal(ks[1], (B, nh, dc), jnp.float32)
    k_e = jax.random.normal(ks[2], (B, S, nkv, r2), jnp.float32)
    c = jax.random.normal(ks[3], (B, S, dc), jnp.float32)
    lengths = jnp.full((B,), S, jnp.int32)

    f_ref = jax.jit(lambda *a: kref.elite_decode_ref(*a, q_group=G, scale=0.1))
    f_ref(q_e, q_lat, k_e, c, c, lengths).block_until_ready()
    t0 = time.time()
    for _ in range(10):
        f_ref(q_e, q_lat, k_e, c, c, lengths).block_until_ready()
    us = (time.time() - t0) / 10 * 1e6
    # bytes actually read per call from the compressed cache:
    comp_bytes = B * S * (nkv * r2 + dc) * 4
    # what an UNcompressed GQA cache read would have been (dh=32, k+v):
    full_bytes = B * S * (2 * nkv * 32) * 4 * 4
    emit("kernels/elite_decode_xla", us,
         f"cache_bytes={comp_bytes};baseline_bytes={full_bytes};"
         f"hbm_read_ratio={comp_bytes / full_bytes:.3f}")

    # baseline full-KV decode attention for wall-clock comparison (CPU)
    dh = 32
    kf = jax.random.normal(ks[2], (B, S, nkv, dh), jnp.float32)
    vf = jax.random.normal(ks[3], (B, S, nkv, dh), jnp.float32)
    qf = jax.random.normal(ks[0], (B, 1, nh, dh), jnp.float32)
    from repro.models.attention import _attend
    f_base = jax.jit(lambda q, k, v: _attend(q, k, v, G, 0.1, q_offset=S - 1))
    f_base(qf, kf, vf).block_until_ready()
    t0 = time.time()
    for _ in range(10):
        f_base(qf, kf, vf).block_until_ready()
    emit("kernels/baseline_decode_xla", (time.time() - t0) / 10 * 1e6,
         "full_kv_read")

    # pallas interpret-mode correctness spot check (slow — 1 call)
    from repro.kernels import elite_decode as ed
    t0 = time.time()
    o_k = ed.elite_decode(q_e[:1], q_lat[:1], k_e[:1, :128], c[:1, :128],
                          c[:1, :128], jnp.array([128], jnp.int32), G, 0.1,
                          block_s=64, interpret=True)
    o_r = kref.elite_decode_ref(q_e[:1], q_lat[:1], k_e[:1, :128], c[:1, :128],
                                c[:1, :128], jnp.array([128], jnp.int32), G, 0.1)
    err = float(jnp.max(jnp.abs(o_k - o_r)))
    emit("kernels/elite_decode_pallas_interpret", (time.time() - t0) * 1e6,
         f"max_err_vs_oracle={err:.2e}")


# ---------------------------------------------------------------------------
# cache accounting across the assigned architectures
# ---------------------------------------------------------------------------

def cache_table():
    for arch in list_archs():
        cfg = get_config(arch)
        if cfg.n_attn_layers == 0:
            emit(f"cache/{arch}", 0, "inapplicable=ssm_no_kv_cache")
            continue
        ek = convert.pick_dims(cfg, 0.25)
        ecfg = dataclasses.replace(cfg, elitekv=ek)
        full = model_cache_floats_per_token(cfg)
        comp = model_cache_floats_per_token(ecfg)
        emit(f"cache/{arch}", 0,
             f"r={ek.elite_r};d_ckv={ek.d_ckv};floats_tok={comp};"
             f"baseline={full};ratio={comp / full:.3f};"
             f"bytes_32k_ctx={comp * 2 * 32768 / 2**20:.0f}MiB")


# ---------------------------------------------------------------------------
# serving: continuous batching over the paged pool (the systems trajectory —
# measures request throughput, not lockstep decode)
# ---------------------------------------------------------------------------

def serving_workload(rate: float, vocab_size: int = 128, n: int = 12,
                     seed: int = 7, sample_seed: int = 1000,
                     temperature: float = 0.0, top_p: float = 1.0):
    """Deterministic serving workload: bimodal prompt lengths (short
    interactive requests racing long ones — the case chunked prefill exists
    for), Poisson arrivals, and **pinned per-request sample seeds**
    (``sample_seed + uid``) so every comparison row — chunked vs one-shot,
    watermark vs preempt, speculative vs plain — decodes the *identical*
    request set and is token-comparable.  Two calls with the same arguments
    return identical requests (regression-tested)."""
    from repro.runtime import serve_loop
    rng = np.random.default_rng(seed)
    t, reqs = 0.0, []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        sp = int(rng.integers(4, 9)) if i % 2 else int(rng.integers(24, 41))
        reqs.append(serve_loop.Request(
            uid=i,
            prompt=rng.integers(0, vocab_size, sp).astype(np.int32),
            max_new_tokens=int(rng.integers(4, 17)), arrival=t,
            temperature=temperature, top_p=top_p, seed=sample_seed + i))
    return reqs


def shared_prefix_workload(vocab_size: int = 128, n: int = 10,
                           shared: int = 64, max_suffix: int = 4,
                           seed: int = 17, sample_seed: int = 2000,
                           temperature: float = 0.0):
    """Template traffic: 90% of the requests share a ``shared``-token system
    prefix (the miniature stand-in for the 512-token system prompts of real
    template-heavy serving) followed by a short unique suffix; one request
    (uid 5) carries an unrelated prompt and must miss.  Request 0 arrives
    alone and warms the cache; the rest arrive after its prefill has
    registered the prefix blocks.  Deterministic: two calls with the same
    arguments return identical requests."""
    from repro.runtime import serve_loop
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, vocab_size, shared).astype(np.int32)
    reqs = []
    for i in range(n):
        if i == 5:                         # the 10% non-sharer
            prompt = rng.integers(0, vocab_size, 8).astype(np.int32)
        else:
            sfx = rng.integers(0, vocab_size,
                               int(rng.integers(2, max_suffix + 1))
                               ).astype(np.int32)
            prompt = np.concatenate([pre, sfx])
        reqs.append(serve_loop.Request(
            uid=i, prompt=prompt,
            max_new_tokens=int(rng.integers(4, 17)),
            arrival=0.0 if i == 0 else float(12 + 2 * i),
            temperature=temperature, top_p=0.9, seed=sample_seed + i))
    return reqs


#: Structured serving rows accumulated by ``serving()`` and written to
#: ``BENCH_serving.json`` at the repo root (schema in docs/observability.md).
#: v2: rows carry ``pool_dtype``/``pool_bytes_per_token``, plus the
#: ``pool_capacity_*`` quantization scenario pair.
#: v3: multi-device ``sharded_dev*`` scaling rows — device_count/tp/dp,
#: per-replica occupancy, pool bytes/token/device, and an asserted
#: ``tokens_match_single_device`` (the sharded path is bit-preserving).
#: v4: long-context sparse decode rows (``longctx_dense`` /
#: ``longctx_sparse_k*``) at ~8x the context of every other row —
#: ``context_tokens``, steady-state decode tok/s, ``sparse_decode_speedup``,
#: and an asserted teacher-forced ``top1_agreement_vs_dense`` >= 0.95 at
#: the benchmark's k.
SERVING_SCHEMA_VERSION = 4


def _serving_row(scenario: str, rep, us: float, **extra):
    """One machine-readable scenario row from a ``ServeReport``."""
    row = dict(
        scenario=scenario,
        us_per_step=round(us, 1),
        tok_s=round(rep.tok_per_s, 2),
        ttft_ms_p50=round(rep.ttft_wall_p50_ms, 2),
        ttft_ms_p95=round(rep.ttft_wall_p95_ms, 2),
        step_ms_p50=round(rep.step_ms_p50, 2),
        step_ms_p95=round(rep.step_ms_p95, 2),
        occupancy=round(rep.mean_occupancy, 4),
        occupancy_retained=round(rep.mean_occupancy_retained, 4),
        completed=rep.completed,
        decode_steps=rep.decode_steps,
        decoded_tokens=rep.decoded_tokens,
        prefill_chunks=rep.prefill_chunks,
        preemptions=rep.preemptions,
        swap_outs=rep.swap_outs,
        blocks_high_water=rep.pool_high_water_blocks,
        blocks_naive=rep.naive_blocks,
        block_reuse=round(rep.block_reuse_ratio, 3),
        acceptance=round(rep.acceptance_rate, 4),
        tokens_per_forward=round(rep.tokens_per_forward, 3),
        phase_ms={k: round(v, 2) for k, v in rep.phase_ms.items()},
        step_wall_ms_total=round(rep.step_wall_ms_total, 2),
        pool_dtype=rep.pool_dtype,
        pool_bytes_per_token=rep.pool_bytes_per_token,
    )
    row.update(extra)
    return row


def write_serving_json(rows, path=None) -> Path:
    """Write the ``BENCH_serving.json`` artifact (repo root by default)."""
    path = Path(path) if path else Path(__file__).resolve().parent.parent \
        / "BENCH_serving.json"
    path.write_text(json.dumps(
        {"benchmark": "serving", "schema_version": SERVING_SCHEMA_VERSION,
         "generated_by": "PYTHONPATH=src python -m benchmarks.run serving",
         "rows": rows}, indent=2) + "\n", encoding="utf-8")
    return path


def serving():
    from repro.runtime import serve_loop

    cfg = get_config("tinyllama_1_1b").reduced(num_layers=2, vocab_size=128)
    cfg = dataclasses.replace(
        cfg, elitekv=EliteKVConfig(enabled=True, elite_r=4, d_ckv=64))
    params, buffers = lm.init(jax.random.PRNGKey(0), cfg)
    json_rows = []

    def run_one(rate, chunk, num_blocks=96, admission="preempt",
                eviction="recompute", lanes=0, speculate=0, draft_rank=0):
        scfg = serve_loop.SchedulerConfig(
            max_slots=4, block_size=8, num_blocks=num_blocks,
            max_new_tokens=16, max_len=64, prefill_bucket=8,
            prefill_chunk_tokens=chunk, prefill_batch_lanes=lanes,
            admission=admission, eviction=eviction,
            speculate_k=speculate, draft_rank=draft_rank)
        sched = serve_loop.Scheduler(params, buffers, cfg, scfg)
        t0 = time.time()
        rep = sched.run(serving_workload(rate, vocab_size=cfg.vocab_size))
        us = (time.time() - t0) * 1e6 / max(rep.decode_steps, 1)
        return sched, rep, us

    plain_baseline = None                  # (sched, rep, us) of bursty/chunk8,
    for rate, tag in [(2.0, "bursty"), (0.4, "trickle")]:  # reused below
        for chunk in (0, 8):               # one-shot admission vs chunked
            sched, rep, us = run_one(rate, chunk)
            if (rate, chunk) == (2.0, 8):
                plain_baseline = (sched, rep, us)
            buckets = ";".join(f"ttft_prompt_{k}={v:.1f}"
                               for k, v in rep.ttft_steps_by_bucket.items())
            json_rows.append(_serving_row(
                f"poisson_{tag}_chunk{chunk}", rep, us,
                rate=rate, prefill_chunk=chunk))
            emit(f"serving/poisson_{tag}_chunk{chunk}", us,
                 f"tok_s={rep.tok_per_s:.1f};ttft_steps={rep.ttft_steps_mean:.1f};"
                 f"{buckets};prefill_chunks={rep.prefill_chunks};"
                 f"prefill_batch={rep.mean_prefill_batch:.2f};"
                 f"occupancy={rep.mean_occupancy:.2f};"
                 f"step_ms_p50={rep.step_ms_p50:.1f};step_ms_p95={rep.step_ms_p95:.1f};"
                 f"peak_slots={rep.peak_slots};"
                 f"blocks_hw={rep.pool_high_water_blocks};"
                 f"blocks_naive={rep.naive_blocks};"
                 f"reuse={rep.block_reuse_ratio:.2f};"
                 f"paged_beats_naive={rep.pool_high_water_blocks < rep.naive_blocks}")

    # watermark vs preempt at half the watermark-required capacity: the
    # reservation policy needs worst-case blocks for every concurrently
    # resident request (max_slots × ceil(max_len / block_size)); at 50% of
    # that it stalls admission (low occupancy, empty slots) while the
    # preempting policy fills the pool and completes the same request set
    # with identical tokens.
    wm_required = 4 * (-(-64 // 8))        # max_slots × blocks per worst case
    small = wm_required // 2
    results = {}
    for admission, eviction in [("watermark", "recompute"),
                                ("preempt", "recompute"), ("preempt", "swap")]:
        sched, rep, us = run_one(2.0, 8, num_blocks=small,
                                 admission=admission, eviction=eviction)
        results[(admission, eviction)] = {
            r.uid: list(r.generated) for r in sched.finished}
        json_rows.append(_serving_row(
            f"pool{small}_{admission}_{eviction}", rep, us,
            admission=admission, eviction=eviction, num_blocks=small,
            tokens_match_watermark=(results[(admission, eviction)]
                                    == results[("watermark", "recompute")])))
        emit(f"serving/pool{small}_{admission}_{eviction}", us,
             f"completed={rep.completed};occupancy={rep.mean_occupancy:.2f};"
             f"peak_slots={rep.peak_slots};preemptions={rep.preemptions};"
             f"preempted_requests={rep.preempted_requests};"
             f"swaps={rep.swap_outs};ttft_steps={rep.ttft_steps_mean:.1f};"
             f"prefill_batch={rep.mean_prefill_batch:.2f};"
             f"tokens_match_watermark="
             f"{results[(admission, eviction)] == results[('watermark', 'recompute')]}")

    # speculative vs plain decode on the identical seeded greedy workload:
    # plain advances 1 token per lane per forward; draft/verify advances
    # 1 + accepted.  The draft rank is a top-singular-direction truncation of
    # the joint factors — on this random-init miniature the spectrum is
    # nearly flat, so useful ranks sit close to d_ckv (64); a converted/
    # uptrained model concentrates energy in far fewer directions (the
    # paper's premise).  Greedy streams must be token-identical to plain.
    plain_sched, plain_rep, plain_us = plain_baseline   # bursty/chunk8 run
    plain_toks = {r.uid: list(r.generated) for r in plain_sched.finished}
    json_rows.append(_serving_row("spec_plain", plain_rep, plain_us,
                                  speculate_k=0))
    emit("serving/spec_plain", plain_us,
         f"tok_per_forward={plain_rep.tokens_per_forward:.2f};"
         f"decode_steps={plain_rep.decode_steps};"
         f"decoded={plain_rep.decoded_tokens}")
    for spec_k, rank in [(2, 0), (2, 60), (4, 60)]:
        sched, rep, us = run_one(2.0, 8, speculate=spec_k, draft_rank=rank)
        toks = {r.uid: list(r.generated) for r in sched.finished}
        buckets = ";".join(f"acc_prompt_{b}={v:.2f}"
                           for b, v in rep.acceptance_by_bucket.items())
        json_rows.append(_serving_row(
            f"spec_k{spec_k}_rank{rank or 'full'}", rep, us,
            speculate_k=spec_k, draft_rank=rank,
            tokens_match_plain=(toks == plain_toks)))
        emit(f"serving/spec_k{spec_k}_rank{rank or 'full'}", us,
             f"tok_per_forward={rep.tokens_per_forward:.2f};"
             f"acceptance={rep.acceptance_rate:.2f};"
             f"mean_accepted={rep.mean_accepted:.2f};{buckets};"
             f"verify_forwards={rep.decode_steps};"
             f"draft_forwards={rep.draft_forwards};"
             f"decoded={rep.decoded_tokens};"
             f"tokens_match_plain={toks == plain_toks}")

    # cross-request prefix caching on template traffic: 90% of requests share
    # a 64-token system prefix.  The cache-on run must emit the identical
    # token streams while serving the shared blocks from cache — hit rate
    # >= 0.8 and strictly lower mean TTFT than the cache-off row (both
    # asserted: the quantities are deterministic, arrivals are in steps).
    def run_shared(prefix_cache):
        scfg = serve_loop.SchedulerConfig(
            max_slots=4, block_size=8, num_blocks=96, max_new_tokens=16,
            max_len=96, prefill_bucket=8, prefill_chunk_tokens=8,
            prefix_cache=prefix_cache)
        sched = serve_loop.Scheduler(params, buffers, cfg, scfg)
        t0 = time.time()
        rep = sched.run(shared_prefix_workload(vocab_size=cfg.vocab_size))
        us = (time.time() - t0) * 1e6 / max(rep.decode_steps, 1)
        return sched, rep, us

    off_sched, off_rep, off_us = run_shared(False)
    on_sched, on_rep, on_us = run_shared(True)
    off_toks = {r.uid: list(r.generated) for r in off_sched.finished}
    on_toks = {r.uid: list(r.generated) for r in on_sched.finished}
    match = on_toks == off_toks
    ttft_win = on_rep.ttft_steps_mean < off_rep.ttft_steps_mean
    assert match, "prefix cache changed token streams"
    assert on_rep.prefix_cache_hit_rate >= 0.8, on_rep.prefix_cache_hit_rate
    assert ttft_win, (on_rep.ttft_steps_mean, off_rep.ttft_steps_mean)
    json_rows.append(_serving_row(
        "shared_prefix_off", off_rep, off_us, prefix_cache=False,
        shared_prefix=64, ttft_steps_mean=round(off_rep.ttft_steps_mean, 2)))
    json_rows.append(_serving_row(
        "shared_prefix_on", on_rep, on_us, prefix_cache=True,
        shared_prefix=64, ttft_steps_mean=round(on_rep.ttft_steps_mean, 2),
        hit_rate=round(on_rep.prefix_cache_hit_rate, 4),
        hit_tokens=on_rep.prefix_cache_hit_tokens,
        cow_copies=on_rep.cow_copies,
        blocks_retained=on_rep.blocks_retained,
        tokens_match_off=match, ttft_lower_than_off=ttft_win))
    emit("serving/shared_prefix_off", off_us,
         f"ttft_steps={off_rep.ttft_steps_mean:.1f};"
         f"prefill_chunks={off_rep.prefill_chunks};"
         f"blocks_hw={off_rep.pool_high_water_blocks}")
    emit("serving/shared_prefix_on", on_us,
         f"hit_rate={on_rep.prefix_cache_hit_rate:.2f};"
         f"hit_tokens={on_rep.prefix_cache_hit_tokens};"
         f"cow={on_rep.cow_copies};"
         f"ttft_steps={on_rep.ttft_steps_mean:.1f};"
         f"prefill_chunks={on_rep.prefill_chunks};"
         f"blocks_hw={on_rep.pool_high_water_blocks};"
         f"tokens_match_off={match};ttft_lower_than_off={ttft_win}")

    # int8 pool capacity vs quality: the same fixed greedy workload through a
    # f32 pool (block_size 16) and an int8 pool (block_size 64 — roughly the
    # same bytes per block, so peak *blocks* compare capacity honestly), then
    # teacher-forced per-position top-1 agreement and ppl delta between the
    # two pools over the f32 streams.  Quantization is the first serving
    # feature that cannot be token-identical, so its wall is a pinned
    # agreement threshold instead (tests/test_quant.py pins the same property
    # suite-side); both inequalities below are asserted, not just recorded.
    from repro.core.cache import PagedKVPool
    qparams, qbuffers = lm.init(jax.random.PRNGKey(7), cfg)
    B, P, new = 4, 16, 48
    prompts = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                                 cfg.vocab_size, jnp.int32)

    def run_pool(dtype, block_size, num_blocks):
        scfg = serve_loop.SchedulerConfig(
            max_slots=B, block_size=block_size, num_blocks=num_blocks,
            max_new_tokens=new, max_len=P + new + 1, cache_dtype=dtype)
        t0 = time.time()
        out, rep = serve_loop.generate_paged(qparams, qbuffers, cfg, prompts,
                                             new, scfg)
        us = (time.time() - t0) * 1e6 / max(rep.decode_steps, 1)
        return out, rep, us

    out_f, rep_f, us_f = run_pool(jnp.float32, 16, 24)
    _, rep_q, us_q = run_pool("int8", 64, 12)

    full = jnp.concatenate([prompts, jnp.asarray(out_f)], axis=1)
    n_tok = int(full.shape[1])

    def forced_logits(dtype, block_size):
        """Teacher-forced logits over the f32 streams: both pools score the
        IDENTICAL context, so agreement is per-position (no compounding of a
        single early argmax flip through every later token)."""
        pool = PagedKVPool(cfg, num_blocks=2 * B * (-(-n_tok // block_size)),
                           block_size=block_size, dtype=dtype)
        sms = []
        for b in range(B):
            pool.ensure_capacity(b, n_tok)
            sms.append(pool.prefill_slot_mapping(b, 0, n_tok, n_tok))
        logits, _ = lm.apply_prefill_paged(
            qparams, qbuffers, cfg, {"tokens": full}, pool.pages,
            jnp.asarray(np.stack(sms)))
        return np.asarray(logits, np.float32)[:, P - 1:n_tok - 1]

    l_f = forced_logits(jnp.float32, 16)
    l_q = forced_logits("int8", 64)
    top1_agreement = float((l_f.argmax(-1) == l_q.argmax(-1)).mean())
    targets = jnp.asarray(out_f)

    def forced_ppl(logits):
        logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return float(np.exp(float(nll.mean())))

    ppl_f, ppl_q = forced_ppl(l_f), forced_ppl(l_q)
    blocks_ratio = rep_q.pool_high_water_blocks / rep_f.pool_high_water_blocks
    assert top1_agreement >= 0.98, top1_agreement
    assert blocks_ratio <= 0.55, blocks_ratio
    json_rows.append(_serving_row(
        "pool_capacity_f32", rep_f, us_f, block_size=16,
        bytes_per_block=16 * rep_f.pool_bytes_per_token,
        forced_ppl=round(ppl_f, 4)))
    json_rows.append(_serving_row(
        "pool_capacity_int8", rep_q, us_q, block_size=64,
        bytes_per_block=64 * rep_q.pool_bytes_per_token,
        peak_blocks_ratio_vs_f32=round(blocks_ratio, 4),
        top1_agreement_vs_f32=round(top1_agreement, 4),
        forced_ppl=round(ppl_q, 4),
        ppl_delta_vs_f32=round(ppl_q - ppl_f, 4)))
    emit("serving/pool_capacity_f32", us_f,
         f"blocks_hw={rep_f.pool_high_water_blocks};block_size=16;"
         f"bytes_tok={rep_f.pool_bytes_per_token};ppl={ppl_f:.3f}")
    emit("serving/pool_capacity_int8", us_q,
         f"blocks_hw={rep_q.pool_high_water_blocks};block_size=64;"
         f"bytes_tok={rep_q.pool_bytes_per_token};"
         f"peak_blocks_ratio={blocks_ratio:.3f};"
         f"top1_agreement={top1_agreement:.4f};"
         f"ppl_delta={ppl_q - ppl_f:+.4f}")

    # long-context sparse decode: block top-k over the paged pool at ~8x
    # the context of every other serving row.  A random-init model has
    # near-uniform attention and vanishing argmax margins, so it cannot
    # separate "selection missed a block that mattered" from "the logits
    # were a coin flip anyway"; a short bigram pretrain (~15s) gives the
    # proxy model confident margins, which makes teacher-forced top-1
    # agreement a real recall signal instead of noise.
    LONGCTX_TOPK, LONGCTX_RECENT = 4, 2
    LONGCTX_AGREEMENT_MIN = 0.95

    def _markov(rng, n):
        out = np.empty(n, np.int32)
        t = int(rng.integers(cfg.vocab_size))
        for i in range(n):
            out[i] = t
            t = (5 * t + 3) % cfg.vocab_size
        return out

    class _MarkovData:
        def __init__(self, batch, seq, seed=0):
            self.rng = np.random.default_rng(seed)
            self.batch, self.seq = batch, seq

        def __iter__(self):
            return self

        def __next__(self):
            toks = np.stack([_markov(self.rng, self.seq + 1)
                             for _ in range(self.batch)])
            return {"tokens": jnp.asarray(toks[:, :-1]),
                    "labels": jnp.asarray(toks[:, 1:]),
                    "loss_mask": jnp.ones((self.batch, self.seq),
                                          jnp.float32)}

    lparams, lbuffers = lm.init(jax.random.PRNGKey(0), cfg)
    lparams, _, _ = train_loop.train(
        lparams, lbuffers, cfg, train_loop.TrainConfig(lr=1e-3),
        iter(_MarkovData(8, 64)), 300)

    B, P, new, bs = 2, 512, 16, 16
    lrng = np.random.default_rng(11)
    lprompts = jnp.asarray(np.stack([_markov(lrng, P) for _ in range(B)]))

    def run_ctx(topk):
        # partial-width sparse decode requires swap eviction (recompute
        # prefill cannot reproduce sparse-generated streams; the pool is
        # ample here so neither path actually evicts)
        scfg = serve_loop.SchedulerConfig(
            max_slots=B, block_size=bs, num_blocks=96, max_new_tokens=new,
            max_len=P + new + 1, cache_dtype=jnp.float32,
            sparse_topk_blocks=topk, sparse_recent_blocks=LONGCTX_RECENT,
            eviction="swap" if topk else "recompute")
        t0 = time.time()
        out, rep = serve_loop.generate_paged(lparams, lbuffers, cfg,
                                             lprompts, new, scfg)
        us = (time.time() - t0) * 1e6 / max(rep.decode_steps, 1)
        return out, rep, us

    out_ld, rep_ld, us_ld = run_ctx(0)
    _, rep_ls, us_ls = run_ctx(LONGCTX_TOPK)

    # teacher-forced recall harness: prefill the dense greedy stream once,
    # then score every 4th position with one dense and one sparse decode
    # forward over the FROZEN pool (pages are immutable jnp trees; each
    # forward's scattered copy is discarded), so agreement is per-position
    # with no compounding of an early flip through later tokens.
    lfull = jnp.concatenate([lprompts, jnp.asarray(out_ld)], axis=1)
    ln_tok = int(lfull.shape[1])
    lmb = -(-ln_tok // bs)
    lpool = PagedKVPool(cfg, num_blocks=2 * B * lmb, block_size=bs,
                        block_summaries=True)
    lsms = []
    for b in range(B):
        lpool.ensure_capacity(b, ln_tok)
        lsms.append(lpool.prefill_slot_mapping(b, 0, ln_tok, ln_tok))
    _, lpool.pages = lm.apply_prefill_paged(
        lparams, lbuffers, cfg, {"tokens": lfull}, lpool.pages,
        jnp.asarray(np.stack(lsms)))
    lpages = lpool.pages
    lbt = jnp.asarray(lpool.block_table_array(list(range(B)), lmb))

    def _forced(topk):
        def f(tok, sm, ln):
            logits, _ = lm.apply_decode_paged(
                lparams, lbuffers, cfg, {"tokens": tok}, lpages, sm, lbt,
                ln, block_size=bs, sparse_topk=topk,
                sparse_recent=LONGCTX_RECENT)
            return logits[:, -1, :]
        return jax.jit(f)

    f_dense, f_sparse = _forced(0), _forced(LONGCTX_TOPK)
    lanes = list(range(B))
    agree = total = 0
    for pos in range(P // 2 - 1, ln_tok - 1, 4):
        tok = lfull[:, pos][:, None]
        sm = jnp.asarray(lpool.slot_mapping(lanes, [pos] * B))
        ln = jnp.full((B,), pos + 1, jnp.int32)
        a_d = np.asarray(jnp.argmax(f_dense(tok, sm, ln), -1))
        a_s = np.asarray(jnp.argmax(f_sparse(tok, sm, ln), -1))
        agree += int((a_d == a_s).sum())
        total += B
    longctx_agreement = agree / total

    # steady-state decode step at full context, post-compile: this is the
    # O(context) vs O(k*block) comparison the sparse path exists for,
    # without prefill/compile wall time diluting it.
    ltok = lfull[:, -1][:, None]
    lsm = jnp.asarray(lpool.slot_mapping(lanes, [ln_tok - 1] * B))
    lln = jnp.full((B,), ln_tok, jnp.int32)

    def _steady(f, reps=20):
        f(ltok, lsm, lln).block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            f(ltok, lsm, lln).block_until_ready()
        return B * reps / (time.time() - t0)

    dense_tok_s = _steady(f_dense)
    sparse_tok_s = _steady(f_sparse)
    speedup = sparse_tok_s / dense_tok_s
    assert longctx_agreement >= LONGCTX_AGREEMENT_MIN, longctx_agreement
    assert sparse_tok_s > dense_tok_s, (sparse_tok_s, dense_tok_s)
    assert rep_ls.mean_selected_blocks < rep_ls.mean_candidate_blocks, \
        "longctx sparse run was not actually partial-width"
    json_rows.append(_serving_row(
        "longctx_dense", rep_ld, us_ld, context_tokens=ln_tok,
        decode_tok_s_steady=round(dense_tok_s, 1)))
    json_rows.append(_serving_row(
        f"longctx_sparse_k{LONGCTX_TOPK}", rep_ls, us_ls,
        context_tokens=ln_tok, sparse_topk=LONGCTX_TOPK,
        sparse_recent=LONGCTX_RECENT,
        mean_selected_blocks=round(rep_ls.mean_selected_blocks, 2),
        mean_candidate_blocks=round(rep_ls.mean_candidate_blocks, 2),
        decode_tok_s_steady=round(sparse_tok_s, 1),
        sparse_decode_speedup=round(speedup, 3),
        top1_agreement_vs_dense=round(longctx_agreement, 4)))
    emit("serving/longctx_dense", us_ld,
         f"context={ln_tok};decode_tok_s={dense_tok_s:.0f}")
    emit(f"serving/longctx_sparse_k{LONGCTX_TOPK}", us_ls,
         f"context={ln_tok};decode_tok_s={sparse_tok_s:.0f};"
         f"speedup={speedup:.2f};sel={rep_ls.mean_selected_blocks:.1f}/"
         f"{rep_ls.mean_candidate_blocks:.1f};"
         f"top1_agreement={longctx_agreement:.4f}")

    # multi-device scaling: tp head-shards absorbed attention inside a
    # replica, dp adds independent router replicas (runtime/router.py).
    # This process is pinned to ONE CPU device (conftest determinism), so
    # each device count runs repro.runtime.sharded_check in a subprocess
    # that forces its own host device count, all serving the identical
    # deterministic greedy workload through chunked prefill + swap
    # preemption.  Token identity vs single-device is ASSERTED — the
    # sharded path is bit-preserving, so a mismatch is a bug, not noise.
    import os as _os
    import subprocess as _sp
    repo = Path(__file__).resolve().parent.parent
    scaling = {}
    for devices, tp, dp in [(1, 1, 1), (2, 2, 1), (4, 2, 2), (8, 2, 4)]:
        env = dict(_os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count"
                             f"={devices}",
                   JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = str(repo / "src") + (
            _os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        proc = _sp.run([sys.executable, "-m", "repro.runtime.sharded_check",
                        "--devices", str(devices), "--tp", str(tp),
                        "--dp", str(dp), "--scenarios", "plain"],
                       capture_output=True, text=True, env=env, cwd=repo,
                       timeout=560)
        assert proc.returncode == 0, \
            f"sharded_check dev={devices} failed:\n{proc.stderr[-2000:]}"
        scaling[devices] = (tp, dp,
                            json.loads(proc.stdout)["scenarios"]["plain"])
    ref_tokens = scaling[1][2]["tokens"]
    for devices in sorted(scaling):
        tp, dp, sc = scaling[devices]
        rep, match = sc["report"], sc["tokens"] == ref_tokens
        assert match, f"device_count={devices} diverged from single-device"
        json_rows.append(dict(
            scenario=f"sharded_dev{devices}_tp{tp}_dp{dp}",
            device_count=devices, tp=tp, dp=dp,
            tok_s=round(rep["tok_s"], 2),
            ttft_ms_p50=round(rep["ttft_wall_p50_ms"], 2),
            ttft_ms_p95=round(rep["ttft_wall_p95_ms"], 2),
            completed=rep["completed"],
            preemptions=rep["preemptions"],
            routed=rep["routed"],
            imbalance=round(min(rep["imbalance"], 999.0), 3),
            occupancy_per_replica=[round(o, 4)
                                   for o in rep["occupancy_per_replica"]],
            pool_bytes_per_token_per_device=(
                rep["pool_bytes_per_token_per_device"]),
            tokens_match_single_device=match))
        emit(f"serving/sharded_dev{devices}_tp{tp}_dp{dp}", 0.0,
             f"tok_s={rep['tok_s']:.1f};"
             f"ttft_p50={rep['ttft_wall_p50_ms']:.0f};"
             f"ttft_p95={rep['ttft_wall_p95_ms']:.0f};"
             f"routed={rep['routed']};"
             f"bytes_tok_dev={rep['pool_bytes_per_token_per_device']};"
             f"tokens_match_single_device={match}")

    out = write_serving_json(json_rows)
    print(f"wrote {out} ({len(json_rows)} scenario rows, "
          f"schema v{SERVING_SCHEMA_VERSION})", file=sys.stderr)


ALL = {"table1": table1, "table2": table2, "fig5": fig5, "fig6": fig6,
       "kernels": kernels, "cache": cache_table, "serving": serving}


def main() -> None:
    which = sys.argv[1:] or list(ALL)
    print("name,us_per_call,derived")
    for name in which:
        ALL[name]()


if __name__ == "__main__":
    compile_cache.enable()
    main()
