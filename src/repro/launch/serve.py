"""Serving driver over the compressed EliteKV cache.

Batch mode — lockstep greedy generation (contiguous cache):

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b \
        --reduced --elitekv --batch 4 --prompt-len 32 --new-tokens 32

Prints per-request outputs plus the measured cache footprint vs the vanilla
baseline (the paper's headline quantity).

Request-stream mode — continuous batching over the paged pool:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b \
        --reduced --elitekv --stream --requests 16 --rate 0.5 \
        --max-slots 4 --block-size 16 --num-blocks 128

``--stream`` replaces the fixed batch with a Poisson arrival process
(``--rate`` requests per decode step, exponential inter-arrivals, seeded):
prompt lengths and generation budgets are sampled per request, the
``runtime.serve_loop.Scheduler`` admits arrivals into free slots mid-flight,
prefills their prompts in ``--prefill-chunk``-token chunks — up to
``--prefill-lanes`` sequences' chunks packed into one forward — interleaved
with decode steps (0 = whole prompt at admission), retires sequences on EOS
or budget, and recycles their pool blocks immediately.  ``--admission
preempt`` (default) admits without reservation and, when the pool runs dry,
preempts the youngest resident — recompute-prefill of its generated prefix,
or host swap with ``--eviction swap``; ``--admission watermark`` keeps the
legacy worst-case reservation for comparison.  ``--temperature`` /
``--top-p`` select per-request sampling (temperature 0 = greedy); each
request gets the PRNG seed ``--sample-seed + uid``, so reruns reproduce
token-for-token — including across preemptions.  ``--speculate K`` switches
decode to self-speculative draft/verify macro-steps (``--draft-rank R``
picks the rank-truncated draft; 0 = full-rank): each step proposes up to K
tokens per resident with the cheap draft and verifies them in one
full-model forward, advancing ``1 + accepted`` tokens per verify — greedy
streams stay identical to plain decode.  ``--prefix-cache`` shares prompt
blocks across requests (content-addressed, copy-on-write — docs/serving.md);
``--shared-prefix N`` prepends a common N-token system prefix to every
stream prompt so the cache has something to hit.  The run ends by printing
the scheduler metrics line:

    completed / decode steps / decoded tokens / tok/s — throughput
    ttft_steps (+ per prompt-length bucket), ttft_ms p50/p95
                                         — time-to-first-token (sim + wall)
    step_ms p50/p95                      — per-decode-step latency
    blocks high-water/naive, reuse×      — peak pool blocks vs the sum of
                                           per-request worst cases; reuse > 1
                                           is paging's memory win
    occ / preempt(swap) / prefill_batch  — mean pool occupancy, evictions
                                           (and how many used host swap),
                                           mean lanes per prefill forward

plus the pool accounting (live vs allocated bytes, block size, free blocks).
``--pool-dtype int8`` stores every pooled stream as symmetric-absmax int8
rows with per-token f32 scales — the paged kernels dequantize in-register —
roughly quartering bytes/token at a small quality cost (docs/serving.md has
the parity/quality wall).  docs/serving.md walks through every field.

Observability (docs/observability.md): ``--trace out.json`` records the run
into a ring-buffer tracer and writes a Chrome trace-event timeline — open it
at https://ui.perfetto.dev — with one row per pool slot (request residency),
plus scheduler phase spans, pool block churn, and occupancy counters;
``--metrics-out metrics.prom`` exports the process-wide metrics registry in
Prometheus text format after the drain.  Tracing never perturbs the run:
traced and untraced streams are token-identical (regression-tested).
Summarise a written trace offline with
``python -m repro.launch.diagnose trace-summary out.json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.cache import model_cache_floats_per_token
from repro.core.convert import pick_dims
from repro.launch import compile_cache
from repro.models import lm
from repro.obs import REGISTRY, Tracer, write_chrome_trace
from repro.runtime import serve_loop


def serve_stream(params, buffers, cfg, args):
    """Poisson request-stream mode: exercises admission, mid-flight prefill,
    retirement and block recycling; prints the scheduler metrics.
    → (report, the ``Scheduler`` or, with ``--dp`` > 1, the ``Router``)."""
    rng = np.random.default_rng(args.seed)
    tracer = Tracer(capacity=args.trace_capacity) if args.trace else None
    scfg = serve_loop.SchedulerConfig(
        max_slots=args.max_slots, block_size=args.block_size,
        num_blocks=args.num_blocks, eos_id=args.eos_id,
        max_new_tokens=args.new_tokens,
        max_len=args.shared_prefix + args.prompt_len + args.new_tokens + 1,
        prefill_chunk_tokens=args.prefill_chunk,
        prefill_batch_lanes=args.prefill_lanes,
        admission=args.admission, eviction=args.eviction,
        speculate_k=args.speculate, draft_rank=args.draft_rank,
        prefix_cache=args.prefix_cache,
        cache_dtype="int8" if args.pool_dtype == "int8" else jnp.float32,
        sparse_topk_blocks=args.sparse_topk,
        sparse_recent_blocks=args.sparse_recent)
    # multi-device serving: a (dp, tp) mesh sliced into per-replica submeshes
    # (launch/mesh.py) — tp head-shards attention inside each replica, dp adds
    # independent scheduler replicas behind the router (runtime/router.py)
    meshes = None
    if args.tp > 1 or args.dp > 1:
        from repro.launch.mesh import make_serving_mesh, replica_meshes
        meshes = replica_meshes(make_serving_mesh(tp=args.tp, dp=args.dp))
    sched = serve_loop.Scheduler(params, buffers, cfg, scfg, tracer=tracer,
                                 metrics=REGISTRY,
                                 mesh=meshes[0] if meshes else None)
    p_lo = min(4, args.prompt_len)          # sampling floors, valid even for
    n_lo = min(4, args.new_tokens)          # --prompt-len/--new-tokens < 4
    shared = (rng.integers(0, cfg.vocab_size, args.shared_prefix)
              .astype(np.int32) if args.shared_prefix else None)
    t = 0.0
    reqs = []
    for i in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        prompt = rng.integers(0, cfg.vocab_size,
                              int(rng.integers(p_lo, args.prompt_len + 1))
                              ).astype(np.int32)
        if shared is not None:
            prompt = np.concatenate([shared, prompt])
        reqs.append(serve_loop.Request(
            uid=i, prompt=prompt,
            max_new_tokens=int(rng.integers(n_lo, args.new_tokens + 1)),
            arrival=t,
            temperature=args.temperature, top_p=args.top_p,
            seed=args.sample_seed + i))
    if args.dp > 1:
        from repro.runtime.router import Router
        router = Router(params, buffers, cfg, scfg, num_replicas=args.dp,
                        meshes=meshes, tracer=tracer, metrics=REGISTRY)
        rep = router.run(reqs)
        pool0 = router.replicas[0].pool
        print(f"arch={cfg.name} stream [tp={args.tp} dp={args.dp} "
              f"devices={args.tp * args.dp}]: {rep.summary()}")
        print(rep.per_replica_table())
        print(f"pool/device: {pool0.bytes_per_token_per_device()}B/token "
              f"(global {pool0.bytes_per_token()}B/token, tp={pool0.tp}); "
              f"{args.dp} replicas x {scfg.num_blocks} blocks x "
              f"{scfg.block_size} tokens")
        if tracer is not None:
            path = write_chrome_trace(args.trace, tracer)
            print(f"trace: {tracer.emitted} events ({tracer.dropped} dropped "
                  f"by the ring) -> {path} (open in https://ui.perfetto.dev)")
        if args.metrics_out:
            with open(args.metrics_out, "w", encoding="utf-8") as f:
                f.write(REGISTRY.to_prometheus())
            print(f"metrics: {len(REGISTRY.names())} instruments -> "
                  f"{args.metrics_out} (Prometheus text format)")
        return rep, router
    report = sched.run(reqs)
    stats = sched.pool.stats()
    tptag = f" [tp={args.tp}]" if args.tp > 1 else ""
    print(f"arch={cfg.name} stream{tptag}: {report.summary()}")
    if args.tp > 1:
        print(f"pool/device: {sched.pool.bytes_per_token_per_device()}B/token "
              f"(global {sched.pool.bytes_per_token()}B/token, "
              f"tp={sched.pool.tp})")
    if scfg.prefill_chunk_tokens:
        print(f"chunked prefill: {report.prefill_chunks} forwards of "
              f"<= {scfg.prefill_chunk_tokens} tokens x {scfg.chunk_lanes} "
              f"lanes (mean {report.mean_prefill_batch:.2f} live) "
              f"interleaved with decode")
    if scfg.speculate_k:
        print(f"speculative decode [k={scfg.speculate_k} "
              f"rank={scfg.draft_rank or 'full'}]: "
              f"accepted {report.draft_accepted}/{report.draft_proposed} "
              f"draft tokens (rate {report.acceptance_rate:.2f}, "
              f"mean {report.mean_accepted:.2f}/window) over "
              f"{report.draft_forwards} draft + {report.decode_steps} verify "
              f"forwards -> {report.tokens_per_forward:.2f} tokens/forward")
    if scfg.sparse_topk_blocks:
        print(f"sparse decode [topk={report.sparse_topk} "
              f"recent={report.sparse_recent}]: "
              f"mean {report.mean_selected_blocks:.1f}/"
              f"{report.mean_candidate_blocks:.1f} blocks attended per lane "
              f"over {report.sparse_steps} decode forwards")
    if scfg.prefix_cache:
        print(f"prefix cache: hit_rate={report.prefix_cache_hit_rate:.2f} "
              f"({report.prefix_cache_hit_tokens} prompt tokens served from "
              f"cache across {report.prefix_cache_hits} hits / "
              f"{report.prefix_cache_misses} misses), "
              f"cow_copies={report.cow_copies}, "
              f"retained_blocks={report.blocks_retained}")
    if report.preemptions:
        print(f"preemption [{scfg.eviction}]: {report.preemptions} evictions "
              f"across {report.preempted_requests} requests "
              f"(host swaps out/in {report.swap_outs}/{report.swap_ins}, "
              f"{report.swapped_bytes / 2**10:.1f}KiB out); "
              f"mean occupancy {report.mean_occupancy:.2f}")
    print(f"pool: block_size={stats.block_size} blocks={stats.num_blocks} "
          f"high_water={report.pool_high_water_blocks} "
          f"free_after_drain={stats.blocks_free} "
          f"dtype={report.pool_dtype} "
          f"bytes_per_token={report.pool_bytes_per_token} "
          f"allocated_bytes_peak={report.pool_allocated_bytes_peak / 2**20:.2f}MiB")
    if report.block_reuse_ratio > 1.0:
        print(f"block reuse: peak {report.pool_high_water_blocks} blocks served "
              f"a workload whose naive footprint is {report.naive_blocks} "
              f"({report.block_reuse_ratio:.2f}x)")
    if report.phase_ms:
        print(f"phases: {report.phase_table()} "
              f"(step wall {report.step_wall_ms_total:.0f}ms)")
    if tracer is not None:
        path = write_chrome_trace(args.trace, tracer)
        print(f"trace: {report.trace_events} events "
              f"({report.trace_dropped} dropped by the ring) -> {path} "
              f"(open in https://ui.perfetto.dev)")
    if args.metrics_out:
        with open(args.metrics_out, "w", encoding="utf-8") as f:
            f.write(REGISTRY.to_prometheus())
        print(f"metrics: {len(REGISTRY.names())} instruments -> "
              f"{args.metrics_out} (Prometheus text format)")
    return report, sched


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--elitekv", action="store_true")
    ap.add_argument("--cache-ratio", type=float, default=0.25)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    # request-stream (continuous batching) mode
    ap.add_argument("--stream", action="store_true",
                    help="Poisson request stream through the paged scheduler")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="per-lane per-step chunked-prefill token budget "
                         "(0 = whole prompt at admission)")
    ap.add_argument("--prefill-lanes", type=int, default=0,
                    help="mid-prefill sequences packed per chunked-prefill "
                         "forward (0 = max-slots, 1 = one request per chunk)")
    ap.add_argument("--admission", choices=("preempt", "watermark"),
                    default="preempt",
                    help="preempt: admit on demand, evict youngest on "
                         "OutOfBlocks; watermark: legacy worst-case "
                         "reservation (never preempts)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix blocks across requests "
                         "(content-addressed cache, copy-on-write; "
                         "docs/serving.md)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common N-token system prefix to every "
                         "stream prompt (exercises --prefix-cache hits)")
    ap.add_argument("--pool-dtype", choices=("f32", "int8"), default="f32",
                    help="paged-pool page storage: f32, or int8 symmetric "
                         "absmax quantization with per-token scales and "
                         "fused in-kernel dequant (docs/serving.md)")
    ap.add_argument("--eviction", choices=("recompute", "swap"),
                    default="recompute",
                    help="preemption mechanism: recompute the evicted prefix "
                         "or swap the cached streams to host memory")
    ap.add_argument("--sparse-topk", type=int, default=0,
                    help="latent-space sparse decode: attend only the top-K "
                         "blocks scored against per-block latent summaries, "
                         "plus --sparse-recent newest blocks (0 = dense; "
                         "K >= blocks-per-chain reproduces dense exactly)")
    ap.add_argument("--sparse-recent", type=int, default=2,
                    help="newest chain blocks always attended under "
                         "--sparse-topk (the in-progress block plus a short "
                         "local-context tail)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="self-speculative decode: draft tokens per resident "
                         "per step (0 = plain one-token decode)")
    ap.add_argument("--draft-rank", type=int, default=0,
                    help="joint-factor rank of the draft model (0 or >= "
                         "d_ckv = full-rank draft, acceptance 1)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for stream requests (0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1 = full softmax)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base PRNG seed; request i samples with seed+i")
    # multi-device serving (docs/serving.md#sharded-serving)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: shard absorbed attention "
                         "heads and the k_e pool pages over a 'model' mesh "
                         "axis (token streams stay bit-identical)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas: N independent schedulers "
                         "behind a least-loaded router (needs tp*dp devices; "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    # observability (docs/observability.md)
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace-event timeline of the stream "
                         "run to this path (view at ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="tracer ring-buffer capacity (oldest events drop "
                         "beyond this)")
    ap.add_argument("--metrics-out", default="",
                    help="write the metrics registry in Prometheus text "
                         "format to this path after the run")
    args = ap.parse_args(argv)

    base = get_config(args.arch)
    if args.reduced:
        base = base.reduced()
    cfg = base
    if args.elitekv and cfg.n_attn_layers:
        cfg = dataclasses.replace(cfg, elitekv=pick_dims(cfg, args.cache_ratio, align=16))

    if args.tp < 1 or args.dp < 1:
        ap.error("--tp and --dp must be >= 1")
    if args.sparse_topk < 0 or args.sparse_recent < 0:
        ap.error("--sparse-topk and --sparse-recent must be >= 0")
    if args.sparse_topk > 0 and args.speculate > 0:
        ap.error("--sparse-topk and --speculate are mutually exclusive "
                 "(the multi-query verify window has no single selection "
                 "query; see docs/serving.md)")
    if args.sparse_topk > 0 and not args.stream:
        ap.error("--sparse-topk selects blocks in the paged decode path; "
                 "add --stream")
    if (args.sparse_topk > 0 and args.admission == "preempt"
            and args.eviction == "recompute"):
        ap.error("--sparse-topk with preempt admission needs --eviction swap "
                 "(recompute prefill cannot reproduce sparse-generated "
                 "streams; docs/serving.md#sparse-decode)")
    if (args.tp > 1 or args.dp > 1) and not args.stream:
        ap.error("--tp/--dp shard the paged serving path; add --stream")
    if args.tp > 1 and cfg.elitekv.enabled and cfg.n_kv_heads % args.tp:
        ap.error(f"--tp {args.tp} must divide n_kv_heads={cfg.n_kv_heads} "
                 "(see pad_cfg_for_tp in distributed/sharding.py)")

    key = jax.random.PRNGKey(args.seed)
    params, buffers = lm.init(key, cfg)
    if args.stream:
        if not cfg.elitekv.enabled:
            ap.error("--stream requires --elitekv (paged pool stores the "
                     "compressed streams)")
        if args.rate <= 0:
            ap.error("--rate must be > 0 (mean arrivals per decode step)")
        return serve_stream(params, buffers, cfg, args)
    if args.trace or args.metrics_out:
        ap.error("--trace/--metrics-out instrument the paged scheduler; "
                 "add --stream")
    prompts = jax.random.randint(jax.random.PRNGKey(1), (args.batch, args.prompt_len),
                                 0, cfg.vocab_size, jnp.int32)
    t0 = time.time()
    out, stats = serve_loop.generate(params, buffers, cfg, prompts,
                                     args.new_tokens)
    dt = time.time() - t0
    base_floats = model_cache_floats_per_token(base)
    elite_floats = model_cache_floats_per_token(cfg)
    print(f"arch={cfg.name} elitekv={cfg.elitekv.enabled}")
    print(f"generated {out.shape} in {dt:.1f}s "
          f"({stats.decoded_tokens / max(dt, 1e-9):.1f} tok/s incl. compile)")
    print(f"cache floats/token: {elite_floats} vs baseline {base_floats} "
          f"→ ratio {elite_floats / max(base_floats, 1):.3f}")
    print(f"measured attention cache: {stats.cache_bytes / 2**20:.2f} MiB")
    for b in range(min(2, args.batch)):
        print(f"  req{b}: {out[b, :16].tolist()} ...")
    return out


if __name__ == "__main__":
    compile_cache.enable()
    main()
