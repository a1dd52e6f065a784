"""Serving runtime over the compressed EliteKV cache (see docs/serving.md).

Two tiers:

* ``generate`` — lockstep batched greedy decoding with a contiguous cache
  (examples / parity oracle).
* ``Scheduler`` — continuous batching over the block-paged pool
  (``core.cache.PagedKVPool`` + ``core.cache.BlockManager``): requests queue
  with arrival times, get admitted into free *slots* mid-flight, prefill
  their prompts in fixed-size token **chunks** interleaved with decode steps
  (so a long arriving prompt never stalls resident sequences), and retire on
  EOS or token budget — their blocks recycle immediately.  Each scheduler
  step packs up to ``prefill_batch_lanes`` mid-prefill sequences' chunks
  (``prefill_chunk_tokens`` each) into **one** padded forward — per-lane
  ``chunk_start`` / ``prefix_lens`` vectors let resumed chunks of different
  sequences attend to their own paged prefixes in the same call — then runs
  one decode step over all ``max_slots`` lanes (idle and still-prefilling
  lanes are masked by length 0).  With ``prefill_chunk_tokens=0`` the whole
  prompt is prefilled at admission in one call (PR-2 behaviour).  The run
  compiles once per prompt-length bucket (one-shot), once for the fixed
  batched chunk shape (chunked), plus once for decode.

Decoding samples per request: temperature / nucleus (top-p) with a
per-request PRNG seed, applied batched over all lanes in one jitted call;
``temperature=0`` lanes reduce exactly to greedy argmax.

Speculative decode (``speculate_k > 0``) replaces the one-token decode step
with a draft/verify macro-step: ``k`` cheap decode forwards of a
rank-truncated *draft* model (``models.lm.make_draft_params`` — the top
singular directions of the existing joint low-rank factors, sharing the same
paged latent cache) propose up to ``k`` tokens per resident, then ONE
full-model verify forward (``models.lm.apply_verify_paged``) re-scores all
``k+1`` window positions against the paged prefix.  Acceptance is standard
distribution-preserving rejection sampling against the per-request
temperature/top-p target using the same count-folded PRNG (greedy lanes
accept on exact argmax match), so accepted streams match plain decode in
distribution — and exactly under greedy (or with a full-rank draft), where
the stream is also invariant under preemption.  A *truncated*-draft sampled
stream is path-dependent by construction — which token the accept coin
judges depends on where the macro-step windows fall, so preemption (which
shifts window alignment) can change the realized sample while preserving
its distribution, exactly as in standard speculative sampling.  Rejected
tokens roll the pool chain back via ``BlockManager.truncate``; the decode
hot path advances ``1 + accepted`` tokens per verify forward instead of 1.

Admission (``admission="preempt"``, the default) holds nothing back: a
request is admitted as soon as its next allocation fits, residents grow
blocks on demand, and when the pool runs dry mid-flight the scheduler
**preempts the youngest resident** — frees its blocks and requeues it at the
head of the waiting line for a recompute-prefill of its already-generated
prefix (``eviction="recompute"``), or copies its cached streams to host
memory and restores them block-exactly on re-admission
(``eviction="swap"``).  Token streams are invariant under preemption: a
recomputed prefix reproduces the exact logits the interrupted decode step
would have seen, and the count-folded sampling PRNG re-draws the exact same
token.  ``admission="watermark"`` keeps the legacy reservation policy
(worst-case remaining blocks of every resident held back, so growth can
never fail) for comparison runs — it trades occupancy for never preempting.

Prefix caching (``prefix_cache=True``, docs/serving.md): the pool's blocks
become shareable across requests.  At admission the scheduler probes the
``BlockManager``'s content-addressed prefix cache with the request's prompt
(chained hashes of full token blocks); cached blocks are spliced into the
newcomer's chain and prefill *skips every fully-covered chunk*, resuming
chunked-prefill attention at the first miss.  Freshly prefilled full prompt
blocks are registered after every chunk, so a long shared system prompt
warms the cache for requests arriving mid-prefill.  All writes go through a
copy-on-write barrier (``prepare_write`` inside ``_grow_or_preempt``), so
the token streams are invariant: a cache-on run emits exactly the cache-off
tokens (greedy and sampled, under preemption and speculative decode —
tests/test_prefix_cache.py pins the wall).  Retired prefixes stay retained
in an LRU until the allocator actually needs their blocks.

Observability (docs/observability.md): the scheduler accepts a
``repro.obs.trace.Tracer`` and a ``repro.obs.metrics.MetricsRegistry``.  Every
``step()`` times itself and is decomposed into host-observable **phases**
(``PHASES``) — prefill / decode / draft / verify forwards, sampling,
speculative accept bookkeeping, swap copies, admission, chain growth, input
packing, plus the step's own ``other`` remainder.  Phases nest (a swap-out
inside chain growth) and each is charged its *self* time, so the totals in
``ServeReport.phase_ms`` sum to ``step_wall_ms_total`` for any caller of
``step()``.  The step and each phase are also ``jax.profiler.TraceAnnotation``
spans (``sched.step``, ``sched.<phase>``), on the device trace's clock in
any profile, and, when a tracer is attached, spans on the ``scheduler``
timeline track; per-request lifecycle events (submit, admit, prefill chunks,
preempt, retire) land on per-slot tracks and the pool emits its own
alloc/free/swap/truncate events.  Instrumentation is passive: it
reads clocks and appends host-side records, never touching PRNG or
scheduling state, so a traced run emits bit-identical tokens to an untraced
one (tests/test_obs.py pins this).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cache import BlockManager, OutOfBlocks, PagedKVPool
from repro.kernels.elite_decode import pages_per_step
from repro.models import lm
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER

#: Host-observable phases of one scheduler step (``ServeReport.phase_ms``
#: keys — all always present, zero-valued when a phase never ran), each
#: charged its self time.  ``admit`` is the admission loop (prefix lookup
#: included), ``grow`` the chain-growth loops (a preemption nests inside),
#: ``pack`` the host input arrays of a forward; ``other`` is the step's own
#: remainder (gauges, counters, lane ordering, token bookkeeping), so the
#: phase totals sum to ``step_wall_ms_total``.  The jitted forwards are
#: opaque to host timing, so their input puts and the embedding dispatch are
#: folded into the enclosing forward phase.
PHASES = ("prefill", "decode", "draft", "verify", "sample", "accept",
          "swap", "admit", "grow", "pack", "other")


def make_prefill_step(cfg: ModelConfig, mesh=None, constrain=None,
                      moe_impl: str = "ragged", data_axes=("data",)):
    constrain = constrain or (lambda n, x: x)

    def prefill_step(params, buffers, batch, cache):
        return lm.apply_prefill(params, buffers, cfg, batch, cache,
                                moe_impl=moe_impl, mesh=mesh,
                                constrain=constrain, data_axes=data_axes)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh=None, constrain=None,
                     moe_impl: str = "ragged", greedy: bool = True,
                     data_axes=("data",)):
    constrain = constrain or (lambda n, x: x)

    def decode_step(params, buffers, tokens, cache):
        batch = ({"tokens": tokens} if cfg.frontend != "audio"
                 else {"frames": tokens})
        logits, cache = lm.apply_decode(params, buffers, cfg, batch, cache,
                                        moe_impl=moe_impl, mesh=mesh,
                                        constrain=constrain, data_axes=data_axes)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return decode_step


@dataclasses.dataclass
class ServeStats:
    """Counters for the lockstep ``generate`` path.

    ``prefill_tokens``  — prompt tokens pushed through the prefill forward
                          (batch × prompt length).
    ``decoded_tokens``  — tokens produced by decode steps (batch × new tokens).
    ``cache_bytes``     — measured bytes of the attention KV cache actually
                          allocated for the run (the paper's headline
                          compression shows up here).
    """
    prefill_tokens: int = 0
    decoded_tokens: int = 0
    cache_bytes: int = 0


def generate(params, buffers, cfg: ModelConfig, prompts: jnp.ndarray,
             max_new_tokens: int, mesh=None, moe_impl: str = "ragged",
             cache_dtype=jnp.float32) -> Tuple[np.ndarray, ServeStats]:
    """Greedy generation for a batch of fixed-length prompts (examples/tests).

    prompts: [B, S_prompt] int32 → generated [B, max_new_tokens].
    """
    B, Sp = prompts.shape
    max_len = Sp + max_new_tokens
    cache = lm.init_cache(cfg, B, max_len, dtype=cache_dtype)
    prefill = jax.jit(make_prefill_step(cfg, mesh=mesh, moe_impl=moe_impl))
    decode = jax.jit(make_decode_step(cfg, mesh=mesh, moe_impl=moe_impl))
    logits, cache = prefill(params, buffers, {"tokens": prompts}, cache)
    nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    outs = [nxt]
    for _ in range(max_new_tokens - 1):
        nxt, _, cache = decode(params, buffers, nxt[:, None], cache)
        outs.append(nxt)
    from repro.core.cache import measured_cache_bytes
    stats = ServeStats(prefill_tokens=B * Sp, decoded_tokens=B * max_new_tokens,
                       cache_bytes=measured_cache_bytes(cache, B, max_len)["attn_bytes"])
    return np.stack([np.asarray(o) for o in outs], axis=1), stats


# ---------------------------------------------------------------------------
# continuous batching over the paged pool
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival`` is in scheduler steps (the
    simulated clock) — the Poisson driver maps wall arrival times onto it.

    Sampling is per request: ``temperature <= 0`` is greedy argmax; otherwise
    nucleus sampling from the smallest token set whose probability mass
    reaches ``top_p``, driven by a PRNG keyed on ``seed`` and folded with the
    token index — the same (seed, prompt) always yields the same tokens.
    """
    uid: int
    prompt: np.ndarray                    # [Sp] int32
    max_new_tokens: int
    arrival: float = 0.0
    temperature: float = 0.0              # 0 → greedy
    top_p: float = 1.0                    # nucleus mass (1 → full softmax)
    seed: int = 0                         # per-request PRNG seed
    # filled in by the scheduler:
    generated: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0                  # prefill-source tokens already cached
    prefill_src: Optional[np.ndarray] = None   # recompute source (None → prompt)
    swapped: Optional[Any] = None         # cache.SwappedSeq awaiting swap-in
    preempted_at: List[int] = dataclasses.field(default_factory=list)
    #   ^ len(generated) at each preemption (0 = preempted mid-prefill)
    spec_proposed: int = 0                # draft tokens proposed for this req
    spec_accepted: int = 0                # draft tokens that survived verify
    prefix_hit_tokens: int = 0            # prompt tokens served from the
                                          # prefix cache (Σ over re-admissions)
    submit_wall: float = 0.0
    first_token_wall: float = 0.0
    first_token_step: int = -1
    finish_step: int = -1
    finish_reason: str = ""               # "eos" | "budget"

    def prefill_source(self) -> np.ndarray:
        """Tokens that must be cached before decode (re)starts: the prompt,
        or — after a recompute preemption — prompt + generated prefix."""
        return self.prompt if self.prefill_src is None else self.prefill_src


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_slots: int = 4                    # concurrent sequences per decode step
    block_size: int = 16                  # tokens per pool block
    num_blocks: int = 128                 # pool capacity
    max_new_tokens: int = 64              # hard per-request generation cap
    max_len: int = 256                    # per-sequence token cap (table width)
    eos_id: Optional[int] = None
    prefill_bucket: int = 16              # prompts pad up to a multiple of this
    prefill_chunk_tokens: int = 0         # per-lane per-step chunk size
                                          # (0 → whole prompt at admission)
    prefill_batch_lanes: int = 0          # mid-prefill lanes packed per chunked
                                          # forward (0 → max_slots; 1 → PR-3
                                          # one-request-per-chunk behaviour)
    speculate_k: int = 0                  # draft tokens per resident per step
                                          # (0 → plain one-token decode)
    draft_rank: int = 0                   # joint-factor rank of the draft
                                          # model (0 or >= d_ckv → full rank)
    admission: str = "preempt"            # "preempt" | "watermark" (legacy)
    eviction: str = "recompute"           # "recompute" | "swap" (host swap-out)
    prefix_cache: bool = False            # share prompt-prefix blocks across
                                          # requests (COW on divergence)
    use_kernel: bool = True               # Pallas paged kernel on TPU
    cache_dtype: Any = jnp.float32        # pool page dtype; "int8" switches
                                          # the pool to symmetric absmax
                                          # quantization with per-token scales
                                          # (core/quant.py, docs/serving.md)
    sparse_topk_blocks: int = 0           # latent-space sparse decode: attend
                                          # only the top-k summary-scored
                                          # blocks per lane (0 → dense decode;
                                          # incompatible with speculate_k)
    sparse_recent_blocks: int = 2         # newest blocks always attended when
                                          # sparse decode is on (the local
                                          # window every selection keeps)

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_len // self.block_size)

    @property
    def chunk_lanes(self) -> int:
        return self.prefill_batch_lanes or self.max_slots


def sample_tokens(logits, temps, top_ps, seeds, counts):
    """Batched per-request sampling for one decode step.

    logits [B,V] fp32-castable, temps/top_ps [B] fp32, seeds/counts [B] int32.
    Lane ``i`` draws from PRNG ``fold_in(PRNGKey(seeds[i]), counts[i])`` — the
    count is the request's token index, so replaying a request with the same
    seed reproduces its tokens regardless of which slot/step served it.
    ``temps[i] <= 0`` reduces exactly to greedy argmax.  → [B] int32.
    """

    def one(lg, temp, top_p, seed, count):
        def greedy(_):
            return jnp.argmax(lg).astype(jnp.int32)

        def sample(_):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), count)
            scaled = lg.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
            order = jnp.argsort(-scaled)            # descending
            sl = scaled[order]
            probs = jax.nn.softmax(sl)
            # nucleus: drop tokens whose preceding cumulative mass already
            # covers top_p; the smallest covering set always keeps its first
            # member (even at the top_p <= 0 boundary, where the cut would
            # otherwise mask everything and sample from garbage)
            cut = (jnp.cumsum(probs) - probs) >= top_p
            cut = cut.at[0].set(False)
            sl = jnp.where(cut, -jnp.inf, sl)
            return order[jax.random.categorical(key, sl)].astype(jnp.int32)

        # temp <= 0 takes the argmax branch STRUCTURALLY — greedy lanes never
        # route through the temperature division, so "temperature 0" is exact
        # argmax rather than clamp-to-1e-6-shaped (tests/test_serve.py pins
        # greedy == temp-0 identity)
        return jax.lax.cond(temp <= 0.0, greedy, sample, None)

    return jax.vmap(one)(logits, temps, top_ps, seeds, counts)


# ---------------------------------------------------------------------------
# speculative-decode acceptance (pure functions — property-tested directly)
# ---------------------------------------------------------------------------

_ACCEPT_SALT = 0x5BEC                     # PRNG fold salts: the accept coin and
_RESID_SALT = 0x5BED                      # residual draw for one token index


def nucleus_probs(logits, temp: float, top_p: float) -> np.ndarray:
    """The exact categorical distribution ``sample_tokens`` draws from, as a
    dense probability vector (numpy, float64): temperature-scaled softmax
    restricted to the smallest descending-probability set whose mass reaches
    ``top_p`` (the set always keeps its first member).  Tokens outside the
    nucleus get probability exactly 0 — the rejection-sampling target/draft
    distributions for speculative decode."""
    scaled = np.asarray(logits, np.float64) / max(float(temp), 1e-6)
    order = np.argsort(-scaled, kind="stable")
    sl = scaled[order]
    e = np.exp(sl - sl.max())
    probs = e / e.sum()
    cut = (np.cumsum(probs) - probs) >= top_p
    cut[0] = False                        # first member survives even top_p=0
    sl = np.where(cut, -np.inf, sl)
    e = np.exp(sl - sl[0])                # sl[0] is always kept (finite max)
    p_sorted = e / e.sum()
    out = np.zeros_like(p_sorted)
    out[order] = p_sorted
    return out


def speculative_accept(token: int, p: np.ndarray, q: np.ndarray,
                       u: float) -> bool:
    """Distribution-preserving accept test for a draft ``token`` proposed
    from draft distribution ``q`` against target ``p``: accept iff
    ``u <= p(token)/q(token)`` (``u`` uniform on [0,1)).  Combined with
    ``residual_sample`` on rejection, the emitted token is distributed
    exactly as ``p`` (Leviathan et al.'s rejection-sampling identity).
    A token outside the *target* nucleus is never accepted, even when the
    host-side ``q`` disagrees with the device sampler's float32 nucleus cut
    at the top-p boundary and reports ``q(token) == 0`` (which would
    otherwise make the ratio vacuously pass)."""
    return p[token] > 0.0 and u * q[token] <= p[token]


def residual_sample(p: np.ndarray, q: np.ndarray, r: float) -> int:
    """Inverse-CDF draw from the normalized residual ``max(p - q, 0)`` — the
    corrected token after a rejection.  Support is a subset of ``p``'s
    (never a token outside the target nucleus).  Degenerate ``p == q``
    residuals (possible only through float rounding — exact equality always
    accepts) fall back to ``p`` itself."""
    res = np.maximum(np.asarray(p, np.float64) - np.asarray(q, np.float64), 0.0)
    if res.sum() <= 1e-12:
        res = np.asarray(p, np.float64)
    nz = np.flatnonzero(res)
    cdf = np.cumsum(res[nz]) / res[nz].sum()
    return int(nz[min(np.searchsorted(cdf, r, side="right"), len(nz) - 1)])


def _spec_uniform(seed: int, count: int, salt: int) -> float:
    """Uniform [0,1) tied to (request seed, token index, salt) — the same
    count-folded PRNG discipline as ``sample_tokens``, so acceptance
    decisions replay identically across preemption/recompute."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), count), salt)
    return float(jax.random.uniform(key))


def _prompt_buckets(finished: List[Request], edges: Tuple[int, ...]):
    """Partition finished requests by prompt length: yields
    ``(label, requests)`` per bucket — the single source of the bucket edges
    and labels every per-bucket metric (TTFT, acceptance) keys on, so the
    ``ttft_prompt_*`` and ``acc_prompt_*`` CSV columns can never
    desynchronize."""
    lo = 0
    for hi in tuple(edges) + (None,):
        label = (f"{lo + 1}-{hi}" if hi is not None else f">{lo}")
        yield label, [r for r in finished if lo < len(r.prompt)
                      and (hi is None or len(r.prompt) <= hi)]
        lo = hi if hi is not None else lo


def acceptance_by_prompt_bucket(finished: List[Request],
                                edges: Tuple[int, ...] = (16, 64)
                                ) -> Dict[str, float]:
    """Mean draft-acceptance rate per prompt-length bucket (same buckets as
    ``ttft_by_prompt_bucket``) — long-prompt windows attend to more context,
    so acceptance can drift with depth; the serving benchmark reports it."""
    out: Dict[str, float] = {}
    for label, rs in _prompt_buckets(finished, edges):
        rs = [r for r in rs if r.spec_proposed]
        if rs:
            out[label] = float(sum(r.spec_accepted for r in rs)
                               / sum(r.spec_proposed for r in rs))
    return out


def ttft_by_prompt_bucket(finished: List[Request],
                          edges: Tuple[int, ...] = (16, 64)) -> Dict[str, float]:
    """Mean TTFT (scheduler steps from arrival to first token) per prompt-
    length bucket — the quantity chunked prefill improves for *short* prompts
    that would otherwise queue behind long ones.  ``edges`` split lengths into
    len(edges)+1 buckets: <=16, 17..64, >64 by default."""
    out: Dict[str, float] = {}
    for label, rs in _prompt_buckets(finished, edges):
        if rs:
            out[label] = float(np.mean([r.first_token_step - r.arrival
                                        for r in rs]))
    return out


@dataclasses.dataclass
class ServeReport:
    """End-of-run scheduler metrics (docs/serving.md explains how to read
    them).  TTFT = arrival → first token; ``_steps`` is in simulated
    scheduler steps, ``_wall`` in wall milliseconds."""
    completed: int = 0
    decode_steps: int = 0
    prefill_tokens: int = 0
    prefill_chunks: int = 0               # prefill forward calls issued
    decoded_tokens: int = 0
    wall_s: float = 0.0
    tok_per_s: float = 0.0
    ttft_steps_mean: float = 0.0
    ttft_steps_by_bucket: Dict[str, float] = dataclasses.field(default_factory=dict)
    ttft_wall_p50_ms: float = 0.0
    ttft_wall_p95_ms: float = 0.0
    step_ms_p50: float = 0.0
    step_ms_p95: float = 0.0
    peak_slots: int = 0
    pool_high_water_blocks: int = 0
    pool_block_size: int = 0
    pool_dtype: str = "float32"           # page storage dtype ("int8" = quantized)
    pool_bytes_per_token: int = 0         # device bytes per pooled token (all
                                          # layers + streams, incl. scales)
    pool_allocated_bytes_peak: int = 0    # bytes at the block high-water mark
    naive_blocks: int = 0                 # Σ per-request worst-case blocks
    block_reuse_ratio: float = 0.0        # naive / high-water (>1 ⇒ paging won)
    admission: str = "preempt"            # policy the run used
    preemptions: int = 0                  # evictions forced by OutOfBlocks
    preempted_requests: int = 0           # distinct requests evicted ≥ once
    swap_outs: int = 0                    # preemptions served by host swap
    swap_ins: int = 0                     # swapped prefixes restored
    swapped_bytes: int = 0                # host↔device eviction traffic (out)
    mean_occupancy: float = 0.0           # mean fraction of pool blocks
                                          # REFERENCED by live chains (matches
                                          # what admission sees as busy)
    mean_occupancy_retained: float = 0.0  # mean fraction counting prefix-cache
                                          # retained (refcount-0 LRU) blocks
                                          # too — i.e. raw allocator usage
    mean_prefill_batch: float = 0.0       # mean lanes per chunked-prefill call
    speculate_k: int = 0                  # draft window size the run used
    draft_rank: int = 0                   # draft joint-factor rank (0 = full)
    draft_forwards: int = 0               # rank-truncated draft decode calls
    draft_proposed: int = 0               # draft tokens proposed across lanes
    draft_accepted: int = 0               # draft tokens that survived verify
    acceptance_rate: float = 0.0          # accepted / proposed
    mean_accepted: float = 0.0            # accepted draft tokens per window
    tokens_per_forward: float = 0.0       # tokens per lane per decode/verify
                                          # forward (plain ≡ 1.0; spec =
                                          # 1 + mean_accepted)
    acceptance_by_bucket: Dict[str, float] = dataclasses.field(default_factory=dict)
    prefix_cache: bool = False            # run shared prompt blocks
    prefix_cache_hits: int = 0            # admissions that reused cached blocks
    prefix_cache_misses: int = 0          # admissions finding nothing cached
    prefix_cache_hit_tokens: int = 0      # prompt tokens skipped at prefill
    prefix_cache_hit_rate: float = 0.0    # hit_tokens / tokens presented to
                                          # lookups (per-token, not per-request)
    cow_copies: int = 0                   # copy-on-write block privatizations
    blocks_retained: int = 0              # zero-ref cached blocks at run end
    sparse_topk: int = 0                  # block top-k the run decoded with
    sparse_recent: int = 0                # forced newest-block tail width
    sparse_steps: int = 0                 # decode forwards that ran sparse
    mean_selected_blocks: float = 0.0     # blocks attended per lane-step
    mean_candidate_blocks: float = 0.0    # resident blocks per lane-step
    phase_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    #   ^ self wall ms per step phase over the whole run (keys == PHASES; a
    #     phase that never ran reports exactly 0.0); they sum to
    #     step_wall_ms_total.
    step_wall_ms_total: float = 0.0       # Σ wall ms of every step() call
    trace_events: int = 0                 # events emitted to the tracer
    trace_dropped: int = 0                # events the ring buffer evicted

    def phase_table(self) -> str:
        """One-line per-phase breakdown: ``phase=total_ms(share%)`` for every
        phase that ran (launch/serve.py prints it; ``trace-summary``
        reconstructs the same table from an exported timeline)."""
        total = max(self.step_wall_ms_total, 1e-9)
        parts = [f"{k}={v:.1f}ms({100 * v / total:.0f}%)"
                 for k, v in self.phase_ms.items() if v > 0]
        return " ".join(parts) if parts else "(no phases recorded)"

    def summary(self) -> str:
        bucket = "".join(f" ttft[{k}]={v:.1f}" for k, v in
                         self.ttft_steps_by_bucket.items())
        spec = ""
        if self.speculate_k:
            spec = (f" spec[k={self.speculate_k},r={self.draft_rank}] "
                    f"acc={self.acceptance_rate:.2f} "
                    f"tok/fwd={self.tokens_per_forward:.2f}")
        pc = ""
        if self.prefix_cache:
            pc = (f" pc[hit={self.prefix_cache_hit_rate:.2f} "
                  f"tok={self.prefix_cache_hit_tokens} "
                  f"cow={self.cow_copies}]")
        q8 = ""
        if self.pool_dtype not in ("float32", ""):
            q8 = (f" pool[{self.pool_dtype} "
                  f"{self.pool_bytes_per_token}B/tok]")
        sp = ""
        if self.sparse_topk:
            sp = (f" sparse[k={self.sparse_topk}+{self.sparse_recent} "
                  f"sel={self.mean_selected_blocks:.1f}/"
                  f"{self.mean_candidate_blocks:.1f}]")
        return (f"completed={self.completed} steps={self.decode_steps} "
                f"decoded={self.decoded_tokens} tok/s={self.tok_per_s:.1f} "
                f"ttft_steps={self.ttft_steps_mean:.1f}{bucket} "
                f"ttft_ms p50/p95={self.ttft_wall_p50_ms:.0f}/{self.ttft_wall_p95_ms:.0f} "
                f"step_ms p50/p95={self.step_ms_p50:.1f}/{self.step_ms_p95:.1f} "
                f"peak_slots={self.peak_slots} "
                f"blocks high-water/naive={self.pool_high_water_blocks}/"
                f"{self.naive_blocks} reuse×{self.block_reuse_ratio:.2f} "
                f"occ={self.mean_occupancy:.2f} [{self.admission}] "
                f"preempt={self.preemptions}"
                f"(swap {self.swap_outs}/{self.swap_ins}) "
                f"prefill_batch={self.mean_prefill_batch:.1f}{spec}{pc}{q8}{sp}")


class Scheduler:
    """Continuous-batching serving loop over the paged compressed cache."""

    def __init__(self, params, buffers, cfg: ModelConfig,
                 scfg: SchedulerConfig, mesh=None, moe_impl: str = "ragged",
                 tracer=None, metrics=None):
        assert cfg.elitekv.enabled, "paged serving requires an EliteKV config"
        assert scfg.eviction in ("recompute", "swap"), scfg.eviction
        # sparse decode scores single-token queries against block summaries;
        # the multi-query verify window has no single selection query, so the
        # speculative path stays dense — the combination is rejected outright
        # rather than silently ignoring one of the knobs
        assert scfg.sparse_topk_blocks == 0 or scfg.speculate_k == 0, \
            "sparse_topk_blocks and speculate_k are mutually exclusive"
        # recompute eviction re-prefills a preempted prefix DENSELY, but a
        # token generated under partial sparse decode carries layer>=1
        # streams shaped by sparse lower-layer attention — dense prefill
        # cannot reproduce them, so recompute would silently fork the
        # stream.  Swap restores the pages (and summary rows) byte-exactly;
        # full selection width is exactly dense, so either keeps the
        # preemption-invariance wall.  Reject the one unsound combination.
        sparse_partial = (0 < scfg.sparse_topk_blocks and
                          scfg.sparse_topk_blocks + scfg.sparse_recent_blocks
                          < scfg.max_blocks_per_seq)
        assert not (sparse_partial and scfg.admission == "preempt"
                    and scfg.eviction == "recompute"), \
            ("partial-width sparse decode requires eviction='swap' (or "
             "admission='watermark'): recompute prefill cannot reproduce "
             "sparse-generated streams")
        if mesh is not None:
            # weights live on this replica's own devices (replicated over its
            # TP axis); left on the default device, every step would copy
            # them to the devices that hold the pool
            params, buffers = jax.device_put(
                (params, buffers), jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
        self.params, self.buffers, self.cfg, self.scfg = params, buffers, cfg, scfg
        self.trace = tracer or NULL_TRACER
        self.metrics = metrics or MetricsRegistry()
        # mesh=None serves single-device; a mesh with a >1 "model" axis
        # head-shards the k_e pages and runs decode/verify attention under
        # shard_map (kernels/ops.py TP wrappers) — token streams stay
        # bit-identical either way (tests/test_sharded_serving.py).
        self.pool = PagedKVPool(cfg, scfg.num_blocks, scfg.block_size,
                                dtype=scfg.cache_dtype, tracer=self.trace,
                                mesh=mesh,
                                block_summaries=scfg.sparse_topk_blocks > 0)
        self.bm = BlockManager(self.pool, policy=scfg.admission,
                               prefix_cache=scfg.prefix_cache)
        self.slots: List[Optional[Request]] = [None] * scfg.max_slots
        self.waiting: collections.deque = collections.deque()
        self.finished: List[Request] = []
        self.t = 0                          # simulated clock (decode steps)
        self._step_wall_ms: List[float] = []
        self._occupancy: List[float] = []   # referenced fill fraction per step
        self._occupancy_retained: List[float] = []  # incl. LRU-retained blocks
        self.peak_slots = 0
        self.naive_blocks = 0
        self.prefill_chunks = 0             # prefill forward calls issued
        self._prefill_lanes_total = 0       # Σ live lanes over those calls
        self.draft_forwards = 0             # speculative: draft decode calls
        self.draft_proposed = 0             # Σ draft tokens proposed
        self.draft_accepted = 0             # Σ draft tokens accepted
        self._spec_windows = 0              # (lane, step) verify windows run
        self._lane_steps = 0                # Σ live lanes over decode forwards
        self._decode_appended = 0           # tokens appended by decode/verify
        # -- observability state (docs/observability.md) ---------------------
        self._phase_ms = {p: 0.0 for p in PHASES}
        self._step_wall_ms_total = 0.0
        self._phase_stack: List[List[Any]] = []   # open [phase, since]
        m = self.metrics
        self._m_submitted = m.counter(
            "serve_requests_submitted_total", "requests submitted")
        self._m_completed = m.counter(
            "serve_requests_completed_total", "requests retired (eos|budget)")
        self._m_decoded = m.counter(
            "serve_tokens_decoded_total", "tokens appended by decode/verify")
        self._m_prefill_tokens = m.counter(
            "serve_prefill_tokens_total", "tokens cached by prefill forwards")
        self._m_prefill_calls = m.counter(
            "serve_prefill_calls_total", "prefill forward calls made")
        self._m_prefill_capacity = m.counter(
            "serve_prefill_capacity_tokens_total",
            "token rows of the prefill calls made, padding included")
        self._m_decode_pages = m.counter(
            "serve_decode_pages_walked_total",
            "pool pages the paged decode kernel walks per layer, summed over "
            "decode calls (each lane's live groups rounded up to P pages)")
        self._m_decode_table_pages = m.counter(
            "serve_decode_table_pages_total",
            "block-table entries of those decode calls (lanes x max_blocks)")
        self._m_preemptions = m.counter(
            "serve_preemptions_total", "residents evicted on OutOfBlocks")
        self._m_swap_outs = m.counter(
            "serve_swap_outs_total", "preemptions served by host swap-out")
        self._m_swap_ins = m.counter(
            "serve_swap_ins_total", "swapped prefixes restored to the pool")
        self._m_draft_proposed = m.counter(
            "serve_draft_proposed_total", "speculative draft tokens proposed")
        self._m_draft_accepted = m.counter(
            "serve_draft_accepted_total", "draft tokens that survived verify")
        self._m_blocks_used = m.gauge(
            "serve_pool_blocks_used",
            "pool blocks referenced by live chains (excludes prefix-cache "
            "retained blocks; see serve_prefix_cache_blocks_retained)")
        self._m_slots = m.gauge(
            "serve_slots_occupied", "scheduler slots currently resident")
        self._m_step_ms = m.histogram(
            "serve_step_ms", "decode/verify macro-step wall milliseconds")
        self._m_ttft_ms = m.histogram(
            "serve_ttft_ms", "request arrival to first token, wall ms")
        self._m_phase = {p: m.counter(f"serve_phase_{p}_ms_total",
                                      f"self wall ms spent in the {p} phase")
                         for p in PHASES}
        self._m_steps = m.counter("serve_steps_total", "step() calls")
        self._m_step_wall = m.counter(
            "serve_step_wall_ms_total",
            "wall ms inside step() (the phase totals sum to it)")
        # prefix-cache family (always registered; zero-valued when the cache
        # is off so exported metric sets stay schema-stable for check_trace)
        self._m_pc_hits = m.counter(
            "serve_prefix_cache_hits_total",
            "admissions that reused >=1 cached prefix block")
        self._m_pc_misses = m.counter(
            "serve_prefix_cache_misses_total",
            "admissions whose prompt missed the prefix cache")
        self._m_pc_hit_tokens = m.counter(
            "serve_prefix_cache_hit_tokens_total",
            "prompt tokens served from cached blocks instead of prefill")
        self._m_pc_cow = m.counter(
            "serve_prefix_cache_cow_total",
            "copy-on-write block copies (write into a shared block)")
        self._m_pc_retained = m.gauge(
            "serve_prefix_cache_blocks_retained",
            "zero-refcount cached blocks held in the reclaimable LRU")
        self._m_pc_cached = m.gauge(
            "serve_prefix_cache_blocks_cached",
            "physical blocks with a registered prefix-hash claim")
        # pool family (always registered; a float pool reports quantized=0 so
        # exported metric sets stay schema-stable for check_trace — same
        # contract as the prefix-cache family above)
        self._pool_bpt = self.pool.bytes_per_token()
        self._m_pool_quantized = m.gauge(
            "serve_pool_quantized",
            "1 when the latent pool stores int8 rows + scales, else 0")
        self._m_pool_bpt = m.gauge(
            "serve_pool_bytes_per_token",
            "device bytes per pooled token across all layers and streams")
        self._m_pool_bytes = m.gauge(
            "serve_pool_allocated_bytes",
            "device bytes of pool blocks currently allocated to sequences")
        self._m_pool_quantized.set(1 if self.pool.quantized else 0)
        self._m_pool_bpt.set(self._pool_bpt)
        self._cow_synced = 0                # pool.cow_copies already metered
        # sparse-decode family — registered ONLY when sparse decode is on
        # (unlike the always-on families above, the summary leaves and
        # selection stage simply don't exist in a dense run; check_trace
        # enforces the family all-or-nothing instead of always-present)
        self._sparse_steps = 0              # decode forwards with sparse on
        self._sparse_selected = 0           # Σ blocks attended across lanes
        self._sparse_candidate = 0          # Σ resident blocks across lanes
        if scfg.sparse_topk_blocks > 0:
            self._m_sparse_topk = m.gauge(
                "serve_sparse_topk",
                "top-k blocks scored into each sparse decode selection")
            self._m_sparse_recent = m.gauge(
                "serve_sparse_recent",
                "newest blocks always attended by sparse decode")
            self._m_sparse_steps = m.counter(
                "serve_sparse_steps_total",
                "decode forwards that ran with sparse block selection")
            self._m_sparse_selected = m.counter(
                "serve_sparse_selected_blocks_total",
                "blocks attended across all sparse-decode lanes")
            self._m_sparse_candidate = m.counter(
                "serve_sparse_candidate_blocks_total",
                "resident blocks eligible across all sparse-decode lanes")
            self._m_sparse_hist = m.histogram(
                "serve_sparse_selected_blocks",
                "blocks attended per lane per sparse decode forward")
            self._m_sparse_topk.set(scfg.sparse_topk_blocks)
            self._m_sparse_recent.set(scfg.sparse_recent_blocks)
        # the draft shares params unless a real rank truncation is requested
        self.draft_params = (
            lm.make_draft_params(params, cfg, scfg.draft_rank)
            if scfg.speculate_k > 0 else None)

        def _prefill(params, buffers, tokens, pages, slot_mapping):
            return lm.apply_prefill_paged(params, buffers, cfg,
                                          {"tokens": tokens}, pages,
                                          slot_mapping, moe_impl=moe_impl,
                                          mesh=mesh)

        def _prefill_batch(params, buffers, tokens, pages, slot_mapping,
                           chunk_starts, block_tables, prefix_lens):
            return lm.apply_prefill_paged(params, buffers, cfg,
                                          {"tokens": tokens}, pages,
                                          slot_mapping,
                                          chunk_start=chunk_starts,
                                          block_tables=block_tables,
                                          prefix_lens=prefix_lens,
                                          block_size=scfg.block_size,
                                          moe_impl=moe_impl, mesh=mesh)

        def _decode(params, buffers, tokens, pages, slot_mapping,
                    block_tables, lengths):
            return lm.apply_decode_paged(params, buffers, cfg,
                                         {"tokens": tokens}, pages,
                                         slot_mapping, block_tables, lengths,
                                         block_size=scfg.block_size,
                                         use_kernel=scfg.use_kernel,
                                         sparse_topk=scfg.sparse_topk_blocks,
                                         sparse_recent=scfg.sparse_recent_blocks,
                                         moe_impl=moe_impl, mesh=mesh)

        def _verify(params, buffers, tokens, pages, slot_mapping,
                    block_tables, q_offsets, lengths):
            return lm.apply_verify_paged(params, buffers, cfg,
                                         {"tokens": tokens}, pages,
                                         slot_mapping, block_tables,
                                         q_offsets, lengths,
                                         block_size=scfg.block_size,
                                         use_kernel=scfg.use_kernel,
                                         moe_impl=moe_impl, mesh=mesh)

        # donate the pages so XLA updates the pool in place rather than
        # copying every block each step (donation is unsupported + noisy on CPU)
        donate = () if jax.default_backend() == "cpu" else (3,)
        self._prefill = jax.jit(_prefill, donate_argnums=donate)
        self._prefill_batch = jax.jit(_prefill_batch, donate_argnums=donate)
        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._verify = jax.jit(_verify, donate_argnums=donate)
        self._sample = jax.jit(sample_tokens)

    # -- observability ------------------------------------------------------
    @contextlib.contextmanager
    def _phase(self, name: str, **args):
        """Attribute the enclosed wall time to step phase ``name`` — into
        ``phase_ms``, the metrics registry, a ``sched.<name>`` profiler
        annotation and (when tracing) a span on the ``scheduler`` track.
        Phases nest: entering one charges the enclosing phase its time so
        far, which resumes on exit, so every total is self time and none
        double-counts."""
        with jax.profiler.TraceAnnotation(f"sched.{name}"), \
                self.trace.span(name, track="scheduler", cat="phase", **args):
            stack = self._phase_stack
            now = time.perf_counter()
            if stack:
                self._charge(stack[-1], now)
            frame = [name, now]
            stack.append(frame)
            try:
                yield
            finally:
                now = time.perf_counter()
                stack.pop()
                self._charge(frame, now)
                if stack:
                    stack[-1][1] = now

    def _charge(self, frame: List[Any], now: float) -> None:
        """Charge ``frame``'s phase its time since it last resumed."""
        dt_ms = (now - frame[1]) * 1e3
        frame[1] = now
        self._phase_ms[frame[0]] += dt_ms
        self._m_phase[frame[0]].inc(dt_ms)

    def _stuck_report(self, max_steps: int) -> str:
        """Diagnostic payload for the did-not-drain failure: per-request
        status of every resident and waiter plus the tracer's recent event
        tail, so a stuck-pool run is debuggable from the exception alone."""
        lines = [f"scheduler did not drain in {max_steps} steps"]
        lines.append(f"pool: {self.pool.allocator.num_used}/"
                     f"{self.pool.num_blocks} blocks used, "
                     f"{self.pool.allocator.num_free} free, "
                     f"block_size={self.pool.block_size}")
        if self.bm.prefix is not None:
            pc = self.bm.prefix
            lines.append(f"prefix cache: {pc.num_cached} cached, "
                         f"{pc.num_retained} retained, hits={pc.hits} "
                         f"misses={pc.misses} cow={self.pool.cow_copies}")
        for i, r in enumerate(self.slots):
            if r is None:
                lines.append(f"slot{i}: empty")
                continue
            lines.append(
                f"slot{i}: uid={r.uid} prefill={r.prefill_pos}/"
                f"{len(r.prefill_source())} generated="
                f"{len(r.generated)}/{r.max_new_tokens} "
                f"pool_len={self.pool.length(r.uid)} "
                f"blocks={len(self.pool.block_table(r.uid))} "
                f"preempted={len(r.preempted_at)}x")
        for r in list(self.waiting)[:8]:
            lines.append(f"waiting: uid={r.uid} arrival={r.arrival:.1f} "
                         f"prefill_src={len(r.prefill_source())} "
                         f"swapped={r.swapped is not None} "
                         f"preempted={len(r.preempted_at)}x")
        if len(self.waiting) > 8:
            lines.append(f"waiting: … {len(self.waiting) - 8} more")
        lines.append(self.trace.format_tail(40))
        return "\n".join(lines)

    # -- request intake -----------------------------------------------------
    def submit(self, req: Request) -> None:
        req.max_new_tokens = min(req.max_new_tokens, self.scfg.max_new_tokens)
        assert len(req.prompt) + req.max_new_tokens <= self.scfg.max_len, \
            (len(req.prompt), req.max_new_tokens, self.scfg.max_len)
        if self._worst_case_blocks(req) > self.scfg.num_blocks:
            raise OutOfBlocks(
                f"request {req.uid} needs {self._worst_case_blocks(req)} blocks "
                f"worst-case but the pool only has {self.scfg.num_blocks} — "
                f"it could never be admitted")
        req.submit_wall = time.perf_counter()
        self.waiting.append(req)
        self.naive_blocks += self._worst_case_blocks(req)
        self._m_submitted.inc()
        self.trace.instant("submit", track="scheduler", cat="request",
                           uid=req.uid, prompt=len(req.prompt),
                           budget=req.max_new_tokens, arrival=req.arrival)

    def _worst_case_blocks(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.max_new_tokens) // self.scfg.block_size)

    def _first_alloc_tokens(self, req: Request) -> int:
        """Pool tokens the request needs *immediately* at admission: the
        swapped-out prefix being restored, the first prefill chunk, or (one-
        shot mode) the whole prefill source."""
        if req.swapped is not None:
            return req.swapped.length
        src = len(req.prefill_source())
        chunk = self.scfg.prefill_chunk_tokens
        return min(chunk, src) if chunk > 0 else src

    # -- admission ----------------------------------------------------------
    def _try_admit(self) -> int:
        admitted = 0
        while self.waiting and self.waiting[0].arrival <= self.t:
            slot = next((i for i, s in enumerate(self.slots) if s is None), None)
            if slot is None:
                break
            req = self.waiting[0]
            if not self.bm.can_admit(self._first_alloc_tokens(req),
                                     self._worst_case_blocks(req)):
                break                       # head-of-line waits for blocks
            self.waiting.popleft()
            self._admit(slot, req)
            admitted += 1
        return admitted

    def _admit(self, slot: int, req: Request) -> None:
        """Claim a slot (restoring a swapped-out prefix if there is one).
        Block allocation otherwise happens on demand, chunk by chunk, in
        ``_prefill_work`` — and prefill itself is interleaved with decode.
        With the prefix cache on, a fresh (non-swapped) admission first
        probes the cache with its prefill source: hit blocks splice into the
        chain and ``prefill_pos`` jumps past them, so prefill resumes at the
        first miss (the final prompt token is never cache-served — its
        logits row seeds the first sampled token)."""
        if req.swapped is not None:
            with self._phase("swap", direction="in", uid=req.uid):
                self.bm.swap_in(req.uid, req.swapped)
            req.swapped = None
            self._m_swap_ins.inc()
        elif self.bm.prefix is not None and req.prefill_pos == 0:
            self._lookup_prefix(req)
        self.bm.register(req.uid, self._worst_case_blocks(req))
        self.slots[slot] = req
        self.trace.begin(f"req{req.uid}", track=f"slot{slot}", cat="request",
                         uid=req.uid)
        self.trace.instant("admit", track="scheduler", cat="request",
                           uid=req.uid, slot=slot,
                           queued_steps=self.t - req.arrival)

    def _lookup_prefix(self, req: Request) -> None:
        """Probe the prefix cache with the request's prefill source and
        splice any hit blocks into its (fresh) chain.  After a recompute
        preemption the source is prompt + generated, so a re-admission can
        hit its *own* earlier blocks (retained at eviction) and skip most of
        the recompute prefill."""
        src = req.prefill_source()
        hit = self.bm.lookup_prefix(req.uid, src)
        if hit:
            req.prefill_pos = hit
            req.prefix_hit_tokens += hit
            self._m_pc_hits.inc()
            self._m_pc_hit_tokens.inc(hit)
            self.trace.instant("prefix_hit", track="scheduler", cat="cache",
                               uid=req.uid, tokens=hit,
                               blocks=hit // self.scfg.block_size)
        else:
            self._m_pc_misses.inc()
            self.trace.instant("prefix_miss", track="scheduler", cat="cache",
                               uid=req.uid, tokens=len(src))

    # -- preemption ---------------------------------------------------------
    def _decode_ready(self, req: Request) -> bool:
        """Prefill source fully cached and the next input token sampled."""
        return bool(req.generated) and \
            req.prefill_pos >= len(req.prefill_source())

    def _youngest_slot(self) -> Optional[int]:
        occ = [(s.arrival, s.uid, i)
               for i, s in enumerate(self.slots) if s is not None]
        return max(occ)[2] if occ else None

    def _preempt(self, slot: int) -> None:
        """Evict the resident in ``slot`` and requeue it at the head of the
        waiting line.  ``eviction="recompute"`` frees its blocks and arms a
        recompute-prefill over prompt + generated-so-far (whose final logits
        re-produce exactly the token the interrupted decode step would have);
        ``eviction="swap"`` copies the cached prefix to host memory instead,
        restored block-exactly at re-admission."""
        req = self.slots[slot]
        req.preempted_at.append(len(req.generated))
        if self.scfg.eviction == "swap":
            # cached tokens from *request* state: prompt + generated minus the
            # not-yet-written last token (decode-ready), or the prefill cursor
            if self._decode_ready(req):
                cached = len(req.prompt) + len(req.generated) - 1
                req.prefill_src = np.concatenate(
                    [req.prompt,
                     np.asarray(req.generated[:-1], np.int32)])
                req.prefill_pos = cached
            else:
                cached = req.prefill_pos
            with self._phase("swap", direction="out", uid=req.uid):
                req.swapped = self.bm.preempt_swap_out(req.uid, cached)
            if req.swapped is not None:
                self._m_swap_outs.inc()
        else:
            if req.generated:
                req.prefill_src = np.concatenate(
                    [req.prompt, np.asarray(req.generated, np.int32)])
            req.prefill_pos = 0
            self.bm.preempt_recompute(req.uid)
        self._m_preemptions.inc()
        self.trace.end(f"req{req.uid}", track=f"slot{slot}", cat="request",
                       reason="preempt")
        self.trace.instant("preempt", track="scheduler", cat="request",
                           uid=req.uid, slot=slot, mode=self.scfg.eviction,
                           generated=len(req.generated))
        self.slots[slot] = None
        self.waiting.appendleft(req)

    def _grow_or_preempt(self, req: Request, length: int,
                         write_from: Optional[int] = None) -> bool:
        """Grow ``req``'s chain to ``length`` tokens, preempting the youngest
        resident until the allocation fits.  Returns False iff ``req`` itself
        was the youngest and got evicted (caller drops it this step).
        Terminates: every retry removes one resident, and a lone resident's
        worst case fits the pool (enforced at ``submit``).

        ``write_from`` is the copy-on-write barrier: the caller is about to
        write pool positions ``[write_from, length)``, so any *shared* block
        covering that range is privatized first (``BlockManager.
        prepare_write``).  The COW copy itself allocates, so it lives inside
        the same OutOfBlocks-preempt retry loop as the growth."""
        while True:
            try:
                self.bm.grow(req.uid, length)
                if write_from is not None:
                    self.bm.prepare_write(req.uid, write_from, length)
                return True
            except OutOfBlocks:
                slot = self._youngest_slot()
                if slot is None:
                    raise
                victim = self.slots[slot]
                self._preempt(slot)
                if victim is req:
                    return False

    # -- single-row sampling ------------------------------------------------
    def _sample_one(self, req: Request, row, count: int) -> int:
        """One token from a single logits row with ``req``'s sampling params
        and the count-folded PRNG — exactly the draw the batched decode
        sampler would make for token index ``count``.  The single source of
        the per-token PRNG discipline for the prefill first-token and the
        speculative bonus token (the golden preemption/speculation stream
        invariants both hang off it)."""
        if req.temperature <= 0:
            return int(np.argmax(np.asarray(row)))
        return int(np.asarray(self._sample(
            jnp.asarray(row)[None],
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32),
            jnp.asarray([req.seed], jnp.int32),
            jnp.asarray([count], jnp.int32)))[0])

    # -- chunked / batched prefill ------------------------------------------
    def _sample_prefill_token(self, req: Request, last_row) -> None:
        """Sample the token that follows a completed (re)prefill from its
        final logits row.  The PRNG count is ``len(generated)``: 0 for a
        fresh prompt (the request's first token), ``k`` after a recompute —
        re-drawing exactly the token the interrupted decode step would have
        produced, so preemption never changes the stream.  (Speculative
        caveat: with a truncated draft at temperature > 0 the interrupted
        token may originally have come through the accept/residual path,
        whose outcome depends on window alignment — the redraw here keeps
        the stream correctly *distributed* but, like any window-alignment
        shift, can change the realized sample; greedy and full-rank-draft
        streams are exactly invariant.)"""
        tok = self._sample_one(req, last_row, len(req.generated))
        req.generated.append(tok)
        self._m_decoded.inc()               # prefill-sampled tokens count too
        if req.first_token_step < 0:        # TTFT survives preemption
            req.first_token_wall = time.perf_counter()
            req.first_token_step = self.t
            self._m_ttft_ms.observe((req.first_token_wall - req.submit_wall)
                                    * 1e3)
            self.trace.instant("first_token", track="scheduler",
                               cat="request", uid=req.uid, step=self.t)

    def _run_oneshot(self, slot: int, req: Request) -> None:
        """Whole-source causal prefill in one call, padded to the bucket.
        A prefix-cache hit leaves ``prefill_pos > 0``: only the uncovered
        tail runs, as one resumed chunk attending to the cached prefix
        through the block table (the chunked machinery's ``chunk_start`` /
        ``prefix_lens`` path with a single lane)."""
        src = req.prefill_source()
        sp = len(src)
        pos = req.prefill_pos
        with self._phase("grow"):
            grown = self._grow_or_preempt(req, sp, write_from=pos)
        if not grown:
            return                          # req evicted itself — retry later
        n = sp - pos
        pad = -(-n // self.scfg.prefill_bucket) * self.scfg.prefill_bucket
        with self._phase("pack"):
            tokens = np.zeros((1, pad), np.int32)
            tokens[0, :n] = src[pos:]
            sm = self.pool.prefill_slot_mapping(req.uid, pos, n, pad)[None]
            if pos > 0:
                bt = self.pool.block_table_array(
                    [req.uid], self.scfg.max_blocks_per_seq)
                starts = np.asarray([pos], np.int32)
        with self._phase("prefill", lanes=1, tokens=n):
            if pos == 0:
                logits, self.pool.pages = self._prefill(
                    self.params, self.buffers, jnp.asarray(tokens),
                    self.pool.pages, jnp.asarray(sm))
            else:
                logits, self.pool.pages = self._prefill_batch(
                    self.params, self.buffers, jnp.asarray(tokens),
                    self.pool.pages, jnp.asarray(sm), jnp.asarray(starts),
                    jnp.asarray(bt), jnp.asarray(starts))
            jax.block_until_ready(logits)
        self.trace.instant("prefill_chunk", track=f"slot{slot}",
                           cat="request", uid=req.uid, start=pos, n=n)
        self._m_prefill_tokens.inc(n)
        self._m_prefill_calls.inc()
        self._m_prefill_capacity.inc(pad)
        req.prefill_pos = sp
        if self.bm.prefix is not None:
            self.bm.register_prefix(req.uid, src[:sp])
        self.prefill_chunks += 1
        self._prefill_lanes_total += 1
        with self._phase("sample"):
            self._sample_prefill_token(req, logits[0, n - 1])
        self._maybe_finish(slot, req.generated[-1])

    def _prefill_work(self) -> None:
        """Advance mid-prefill residents.  One-shot mode (``chunk == 0``):
        each pending prompt prefills whole, FCFS.  Chunked mode: pack the
        next ``prefill_chunk_tokens``-token chunk of up to ``chunk_lanes``
        lanes (FCFS by arrival) into ONE fixed-shape forward — per-lane
        ``chunk_start``/``prefix_lens`` vectors give every lane its own
        offset causal mask against its own paged prefix."""
        scfg = self.scfg
        chunk = scfg.prefill_chunk_tokens
        if chunk <= 0:
            while True:
                cand = [(s.arrival, s.uid, i)
                        for i, s in enumerate(self.slots)
                        if s is not None
                        and s.prefill_pos < len(s.prefill_source())]
                if not cand:
                    return
                _, _, slot = min(cand)
                self._run_oneshot(slot, self.slots[slot])
        # chunked: FCFS-select lanes, growing each chain for its chunk
        # (growth may preempt residents — including already-selected lanes)
        cand = sorted((s.arrival, s.uid, i)
                      for i, s in enumerate(self.slots)
                      if s is not None
                      and s.prefill_pos < len(s.prefill_source()))
        if not cand:
            return
        selected: List[Tuple[int, Request, int, int]] = []
        with self._phase("grow"):
            for _, _, slot in cand:
                if len(selected) >= scfg.chunk_lanes:
                    break
                req = self.slots[slot]
                if req is None:             # evicted by an earlier growth
                    continue
                n = min(chunk, len(req.prefill_source()) - req.prefill_pos)
                if self._grow_or_preempt(req, req.prefill_pos + n,
                                         write_from=req.prefill_pos):
                    selected.append((slot, req, req.prefill_pos, n))
        selected = [(s, r, st, n) for s, r, st, n in selected
                    if self.slots[s] is r]  # drop lanes evicted after selection
        if not selected:
            return
        lanes = scfg.chunk_lanes
        with self._phase("pack"):
            tokens = np.zeros((lanes, chunk), np.int32)
            sms = np.full((lanes, chunk), self.pool.oob_slot, np.int32)
            starts = np.zeros((lanes,), np.int32)
            seq_ids: List[Optional[int]] = [None] * lanes
            for lane, (slot, req, start, n) in enumerate(selected):
                tokens[lane, :n] = req.prefill_source()[start:start + n]
                sms[lane] = self.pool.prefill_slot_mapping(req.uid, start, n,
                                                           chunk)
                starts[lane] = start        # chunk offset == cached prefix len
                seq_ids[lane] = req.uid
            bt = self.pool.block_table_array(seq_ids, scfg.max_blocks_per_seq)
        n_toks = sum(n for _, _, _, n in selected)
        with self._phase("prefill", lanes=len(selected), tokens=n_toks):
            logits, self.pool.pages = self._prefill_batch(
                self.params, self.buffers, jnp.asarray(tokens), self.pool.pages,
                jnp.asarray(sms), jnp.asarray(starts), jnp.asarray(bt),
                jnp.asarray(starts))
            jax.block_until_ready(logits)
        self._m_prefill_tokens.inc(n_toks)
        self._m_prefill_calls.inc()
        self._m_prefill_capacity.inc(lanes * chunk)
        self.prefill_chunks += 1
        self._prefill_lanes_total += len(selected)
        for lane, (slot, req, start, n) in enumerate(selected):
            self.trace.instant("prefill_chunk", track=f"slot{slot}",
                               cat="request", uid=req.uid, start=start, n=n)
            req.prefill_pos = start + n
            if self.bm.prefix is not None:
                # register freshly completed full prompt blocks after every
                # chunk, so requests arriving mid-prefill can already hit
                self.bm.register_prefix(
                    req.uid, req.prefill_source()[:req.prefill_pos])
            if req.prefill_pos >= len(req.prefill_source()):
                with self._phase("sample"):
                    self._sample_prefill_token(req, logits[lane, n - 1])
                self._maybe_finish(slot, req.generated[-1])

    # -- retirement ---------------------------------------------------------
    def _maybe_finish(self, slot: int, token: int) -> None:
        req = self.slots[slot]
        if self.scfg.eos_id is not None and token == self.scfg.eos_id:
            req.finish_reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            req.finish_reason = "budget"
        else:
            return
        req.finish_step = self.t
        self.bm.release(req.uid)            # blocks recycle immediately
        self.finished.append(req)
        self.slots[slot] = None
        self._m_completed.inc()
        self.trace.end(f"req{req.uid}", track=f"slot{slot}", cat="request",
                       reason=req.finish_reason)
        self.trace.instant("retire", track="scheduler", cat="request",
                           uid=req.uid, reason=req.finish_reason,
                           tokens=len(req.generated))

    def _blocks_referenced(self) -> int:
        """Pool blocks referenced by live chains — allocator usage minus the
        refcount-0 blocks the prefix cache merely retains for reuse (those
        are reclaimable, and admission already treats them as free)."""
        retained = self.bm.prefix.num_retained if self.bm.prefix else 0
        return self.pool.allocator.num_used - retained

    # -- one scheduler iteration -------------------------------------------
    def step(self) -> bool:
        """Admit + chunk-prefill + decode (or draft/verify) once.  Returns
        False when drained.

        Times itself: the call's wall lands in ``serve_step_wall_ms_total``
        and ``step_wall_ms_total``, and the step's own remainder outside
        every phase in ``other``, so the phase totals sum to it whoever
        drives the loop."""
        with jax.profiler.TraceAnnotation("sched.step"), \
                self.trace.span("step", track="scheduler", cat="step"):
            start = time.perf_counter()
            frame = ["other", start]
            self._phase_stack.append(frame)
            try:
                return self._step()
            finally:
                now = time.perf_counter()
                self._phase_stack.pop()
                self._charge(frame, now)
                dt_ms = (now - start) * 1e3
                self._step_wall_ms_total += dt_ms
                self._m_step_wall.inc(dt_ms)
                self._m_steps.inc()

    def _step(self) -> bool:
        with self._phase("admit"):
            self._try_admit()
        self._prefill_work()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        self.peak_slots = max(self.peak_slots, len(occupied))
        # "used" means referenced by a live chain: prefix-cache-retained
        # blocks (refcount 0, LRU-parked) are reclaimable on demand, so they
        # count as free for admission and must not show as in-use here —
        # they are reported separately via serve_prefix_cache_blocks_retained.
        referenced = self._blocks_referenced()
        self._m_blocks_used.set(referenced)
        self._m_slots.set(len(occupied))
        self.trace.counter("pool_blocks_used", referenced, track="pool")
        alloc_bytes = (self.pool.allocator.num_used * self.scfg.block_size
                       * self._pool_bpt)
        self._m_pool_bytes.set(alloc_bytes)
        self.trace.counter("pool_allocated_bytes", alloc_bytes, track="pool")
        self.trace.counter("slots_occupied", len(occupied), track="scheduler")
        if self.bm.prefix is not None:
            if self.pool.cow_copies > self._cow_synced:
                self._m_pc_cow.inc(self.pool.cow_copies - self._cow_synced)
                self._cow_synced = self.pool.cow_copies
            self._m_pc_retained.set(self.bm.prefix.num_retained)
            self._m_pc_cached.set(self.bm.prefix.num_cached)
            self.trace.counter("prefix_blocks_retained",
                               self.bm.prefix.num_retained, track="pool")
        # decode lanes: slots whose prefill source is fully cached, oldest
        # first — chain growth may preempt the youngest residents (who then
        # sit out this step in the queue).
        order = sorted((self.slots[i].arrival, self.slots[i].uid, i)
                       for i in occupied if self._decode_ready(self.slots[i]))
        if self.scfg.speculate_k > 0:
            progressed = self._speculative_step(order)
        else:
            progressed = self._decode_step(order)
        if not progressed:
            if all(s is None for s in self.slots) and not self.waiting:
                return False
            self.t += 1                     # waiting on arrivals or prefill
            return True
        self.t += 1
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def _decode_step(self, order) -> bool:
        """Plain one-token decode over every decode-ready lane (one forward).
        Returns False when no lane was live (waiting on arrivals/prefill)."""
        grown: Dict[int, int] = {}          # slot → position of the new token
        with self._phase("grow"):
            for _, _, i in order:
                req = self.slots[i]
                if req is None:
                    continue                # evicted by an older lane's growth
                cur = self.pool.length(req.uid)
                if self._grow_or_preempt(req, cur + 1, write_from=cur):
                    grown[i] = cur
        active = [i for i in grown if self.slots[i] is not None]
        self._occupancy.append(
            self._blocks_referenced() / self.pool.num_blocks)
        self._occupancy_retained.append(
            self.pool.allocator.num_used / self.pool.num_blocks)
        if not active:
            return False

        scfg = self.scfg
        B = scfg.max_slots
        with self._phase("pack"):
            tokens = np.zeros((B, 1), np.int32)
            lengths = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            top_ps = np.ones((B,), np.float32)
            seeds = np.zeros((B,), np.int32)
            counts = np.zeros((B,), np.int32)
            seq_ids: List[Optional[int]] = [None] * B
            positions = [0] * B
            for i in active:
                req = self.slots[i]
                cur = grown[i]              # chain already grown above
                tokens[i, 0] = req.generated[-1]
                lengths[i] = cur + 1
                seq_ids[i] = req.uid
                positions[i] = cur
                temps[i] = req.temperature
                top_ps[i] = req.top_p
                seeds[i] = req.seed
                counts[i] = len(req.generated)  # token index within request
            sm = self.pool.slot_mapping(seq_ids, positions)
            bt = self.pool.block_table_array(seq_ids, scfg.max_blocks_per_seq)

        t0 = time.perf_counter()
        with self._phase("decode", lanes=len(active)):
            logits, self.pool.pages = self._decode(self.params, self.buffers,
                                                   jnp.asarray(tokens),
                                                   self.pool.pages,
                                                   jnp.asarray(sm), jnp.asarray(bt),
                                                   jnp.asarray(lengths))
            jax.block_until_ready(logits)
        with self._phase("sample"):
            if np.any(temps > 0):
                nxt = np.asarray(self._sample(logits[:, -1, :], jnp.asarray(temps),
                                              jnp.asarray(top_ps),
                                              jnp.asarray(seeds),
                                              jnp.asarray(counts)))
            else:                           # all-greedy step: skip the
                nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))  # sampler
        self._step_wall_ms.append((time.perf_counter() - t0) * 1e3)
        self._m_step_ms.observe(self._step_wall_ms[-1])
        self._lane_steps += len(active)
        mb = scfg.max_blocks_per_seq
        P = pages_per_step(scfg.block_size, mb)
        self._m_decode_pages.inc(
            int(np.sum(-(-lengths // (P * scfg.block_size)))) * P)
        self._m_decode_table_pages.inc(B * mb)
        if scfg.sparse_topk_blocks > 0:
            # the forward already ran the selection on device; mirror the
            # arithmetic here (ceil-div resident chain vs. selection width)
            # rather than pulling sel_tables back across the transfer fence
            bs = scfg.block_size
            width = min(scfg.sparse_topk_blocks + scfg.sparse_recent_blocks,
                        scfg.max_blocks_per_seq)
            step_sel = step_cand = 0
            for i in active:
                n_chain = -(-int(lengths[i]) // bs)
                sel = min(width, n_chain)
                step_sel += sel
                step_cand += n_chain
                self._m_sparse_hist.observe(sel)
            self._sparse_steps += 1
            self._sparse_selected += step_sel
            self._sparse_candidate += step_cand
            self._m_sparse_steps.inc()
            self._m_sparse_selected.inc(step_sel)
            self._m_sparse_candidate.inc(step_cand)
            self.trace.instant("sparse_select", track="pool", cat="cache",
                               selected=step_sel, candidate=step_cand)
        for i in active:
            req = self.slots[i]
            tok = int(nxt[i])
            req.generated.append(tok)
            self._decode_appended += 1
            self._m_decoded.inc()
            self._maybe_finish(i, tok)
        return True

    # -- speculative decode: draft / verify macro-step -----------------------
    def _speculative_step(self, order) -> bool:
        """Draft + verify for every decode-ready lane (docs/serving.md):

        1. grow each lane's chain for its whole window up front (``w`` draft
           slots + the pending token's slot, ``w = min(k, budget left)``) —
           growth may preempt, exactly like plain decode's one-token growth;
        2. ``k`` sequential decode forwards of the rank-truncated draft
           propose tokens (batched over lanes; draft streams scatter into the
           pool so later draft tokens attend to earlier ones);
        3. ONE full-model verify forward re-scores all ``k+1`` window
           positions per lane against the paged prefix — overwriting the
           window's pool slots with full-model streams;
        4. per lane, accept a prefix by rejection sampling (greedy: exact
           argmax match) and roll the chain back over rejected tokens via
           ``BlockManager.truncate``.

        Between steps the request/pool invariant is exactly plain decode's
        (cache = prompt + generated[:-1], last token pending), so preemption,
        swap and recompute machinery work unchanged."""
        scfg = self.scfg
        k = scfg.speculate_k
        B = scfg.max_slots
        W = k + 1
        windows: Dict[int, Tuple[int, int]] = {}   # slot → (cur, w)
        with self._phase("grow"):
            for _, _, i in order:
                req = self.slots[i]
                if req is None:
                    continue                # evicted by an older lane's growth
                cur = self.pool.length(req.uid)
                w = min(k, req.max_new_tokens - len(req.generated))
                if self._grow_or_preempt(req, cur + w + 1, write_from=cur):
                    windows[i] = (cur, w)
        active = [i for i in windows if self.slots[i] is not None]
        self._occupancy.append(
            self._blocks_referenced() / self.pool.num_blocks)
        self._occupancy_retained.append(
            self.pool.allocator.num_used / self.pool.num_blocks)
        if not active:
            return False

        t0 = time.perf_counter()
        # block tables are invariant for the whole macro-step (every chain
        # was grown to its full window above): build them once, reuse for
        # all k draft forwards and the verify forward.  Lanes that fall out
        # of a shorter window mid-draft are masked by length 0 + oob slots.
        with self._phase("pack"):
            seq_ids_act: List[Optional[int]] = [None] * B
            for i in active:
                seq_ids_act[i] = self.slots[i].uid
            bt = jnp.asarray(self.pool.block_table_array(
                seq_ids_act, scfg.max_blocks_per_seq))
        # -- draft: k cheap truncated-rank decode forwards, batched over lanes
        drafts: Dict[int, List[int]] = {i: [] for i in active}
        dlogits: Dict[int, List[np.ndarray]] = {i: [] for i in active}
        xs = {i: self.slots[i].generated[-1] for i in active}
        for j in range(k):
            live = [i for i in active if windows[i][1] > j]
            if not live:
                break
            with self._phase("pack"):
                tokens = np.zeros((B, 1), np.int32)
                lengths = np.zeros((B,), np.int32)
                temps = np.zeros((B,), np.float32)
                top_ps = np.ones((B,), np.float32)
                seeds = np.zeros((B,), np.int32)
                counts = np.zeros((B,), np.int32)
                seq_ids: List[Optional[int]] = [None] * B
                positions = [0] * B
                for i in live:
                    req = self.slots[i]
                    cur, _ = windows[i]
                    tokens[i, 0] = xs[i]
                    lengths[i] = cur + j + 1
                    seq_ids[i] = req.uid
                    positions[i] = cur + j
                    temps[i] = req.temperature
                    top_ps[i] = req.top_p
                    seeds[i] = req.seed
                    counts[i] = len(req.generated) + j  # proposal's index
                sm = self.pool.slot_mapping(seq_ids, positions)
            with self._phase("draft", j=j, lanes=len(live)):
                logits, self.pool.pages = self._decode(
                    self.draft_params, self.buffers, jnp.asarray(tokens),
                    self.pool.pages, jnp.asarray(sm), bt,
                    jnp.asarray(lengths))
                self.draft_forwards += 1
                sampled = bool(np.any(temps > 0))
                if sampled:
                    nxt = np.asarray(self._sample(
                        logits[:, -1, :], jnp.asarray(temps),
                        jnp.asarray(top_ps),
                        jnp.asarray(seeds), jnp.asarray(counts)))
                    # draft distributions are only needed for the accept
                    # ratio — all-greedy macro-steps skip the host transfer
                    rows = np.asarray(logits[:, -1, :])
                else:
                    nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
                    rows = None
            for i in live:
                drafts[i].append(int(nxt[i]))
                if rows is not None:
                    dlogits[i].append(rows[i])
                xs[i] = int(nxt[i])

        # -- verify: all k+1 window positions per lane in ONE forward --------
        with self._phase("pack"):
            tokens = np.zeros((B, W), np.int32)
            sms = np.full((B, W), self.pool.oob_slot, np.int32)
            offs = np.zeros((B,), np.int32)
            lengths = np.zeros((B,), np.int32)
            for i in active:
                req = self.slots[i]
                cur, w = windows[i]
                tokens[i, 0] = req.generated[-1]
                tokens[i, 1:1 + w] = drafts[i][:w]
                sms[i] = self.pool.prefill_slot_mapping(req.uid, cur, w + 1,
                                                        W)
                offs[i] = cur
                lengths[i] = cur + w + 1
        with self._phase("verify", lanes=len(active)):
            logits, self.pool.pages = self._verify(
                self.params, self.buffers, jnp.asarray(tokens), self.pool.pages,
                jnp.asarray(sms), bt, jnp.asarray(offs),
                jnp.asarray(lengths))
            rows_all = np.asarray(logits)
        self._step_wall_ms.append((time.perf_counter() - t0) * 1e3)
        self._m_step_ms.observe(self._step_wall_ms[-1])
        self._lane_steps += len(active)

        # -- accept a prefix per lane, roll the chain back over the rest -----
        with self._phase("accept", lanes=len(active)):
            for i in active:
                req = self.slots[i]
                cur, w = windows[i]
                out = self._accept_window(req, drafts[i][:w], dlogits[i][:w],
                                          rows_all[i])
                n_acc = len(out) - 1
                self.bm.truncate(req.uid, cur + n_acc + 1)
                appended = 0
                for tok in out:
                    req.generated.append(tok)
                    appended += 1
                    self._maybe_finish(i, tok)
                    if self.slots[i] is None:
                        break               # EOS/budget mid-window: rest drops
                self._decode_appended += appended
                self._m_decoded.inc(appended)
                # count only accepted drafts that were actually *kept* — an
                # EOS cutting an accepted prefix short must not inflate
                # acceptance (keeps tokens_per_forward == 1 + mean_accepted
                # away from EOS)
                kept = min(n_acc, appended)
                req.spec_proposed += w
                req.spec_accepted += kept
                self.draft_proposed += w
                self.draft_accepted += kept
                self._m_draft_proposed.inc(w)
                self._m_draft_accepted.inc(kept)
                self._spec_windows += 1
        return True

    def _accept_window(self, req: Request, drafts: List[int],
                       dlogits: List[np.ndarray], rows: np.ndarray
                       ) -> List[int]:
        """Decide one lane's verify window.  Returns the tokens to append:
        the accepted draft prefix plus exactly one more — the corrected token
        on the first rejection, or the bonus token when every draft survived.
        ``rows[j]`` is the full model's logits after window token ``j``
        (j = 0 is the pending token), the distribution plain decode would
        have sampled token ``len(generated) + j`` from."""
        out: List[int] = []
        for j, x in enumerate(drafts):
            t_idx = len(req.generated) + j  # generated index of the candidate
            if req.temperature <= 0:
                tgt = int(np.argmax(rows[j]))
                if x != tgt:
                    out.append(tgt)         # greedy correction == plain token
                    return out
                out.append(x)
                continue
            p = nucleus_probs(rows[j], req.temperature, req.top_p)
            q = nucleus_probs(dlogits[j], req.temperature, req.top_p)
            if not speculative_accept(
                    x, p, q, _spec_uniform(req.seed, t_idx, _ACCEPT_SALT)):
                out.append(residual_sample(
                    p, q, _spec_uniform(req.seed, t_idx, _RESID_SALT)))
                return out
            out.append(x)
        # every draft accepted → bonus token from the final verify row, drawn
        # exactly as plain decode would (same count-folded PRNG)
        j = len(drafts)
        out.append(self._sample_one(req, rows[j], len(req.generated) + j))
        return out

    # -- drive to completion ------------------------------------------------
    def run(self, requests: Optional[List[Request]] = None,
            max_steps: int = 100_000) -> ServeReport:
        for r in requests or []:
            self.submit(r)
        t0 = time.perf_counter()
        steps = 0
        while self.step():
            steps += 1
            if steps > max_steps:
                raise RuntimeError(self._stuck_report(max_steps))
        return self.report(time.perf_counter() - t0)

    def report(self, wall_s: float) -> ServeReport:
        fin = self.finished
        decoded = sum(len(r.generated) for r in fin)
        prefill_toks = sum(len(r.prompt) for r in fin)
        ttft_steps = [r.first_token_step - r.arrival for r in fin]
        ttft_ms = [(r.first_token_wall - r.submit_wall) * 1e3 for r in fin]
        pct = lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0
        hw = self.pool.allocator.high_water
        return ServeReport(
            completed=len(fin), decode_steps=len(self._step_wall_ms),
            prefill_tokens=prefill_toks, prefill_chunks=self.prefill_chunks,
            decoded_tokens=decoded,
            wall_s=wall_s, tok_per_s=decoded / max(wall_s, 1e-9),
            ttft_steps_mean=float(np.mean(ttft_steps)) if ttft_steps else 0.0,
            ttft_steps_by_bucket=ttft_by_prompt_bucket(fin),
            ttft_wall_p50_ms=pct(ttft_ms, 50), ttft_wall_p95_ms=pct(ttft_ms, 95),
            step_ms_p50=pct(self._step_wall_ms, 50),
            step_ms_p95=pct(self._step_wall_ms, 95),
            peak_slots=self.peak_slots, pool_high_water_blocks=hw,
            pool_block_size=self.scfg.block_size,
            pool_dtype=str(self.pool.dtype),
            pool_bytes_per_token=self._pool_bpt,
            pool_allocated_bytes_peak=hw * self.scfg.block_size
            * self._pool_bpt,
            naive_blocks=self.naive_blocks,
            block_reuse_ratio=self.naive_blocks / max(hw, 1),
            admission=self.scfg.admission,
            preemptions=self.bm.preemptions,
            preempted_requests=sum(1 for r in fin if r.preempted_at),
            swap_outs=self.bm.swap_outs, swap_ins=self.bm.swap_ins,
            swapped_bytes=self.bm.swapped_bytes,
            mean_occupancy=(float(np.mean(self._occupancy))
                            if self._occupancy else 0.0),
            mean_occupancy_retained=(float(np.mean(self._occupancy_retained))
                                     if self._occupancy_retained else 0.0),
            sparse_topk=self.scfg.sparse_topk_blocks,
            sparse_recent=self.scfg.sparse_recent_blocks,
            sparse_steps=self._sparse_steps,
            mean_selected_blocks=(self._sparse_selected
                                  / max(self._lane_steps, 1)
                                  if self._sparse_steps else 0.0),
            mean_candidate_blocks=(self._sparse_candidate
                                   / max(self._lane_steps, 1)
                                   if self._sparse_steps else 0.0),
            mean_prefill_batch=(self._prefill_lanes_total
                                / max(self.prefill_chunks, 1)),
            speculate_k=self.scfg.speculate_k,
            draft_rank=self.scfg.draft_rank,
            draft_forwards=self.draft_forwards,
            draft_proposed=self.draft_proposed,
            draft_accepted=self.draft_accepted,
            acceptance_rate=self.draft_accepted / max(self.draft_proposed, 1),
            mean_accepted=self.draft_accepted / max(self._spec_windows, 1),
            tokens_per_forward=(self._decode_appended
                                / max(self._lane_steps, 1)),
            acceptance_by_bucket=acceptance_by_prompt_bucket(fin),
            prefix_cache=self.bm.prefix is not None,
            prefix_cache_hits=self.bm.prefix.hits if self.bm.prefix else 0,
            prefix_cache_misses=(self.bm.prefix.misses
                                 if self.bm.prefix else 0),
            prefix_cache_hit_tokens=(self.bm.prefix.hit_tokens
                                     if self.bm.prefix else 0),
            prefix_cache_hit_rate=(
                self.bm.prefix.hit_tokens
                / max(self.bm.prefix.lookup_tokens, 1)
                if self.bm.prefix else 0.0),
            cow_copies=self.pool.cow_copies,
            blocks_retained=(self.bm.prefix.num_retained
                             if self.bm.prefix else 0),
            phase_ms=dict(self._phase_ms),
            step_wall_ms_total=self._step_wall_ms_total,
            trace_events=self.trace.emitted if self.trace.enabled else 0,
            trace_dropped=self.trace.dropped if self.trace.enabled else 0)


def generate_paged(params, buffers, cfg: ModelConfig, prompts: jnp.ndarray,
                   max_new_tokens: int, scfg: Optional[SchedulerConfig] = None
                   ) -> Tuple[np.ndarray, ServeReport]:
    """Paged-pool twin of ``generate`` (same greedy semantics, same output
    shape) — the parity surface for scheduler tests."""
    B, Sp = prompts.shape
    scfg = scfg or SchedulerConfig(
        max_slots=B, max_new_tokens=max_new_tokens,
        max_len=Sp + max_new_tokens + 1,
        num_blocks=2 * B * (-(-(Sp + max_new_tokens) // 16)), block_size=16)
    sched = Scheduler(params, buffers, cfg, scfg)
    reqs = [Request(uid=i, prompt=np.asarray(prompts[i]),
                    max_new_tokens=max_new_tokens) for i in range(B)]
    report = sched.run(reqs)
    out = np.zeros((B, max_new_tokens), np.int32)
    for r in sched.finished:
        out[r.uid, :len(r.generated)] = r.generated
    return out, report
