"""KV-cache size accounting + the block-paged compressed-KV pool.

Size formulas (paper §3.2), per token per attention layer, in floats:
    vanilla MHA/GQA:      2 · n_kv · d_h
    RoPElite + J-LRD:     2 · r · n_kv + d_ckv
    RoPElite + S-LRD:     2 · r · n_kv + d_ck + d_cv
Mamba layers hold O(1) state instead (conv + ssm), reported separately.

Paged pool
----------
``PagedKVPool`` stores the compressed ``(k_e, c_kv)`` streams of every
attention layer in fixed-size token *blocks* shared across sequences
(vLLM-style).  Sequences own ragged chains of blocks via per-sequence block
tables; chains grow one block at a time on demand and recycle the moment a
sequence retires.  Device pages are plain jax arrays handed to jitted steps
and reassigned; all bookkeeping (free list, tables, lengths) is host-side
Python.

``BlockManager`` layers the serving scheduler's *policy* on top of the pool:
admission gating (preempt-on-demand vs the legacy watermark reservation),
resident registration, and the two eviction mechanisms — recompute (free the
victim's blocks; the scheduler re-prefills its prefix later) and host
swap-out (copy the victim's cached streams to host memory and restore them
block-exactly on re-admission).

Cross-request prefix caching (docs/serving.md §prefix caching)
--------------------------------------------------------------
Real traffic shares huge prompt prefixes (system prompts, few-shot
templates, multi-turn history).  With ``BlockManager(prefix_cache=True)``
the pool's physical blocks become *shareable*: every block carries a
refcount, full prompt-token blocks are content-addressed by a chained hash
(``prefix_block_hashes`` — block ``i``'s key commits to every token before
it), and an admission-time ``lookup_prefix`` splices already-cached blocks
into a newcomer's chain instead of re-prefilling them.  Writes go through a
copy-on-write barrier (``PagedKVPool.make_private``): a resident that would
write into a block another chain references gets a private copy first, so
no write is ever visible through another resident's chain.  Retired
prefixes' blocks (refcount 0) are *retained* in an LRU rather than freed —
still servable to future lookups, reclaimed oldest-first only when the
allocator runs dry.  EliteKV's ~75% cache compression multiplies with this
dedup: the same physical pool holds proportionally more distinct prefixes.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.obs.trace import NULL_TRACER


def attn_cache_floats_per_token(cfg: ModelConfig) -> int:
    return cfg.elitekv.cache_per_token_per_layer(cfg.n_kv_heads, cfg.head_dim)


def model_cache_floats_per_token(cfg: ModelConfig) -> int:
    return cfg.n_attn_layers * attn_cache_floats_per_token(cfg)


def ssm_state_floats(cfg: ModelConfig, batch: int) -> int:
    n_ssm = sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i) == "ssm")
    per = (cfg.ssm_conv - 1) * cfg.d_inner + cfg.d_inner * cfg.ssm_state
    return n_ssm * per * batch


def cache_ratio(cfg_elite: ModelConfig, cfg_base: ModelConfig) -> float:
    """Attention-KV compression ratio vs the unmodified model."""
    a = model_cache_floats_per_token(cfg_elite)
    b = model_cache_floats_per_token(cfg_base)
    return a / b if b else 1.0


class OutOfBlocks(RuntimeError):
    """Raised when the pool cannot satisfy an allocation (caller may retry
    after retiring sequences, or refuse admission)."""


#: Per-BLOCK summary leaf suffixes (sparse decode, docs/serving.md).  A pool
#: built with ``block_summaries=True`` stores, beside the latent key stream,
#: a masked mean and absmax of each block's valid rows:
#:   "<stream>_blkmean" / "<stream>_blkmax"  —  [n_super, num_blocks, d_c] f32
#: Unlike the int8 scale leaves these index the BLOCK axis, not the slot
#: axis, so the slot-generic lifecycle edges (COW copy, host swap) special-
#: case them by name — everything else (truncate, prefix sharing, release)
#: needs nothing: summaries are a pure function of block content.
BLOCK_SUMMARY_SUFFIXES = ("_blkmean", "_blkmax")


def is_block_summary(name: str) -> bool:
    """True for page-leaf names that index blocks rather than slots."""
    return name.endswith(BLOCK_SUMMARY_SUFFIXES)


# ---------------------------------------------------------------------------
# prefix caching: chained block hashes + the content-addressed block cache
# ---------------------------------------------------------------------------

#: Domain separator — the hash chain's root "parent" digest.  Bump on any
#: change to the hashing scheme so stale keys can never alias fresh ones.
_HASH_ROOT = b"elitekv-prefix-v1"


def block_hash(parent: bytes, tokens) -> bytes:
    """Key of one full token block: ``H(parent_hash ‖ block_tokens)``.

    Chaining through ``parent`` makes the key commit to *every* token before
    the block, not just its own — two prompts sharing block ``i``'s tokens
    but differing earlier can never collide (parent-hash dependence)."""
    h = hashlib.sha256(parent)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


def prefix_block_hashes(tokens, block_size: int) -> List[bytes]:
    """Chained hashes of every FULL ``block_size``-token block of ``tokens``.
    A partial tail block has no hash — it is never cached (its content would
    change as the sequence grows into it)."""
    toks = np.asarray(tokens, np.int32)
    out: List[bytes] = []
    parent = _HASH_ROOT
    for i in range(len(toks) // block_size):
        parent = block_hash(parent,
                            toks[i * block_size:(i + 1) * block_size])
        out.append(parent)
    return out


class PrefixCache:
    """Content-addressed map from chained block hashes to physical blocks,
    with LRU retention of unreferenced entries.

    Owned by a ``BlockManager``; the pool consults it on the block lifecycle
    edges: a cached block whose refcount drops to 0 is *retained* (moved to
    the LRU, still servable to lookups) instead of freed, and reclaimed
    oldest-first only when the allocator runs dry.  A cached block is never
    rewritten in place: shared blocks copy-on-write, and a sole owner about
    to rewrite one first ``invalidate``s its claim.
    """

    def __init__(self):
        self._by_hash: Dict[bytes, int] = {}          # chain hash → block
        self._by_block: Dict[int, bytes] = {}         # block → chain hash
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()                 # refcount-0, oldest first
        self.hits = 0                                 # lookups that shared ≥ 1 block
        self.misses = 0                               # lookups that shared none
        self.hit_tokens = 0                           # tokens served from cache
        self.lookup_tokens = 0                        # tokens presented to lookups
        self.reclaimed = 0                            # retained blocks evicted

    @property
    def num_cached(self) -> int:
        return len(self._by_hash)

    @property
    def num_retained(self) -> int:
        return len(self._lru)

    def get(self, h: bytes) -> Optional[int]:
        return self._by_hash.get(h)

    def is_cached(self, block: int) -> bool:
        return block in self._by_block

    def claim(self, h: bytes, block: int) -> bool:
        """Register ``block`` as the physical home of chain hash ``h``.
        First claim wins — a duplicate hash keeps the existing block (the
        newcomer's copy stays private and is freed normally)."""
        if h in self._by_hash or block in self._by_block:
            return False
        self._by_hash[h] = block
        self._by_block[block] = h
        return True

    def on_ref(self, block: int) -> None:
        """``block`` gained a reference: it leaves the reclaimable LRU."""
        self._lru.pop(block, None)

    def retain(self, block: int) -> bool:
        """``block``'s refcount hit 0.  Returns True when the block is cached
        and should be kept (appended as most-recently-used); False means the
        pool frees it normally."""
        if block not in self._by_block:
            return False
        self._lru[block] = None
        self._lru.move_to_end(block)
        return True

    def invalidate(self, block: int) -> None:
        """Drop ``block``'s content claim (sole owner about to rewrite it, or
        a COW copy superseding it).  The block itself stays wherever it is —
        owned by its chain, or freed by the caller."""
        h = self._by_block.pop(block, None)
        if h is not None:
            del self._by_hash[h]
        self._lru.pop(block, None)

    def reclaim(self, n: int) -> List[int]:
        """Evict up to ``n`` retained blocks, least-recently-used first,
        dropping their hash claims.  Returns the blocks (now unowned — the
        caller puts them back on the free list)."""
        out: List[int] = []
        while len(out) < n and self._lru:
            block, _ = self._lru.popitem(last=False)
            h = self._by_block.pop(block)
            del self._by_hash[h]
            self.reclaimed += 1
            out.append(block)
        return out


class BlockAllocator:
    """Host-side free-list over ``num_blocks`` fixed-size token blocks."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.high_water = 0          # max blocks simultaneously in use
        self.total_allocs = 0        # lifetime alloc count (reuse visibility)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise OutOfBlocks(f"need {n} blocks, {len(self._free)} free")
        got = [self._free.pop() for _ in range(n)]
        self.total_allocs += n
        self.high_water = max(self.high_water, self.num_used)
        return got

    def free(self, blocks: Sequence[int]) -> None:
        self._free.extend(blocks)

    def reset(self) -> None:
        self._free = list(range(self.num_blocks - 1, -1, -1))


@dataclasses.dataclass
class PoolStats:
    block_size: int
    num_blocks: int
    blocks_in_use: int
    blocks_free: int
    high_water_blocks: int
    total_allocs: int
    live_tokens: int        # sum of sequence lengths
    allocated_tokens: int   # blocks_in_use * block_size (internal fragmentation)
    live_bytes: int
    allocated_bytes: int
    blocks_shared: int = 0     # blocks referenced by more than one chain
    blocks_retained: int = 0   # refcount-0 prefix-cache blocks (reclaimable)
    cow_copies: int = 0        # lifetime copy-on-write block copies
    dtype: str = "float32"     # pool storage dtype ("int8" = quantized)
    bytes_per_token: int = 0   # actual bytes/slot incl. quantization scales


class PagedKVPool:
    """Block-paged device storage for EliteKV's compressed cache streams.

    Pages mirror ``lm.init_cache``'s per-``p_pos`` layout but replace the
    ``[B, max_len, ...]`` leading dims with one flat ``[n_slots, ...]`` token
    axis (``n_slots = num_blocks · block_size``); token ``t`` of block ``b``
    lives at flat slot ``b · block_size + t``.  ``k_e`` also folds its
    ``[nkv, 2r]`` tail into one ``nkv · 2r`` row (kv head ``h`` in columns
    ``[h·2r, (h+1)·2r)``), so every stream is a 2-D ``[n_slots, width]``
    array per layer whose ``[block, width]`` pages the kernels read without
    a relayout.  Only attention layers page —
    serving currently requires an attention-only, EliteKV-enabled config
    (Mamba's O(1) state needs no paging; hybrid support is a ROADMAP item).
    """

    def __init__(self, cfg: ModelConfig, num_blocks: int, block_size: int,
                 dtype=jnp.float32, tracer=None, mesh=None,
                 tp_axis: str = "model", block_summaries: bool = False):
        assert cfg.elitekv.enabled, "paged pool stores compressed streams only"
        self.trace = tracer or NULL_TRACER   # obs: alloc/free/truncate events
        for p_pos in range(cfg.block_period):
            assert cfg.layer_kind(p_pos) == "attn", \
                "paged serving supports attention-only stacks (see ROADMAP)"
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        # dtype="int8" (string or dtype) selects the quantized pool: stream
        # leaves store symmetric-absmax int8 rows and every stream gains a
        # per-slot f32 scale leaf "<name>_scale" beside it (core/quant.py).
        # Scales keep the [n_super, n_slots, ...] slot axis at position 1, so
        # COW copies, host swap and truncate handle them with zero special
        # cases — they are just more page leaves.
        self.dtype = jnp.dtype(dtype)
        self.quantized = quant.is_int8(self.dtype)
        # block_summaries=True (sparse top-k decode) adds two f32 leaves per
        # latent KEY stream summarizing each block's valid rows — see
        # BLOCK_SUMMARY_SUFFIXES above.  Maintained by the jitted scatter
        # (core/elite_attention.py), copied block-row-wise on COW, carried
        # byte-exactly through host swap, rewritten by recompute prefill.
        self.block_summaries = bool(block_summaries)
        self.allocator = BlockAllocator(num_blocks)
        self._tables: Dict[int, List[int]] = {}   # seq_id → block chain
        self._lengths: Dict[int, int] = {}        # seq_id → live token count
        self._refcount: Dict[int, int] = {}       # block → referencing chains
        self.prefix: Optional[PrefixCache] = None  # set by BlockManager
        self.cow_copies = 0                       # lifetime copy-on-write count
        e = cfg.elitekv
        n_super = cfg.num_layers // cfg.block_period
        n_slots = num_blocks * block_size
        r2 = 2 * e.elite_r

        def _streams():
            # k_e rows are flat: kv head h owns columns [h·2r, (h+1)·2r), the
            # lane-dense [block, nkv·2r] tile the paged kernels read
            tails = {"k_e": (cfg.n_kv_heads * r2,)}
            if e.lrd == "joint":
                tails["c"] = (e.d_ckv,)
            else:
                tails["c_k"] = (e.d_ck,)
                tails["c_v"] = (e.d_cv,)
            s = {}
            for name, tail in tails.items():
                s[name] = jnp.zeros((n_super, n_slots) + tail, self.dtype)
                if self.quantized:
                    s[name + "_scale"] = jnp.zeros((n_super, n_slots),
                                                   jnp.float32)
            if self.block_summaries:
                key = "c" if e.lrd == "joint" else "c_k"
                for sfx in BLOCK_SUMMARY_SUFFIXES:
                    s[key + sfx] = jnp.zeros(
                        (n_super, num_blocks) + tails[key], jnp.float32)
            return s

        self.pages = {f"p{p}": _streams() for p in range(cfg.block_period)}

        # Tensor-parallel page placement: the k_e stream shards its row over
        # the mesh's TP axis (each shard holds its kv heads' columns); the
        # head-shared latent and the per-token scales replicate
        # (distributed/sharding.py ``serving_page_pspecs``).  Block ids, chains, refcounts, prefix
        # hashes — everything host-side — stay shard-invariant: every device
        # holds the same slot layout, just a head slice of k_e, so COW / swap
        # / truncate / prefix sharing below never special-case the mesh.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = 1
        if mesh is not None:
            from repro.distributed import sharding as shardlib
            plan = shardlib.plan_for_mesh(mesh)
            if tp_axis in mesh.shape and mesh.shape[tp_axis] > 1:
                assert cfg.n_kv_heads % mesh.shape[tp_axis] == 0, \
                    (cfg.n_kv_heads, mesh.shape[tp_axis],
                     "kv heads must divide tp (pad_cfg_for_tp)")
                self.tp = mesh.shape[tp_axis]
            specs = shardlib.serving_page_pspecs(cfg, plan)
            self.pages = {
                p_key: {name: jax.device_put(
                            arr, jax.sharding.NamedSharding(mesh, specs[name]))
                        for name, arr in layer.items()}
                for p_key, layer in self.pages.items()}

    # -- allocation plumbing (prefix-cache aware) ---------------------------
    def _alloc(self, n: int) -> List[int]:
        """Allocate ``n`` blocks, reclaiming LRU-retained prefix-cache blocks
        (oldest first) when the free list alone cannot cover the request."""
        short = n - self.allocator.num_free
        if short > 0 and self.prefix is not None:
            evicted = self.prefix.reclaim(short)
            if evicted:
                self.allocator.free(evicted)
                self.trace.instant("free", track="pool", cat="pool", seq=-1,
                                   blocks=evicted, reason="reclaim")
        got = self.allocator.alloc(n)       # raises OutOfBlocks if still short
        for b in got:
            self._refcount[b] = 1
        return got

    def _release_blocks(self, blocks: Sequence[int], seq_id: int,
                        reason: str) -> None:
        """Drop one reference per block.  A block reaching refcount 0 either
        returns to the free list or — when it backs a cached prefix — is
        retained reclaimable in the prefix cache's LRU."""
        freed: List[int] = []
        retained: List[int] = []
        for b in blocks:
            self._refcount[b] -= 1
            if self._refcount[b] > 0:
                continue                    # another chain still reads it
            del self._refcount[b]
            if self.prefix is not None and self.prefix.retain(b):
                retained.append(b)
            else:
                freed.append(b)
        if freed:
            self.allocator.free(freed)
            self.trace.instant("free", track="pool", cat="pool", seq=seq_id,
                               blocks=freed, reason=reason)
        if retained:
            self.trace.instant("retain", track="pool", cat="cache",
                               seq=seq_id, blocks=retained)

    # -- sequence lifecycle -------------------------------------------------
    def ensure_capacity(self, seq_id: int, length: int) -> None:
        """Grow ``seq_id``'s block chain to hold ``length`` tokens (allocates
        lazily on first touch).  Raises OutOfBlocks when the pool is full."""
        table = self._tables.setdefault(seq_id, [])
        need = -(-length // self.block_size) - len(table)
        if need > 0:
            got = self._alloc(need)
            table.extend(got)
            self.trace.instant("alloc", track="pool", cat="pool", seq=seq_id,
                               blocks=got, length=length)
        self._lengths[seq_id] = max(self._lengths.get(seq_id, 0), length)

    def share_prefix(self, seq_id: int, blocks: Sequence[int]) -> None:
        """Splice already-cached ``blocks`` into ``seq_id``'s (empty) chain
        as its head: each gains a reference instead of being re-prefilled.
        The chain's length becomes exactly the shared coverage."""
        table = self._tables.setdefault(seq_id, [])
        assert not table and not self._lengths.get(seq_id, 0), \
            (seq_id, "prefix sharing requires a fresh chain")
        for b in blocks:
            self._refcount[b] = self._refcount.get(b, 0) + 1
            if self.prefix is not None:
                self.prefix.on_ref(b)
        table.extend(blocks)
        self._lengths[seq_id] = len(blocks) * self.block_size
        if blocks:
            self.trace.instant("share", track="pool", cat="cache",
                               seq=seq_id, blocks=list(blocks))

    def make_private(self, seq_id: int, start: int, end: int) -> None:
        """Copy-on-write barrier: before ``seq_id`` writes token positions
        ``[start, end)``, give it exclusive ownership of every covered block.
        A block another chain references is copied device-side into a fresh
        block (the writer's chain repoints; readers keep the original); a
        sole-owner block that backs a cached prefix just drops its content
        claim (no copy needed — nobody else can read it)."""
        if end <= start:
            return
        table = self._tables.get(seq_id, [])
        bs = self.block_size
        for bi in range(start // bs, min(-(-end // bs), len(table))):
            b = table[bi]
            if self._refcount.get(b, 0) > 1:
                new = self._alloc(1)[0]
                src = np.arange(b * bs, (b + 1) * bs)
                dst = np.arange(new * bs, (new + 1) * bs)
                for p_key, layer in self.pages.items():
                    # block-summary leaves index blocks, not slots: copy the
                    # single summary row; every other leaf copies slot-wise
                    self.pages[p_key] = {
                        name: (arr.at[:, new].set(arr[:, b])
                               if is_block_summary(name)
                               else arr.at[:, dst].set(arr[:, src]))
                        for name, arr in layer.items()}
                self._refcount[b] -= 1
                table[bi] = new
                self.cow_copies += 1
                self.trace.instant("cow", track="pool", cat="cache",
                                   seq=seq_id, block=b, copy=new)
            elif self.prefix is not None and self.prefix.is_cached(b):
                self.prefix.invalidate(b)   # sole owner rewrites in place

    def can_fit(self, extra_tokens: int) -> bool:
        avail = self.allocator.num_free + \
            (self.prefix.num_retained if self.prefix is not None else 0)
        return avail * self.block_size >= extra_tokens

    def truncate(self, seq_id: int, length: int) -> None:
        """Shrink ``seq_id`` to ``length`` tokens, releasing tail blocks the
        shorter chain no longer covers (speculative decode rolls rejected
        verify-window tokens back through here — pages are never rewritten,
        the stale slots are simply re-extended over by later growth).
        A released block still referenced by another chain is merely
        un-linked, never freed or rolled back; the next write into a kept
        block that is still shared goes through ``make_private`` first.
        ``length`` must not exceed the current length; 0 keeps the (empty)
        chain registered."""
        assert length >= 0, length
        if seq_id not in self._lengths:     # unknown/freed seq: only the
            assert length == 0, (seq_id, length)   # no-op shrink is legal,
            return                          # and it must not register one
        cur = self._lengths[seq_id]
        assert length <= cur, (seq_id, length, cur)
        table = self._tables.get(seq_id, [])
        keep = -(-length // self.block_size)
        if keep < len(table):
            dropped = table[keep:]
            del table[keep:]
            self._release_blocks(dropped, seq_id, reason="truncate")
        self._lengths[seq_id] = length

    def free_seq(self, seq_id: int) -> None:
        blocks = self._tables.pop(seq_id, [])
        if blocks:
            self._release_blocks(blocks, seq_id, reason="release")
        self._lengths.pop(seq_id, None)

    def reset(self) -> None:
        self.allocator.reset()
        self._tables.clear()
        self._lengths.clear()
        self._refcount.clear()
        self.cow_copies = 0
        if self.prefix is not None:
            self.prefix = PrefixCache()

    def length(self, seq_id: int) -> int:
        return self._lengths.get(seq_id, 0)

    def block_table(self, seq_id: int) -> List[int]:
        return list(self._tables.get(seq_id, []))

    # -- device-side index helpers -----------------------------------------
    @property
    def oob_slot(self) -> int:
        """Scatter sentinel: one past the last flat slot (dropped by
        ``mode="drop"`` writes — used to mask inactive batch lanes)."""
        return self.num_blocks * self.block_size

    def block_table_array(self, seq_ids: Sequence[Optional[int]],
                          max_blocks: int) -> np.ndarray:
        """Padded int32 ``[len(seq_ids), max_blocks]`` table (pad = block 0;
        padded pages are masked out by per-sequence lengths downstream)."""
        out = np.zeros((len(seq_ids), max_blocks), np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is None:
                continue
            t = self._tables.get(sid, [])
            assert len(t) <= max_blocks, (len(t), max_blocks)
            out[i, :len(t)] = t
        return out

    def flat_slots(self, seq_id: int, positions) -> np.ndarray:
        """Flat pool slots for logical ``positions`` of ``seq_id``'s chain:
        position ``p`` lives at ``table[p // bs] · bs + p % bs``.  The single
        source of the slot-layout formula (decode/prefill mappings and host
        swap all route through here)."""
        table = np.asarray(self._tables[seq_id], np.int64)
        pos = np.asarray(positions)
        return table[pos // self.block_size] * self.block_size \
            + pos % self.block_size

    def slot_mapping(self, seq_ids: Sequence[Optional[int]],
                     positions: Sequence[int]) -> np.ndarray:
        """Flat write slots for one token per sequence; inactive lanes
        (seq_id None) map to ``oob_slot``."""
        out = np.full((len(seq_ids),), self.oob_slot, np.int32)
        for i, (sid, pos) in enumerate(zip(seq_ids, positions)):
            if sid is not None:
                out[i] = self.flat_slots(sid, pos)
        return out

    def prefill_slot_mapping(self, seq_id: int, start: int,
                             n_tokens: int, pad_to: int) -> np.ndarray:
        """Flat write slots for ``n_tokens`` consecutive positions starting at
        ``start``, padded with ``oob_slot`` up to ``pad_to`` (prompt padding)."""
        out = np.full((pad_to,), self.oob_slot, np.int32)
        out[:n_tokens] = self.flat_slots(seq_id,
                                         np.arange(start, start + n_tokens))
        return out

    # -- accounting ---------------------------------------------------------
    def floats_per_token(self) -> int:
        return model_cache_floats_per_token(self.cfg)

    def bytes_per_token(self) -> int:
        """Actual pool bytes per token slot, summed over every page leaf —
        int8 stream rows AND their f32 scales in quantized mode (the honest
        capacity number the serving stats report)."""
        n_slots = self.num_blocks * self.block_size
        return sum(a.nbytes // n_slots
                   for layer in self.pages.values() for a in layer.values())

    def bytes_per_token_per_device(self) -> int:
        """Pool bytes per token slot actually resident on EACH device: the
        head-sharded ``k_e`` stream contributes ``1/tp`` of its global bytes,
        replicated leaves contribute in full.  Equals ``bytes_per_token()``
        when unsharded — the per-device-count benchmark scaling row reports
        this number."""
        n_slots = self.num_blocks * self.block_size
        total = 0
        for layer in self.pages.values():
            for name, a in layer.items():
                div = self.tp if name == "k_e" else 1
                total += a.nbytes // div // n_slots
        return total

    def stats(self) -> PoolStats:
        live = sum(self._lengths.values())
        alloc_tok = self.allocator.num_used * self.block_size
        bpt = self.bytes_per_token()
        return PoolStats(
            block_size=self.block_size, num_blocks=self.num_blocks,
            blocks_in_use=self.allocator.num_used,
            blocks_free=self.allocator.num_free,
            high_water_blocks=self.allocator.high_water,
            total_allocs=self.allocator.total_allocs,
            live_tokens=live, allocated_tokens=alloc_tok,
            live_bytes=live * bpt,
            allocated_bytes=alloc_tok * bpt,
            dtype=str(self.dtype), bytes_per_token=bpt,
            blocks_shared=sum(1 for c in self._refcount.values() if c > 1),
            blocks_retained=(self.prefix.num_retained
                             if self.prefix is not None else 0),
            cow_copies=self.cow_copies)


@dataclasses.dataclass
class SwappedSeq:
    """Host-side copy of a preempted sequence's cached streams (swap
    eviction).  ``streams[p_key][name]`` is a ``[n_super, length, ...]``
    numpy array in *token order* — independent of which physical blocks the
    sequence owned, so swap-in may land on a completely different chain.
    ``block_streams`` carries the chain's per-block summary rows (sparse
    pools only) in *chain order* — ``[n_super, n_chain_blocks, ...]`` —
    restored byte-exactly onto whatever blocks swap-in allocates, so block
    selection is invariant under swap."""
    length: int
    streams: Dict[str, Dict[str, np.ndarray]]
    block_streams: Dict[str, Dict[str, np.ndarray]] = \
        dataclasses.field(default_factory=dict)

    def nbytes(self) -> int:
        return sum(a.nbytes for s in self.streams.values() for a in s.values()) \
            + sum(a.nbytes for s in self.block_streams.values()
                  for a in s.values())


class BlockManager:
    """Admission + eviction policy over a ``PagedKVPool``.

    Two admission policies:

    * ``"preempt"`` (default) — no reservation.  A request is admitted as
      soon as its *next allocation* (first prefill chunk, or the swapped-out
      prefix being restored) fits in the free list; residents grow blocks on
      demand and growth may raise ``OutOfBlocks`` mid-flight, which the
      scheduler resolves by preempting the youngest resident.
    * ``"watermark"`` — the legacy reservation policy: the worst-case blocks
      still owed to every registered resident are held back, so admission is
      refused unless the newcomer's full worst case fits in
      ``free − reserved`` and growth can never fail.

    Eviction mechanisms (used by the scheduler's preemption path):

    * ``preempt_recompute`` — drop the victim's blocks; its cached prefix is
      rebuilt by a recompute-prefill after re-admission.  Cheap to evict,
      costs one prefill of the prefix — and under EliteKV that prefill only
      re-fills the low-rank ``(k_e, c_kv)`` streams, the paper's compression
      making recompute proportionally cheaper than for a full KV cache.
    * ``preempt_swap_out`` / ``swap_in`` — copy the victim's live tokens to
      host memory, free the blocks, and scatter the copy back into a fresh
      chain on re-admission.  Costs PCIe traffic instead of FLOPs.

    With ``prefix_cache=True`` the manager additionally runs the
    cross-request prefix cache (``PrefixCache``): ``lookup_prefix`` splices
    cached full prompt blocks into a newcomer's chain, ``register_prefix``
    claims a resident's freshly prefilled full blocks for future lookups,
    and ``prepare_write`` is the copy-on-write barrier callers invoke before
    scattering into a chain.  Eviction, preemption and ``truncate`` all
    respect refcounts — a block another chain references is never freed or
    rolled back.
    """

    def __init__(self, pool: PagedKVPool, policy: str = "preempt",
                 prefix_cache: bool = False):
        assert policy in ("preempt", "watermark"), policy
        self.pool = pool
        self.policy = policy
        if prefix_cache and pool.prefix is None:
            pool.prefix = PrefixCache()
        self._resident_worst: Dict[int, int] = {}   # seq_id → worst-case blocks
        self.preemptions = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swapped_bytes = 0                      # lifetime host-swap traffic

    @property
    def prefix(self) -> Optional[PrefixCache]:
        return self.pool.prefix

    # -- prefix cache (cross-request block sharing) -------------------------
    def lookup_prefix(self, seq_id: int, tokens) -> int:
        """Admission-time cache probe: share the longest cached chain of full
        ``tokens`` blocks into ``seq_id``'s fresh chain and return the number
        of tokens covered (0 on a miss).  The hit is capped one token short
        of ``len(tokens)`` — at least the final prompt token is always
        re-prefilled so the forward produces the logits row the first
        sampled token comes from."""
        pc = self.prefix
        if pc is None or len(tokens) == 0:
            return 0
        bs = self.pool.block_size
        pc.lookup_tokens += len(tokens)
        cap = (len(tokens) - 1) // bs       # never cover the whole prompt
        blocks: List[int] = []
        for h in prefix_block_hashes(tokens, bs)[:cap]:
            b = pc.get(h)
            if b is None:
                break
            blocks.append(b)
        if not blocks:
            pc.misses += 1
            return 0
        self.pool.share_prefix(seq_id, blocks)
        pc.hits += 1
        pc.hit_tokens += len(blocks) * bs
        return len(blocks) * bs

    def register_prefix(self, seq_id: int, tokens) -> int:
        """Claim ``seq_id``'s fully-written prompt blocks for future lookups:
        every full block of ``tokens`` the chain already covers gets its
        chain hash registered (first claim wins; a hash someone else already
        owns leaves this chain's copy private).  Returns new claims made."""
        pc = self.prefix
        if pc is None:
            return 0
        bs = self.pool.block_size
        table = self.pool.block_table(seq_id)
        n_full = min(len(tokens) // bs, self.pool.length(seq_id) // bs,
                     len(table))
        claimed = 0
        for i, h in enumerate(prefix_block_hashes(tokens, bs)[:n_full]):
            if pc.claim(h, table[i]):
                claimed += 1
        if claimed:
            self.pool.trace.instant("prefix_register", track="pool",
                                    cat="cache", seq=seq_id, blocks=claimed)
        return claimed

    def prepare_write(self, seq_id: int, start: int, end: int) -> None:
        """Copy-on-write barrier for an upcoming scatter into positions
        ``[start, end)`` of ``seq_id``'s chain (no-op without sharing)."""
        if self.prefix is not None:
            self.pool.make_private(seq_id, start, end)

    # -- admission ----------------------------------------------------------
    @property
    def reserved_blocks(self) -> int:
        """Watermark: worst-case blocks still owed to registered residents."""
        return sum(max(0, w - len(self.pool.block_table(sid)))
                   for sid, w in self._resident_worst.items())

    def can_admit(self, first_alloc_tokens: int, worst_case_blocks: int) -> bool:
        if self.policy == "watermark":
            # LRU-retained prefix blocks count as free: growth reclaims them
            # on demand, so the reservation guarantee still holds
            retained = self.prefix.num_retained if self.prefix else 0
            return (self.pool.allocator.num_free + retained
                    - self.reserved_blocks >= worst_case_blocks)
        return self.pool.can_fit(first_alloc_tokens)

    def register(self, seq_id: int, worst_case_blocks: int) -> None:
        """Mark ``seq_id`` resident (watermark accounting input)."""
        self._resident_worst[seq_id] = worst_case_blocks

    # -- growth / release ---------------------------------------------------
    def grow(self, seq_id: int, length: int) -> None:
        """Grow ``seq_id`` to ``length`` tokens; raises ``OutOfBlocks`` when
        the pool is exhausted (the scheduler then preempts)."""
        self.pool.ensure_capacity(seq_id, length)

    def release(self, seq_id: int) -> None:
        """Retire or evict: free the chain and drop residency."""
        self.pool.free_seq(seq_id)
        self._resident_worst.pop(seq_id, None)

    def truncate(self, seq_id: int, length: int) -> None:
        """Roll ``seq_id`` back to ``length`` tokens (rejected speculative
        verify-window tail): exclusively-owned tail blocks return to the free
        list immediately, while a tail block another chain still references
        is only un-linked (its content is never rolled back under the other
        resident); residency is kept — the watermark reservation grows back
        by exactly the released blocks, so both admission policies stay
        conserved."""
        self.pool.truncate(seq_id, length)

    # -- eviction -----------------------------------------------------------
    def preempt_recompute(self, seq_id: int) -> None:
        self.release(seq_id)
        self.preemptions += 1

    def preempt_swap_out(self, seq_id: int, length: int) -> Optional[SwappedSeq]:
        """Copy ``length`` cached tokens to host, then free the chain.
        ``length`` comes from the *request's* state, not ``pool.length`` —
        a growth bump whose decode step never ran must not be swapped.
        Returns None when nothing is cached yet (plain requeue)."""
        self.preemptions += 1
        if length <= 0:
            self.release(seq_id)
            return None
        with self.pool.trace.span("swap_out", track="pool", cat="swap",
                                  seq=seq_id, length=length):
            # gather the victim's slots on device, then transfer just those —
            # host traffic is O(sequence), not O(pool).  Block-summary leaves
            # index blocks, not slots: their chain rows travel separately.
            slots = jnp.asarray(self.pool.flat_slots(seq_id, np.arange(length)))
            chain = jnp.asarray(
                self.pool.block_table(seq_id)[:-(-length // self.pool.block_size)],
                jnp.int32)
            streams = {p_key: {name: np.asarray(arr[:, slots])
                               for name, arr in layer.items()
                               if not is_block_summary(name)}
                       for p_key, layer in self.pool.pages.items()}
            block_streams = {
                p_key: {name: np.asarray(arr[:, chain])
                        for name, arr in layer.items()
                        if is_block_summary(name)}
                for p_key, layer in self.pool.pages.items()}
            self.release(seq_id)
            swapped = SwappedSeq(length=length, streams=streams,
                                 block_streams=block_streams)
        self.swap_outs += 1
        self.swapped_bytes += swapped.nbytes()
        return swapped

    def swap_in(self, seq_id: int, swapped: SwappedSeq) -> None:
        """Allocate a fresh chain and scatter the host copy back.  Raises
        ``OutOfBlocks`` if the prefix does not fit (caller defers admission)."""
        self.pool.ensure_capacity(seq_id, swapped.length)
        with self.pool.trace.span("swap_in", track="pool", cat="swap",
                                  seq=seq_id, length=swapped.length):
            slots = jnp.asarray(self.pool.flat_slots(seq_id,
                                                     np.arange(swapped.length)))
            for p_key, layer in swapped.streams.items():
                self.pool.pages[p_key] = {
                    **self.pool.pages[p_key],
                    **{name: self.pool.pages[p_key][name].at[:, slots].set(
                        jnp.asarray(host, self.pool.pages[p_key][name].dtype))
                       for name, host in layer.items()}}
            if swapped.block_streams:
                chain = jnp.asarray(
                    self.pool.block_table(seq_id)[
                        :-(-swapped.length // self.pool.block_size)],
                    jnp.int32)
                for p_key, layer in swapped.block_streams.items():
                    self.pool.pages[p_key] = {
                        **self.pool.pages[p_key],
                        **{name: self.pool.pages[p_key][name]
                            .at[:, chain].set(jnp.asarray(host))
                           for name, host in layer.items()}}
        self.swap_ins += 1


def measured_cache_bytes(cache, batch: int, max_len: int) -> Dict[str, int]:
    """Actual bytes in a live cache pytree, split attn vs ssm."""
    attn = ssm = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache["blocks"]):
        name = jax.tree_util.keystr(path)
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if "conv" in name or "ssm" in name:
            ssm += nbytes
        else:
            attn += nbytes
    return {"attn_bytes": attn, "ssm_bytes": ssm,
            "attn_bytes_per_token": attn // (batch * max_len)}
