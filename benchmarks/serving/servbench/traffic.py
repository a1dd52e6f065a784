"""The one traffic generator: a mix file's parameters plus ``--seed`` give
every request the benchmark sends.

Sizes come from a fixed set that does not depend on the seed: for ``M``
requests (``set_size``) each length is the distribution's quantile at
``(i + 0.5) / M``, the lists are paired by a fixed shuffle, and the seed only
permutes the order in which the set is sent (a fresh permutation per pass
over the set) and draws the token ids.  Every seed therefore offers the same
work, in another order.  Open loops space arrivals by the same rule
(stratified-exponential): the exponential's quantiles at the mix's rate,
permuted, as the gaps.

Mix keys (``traffic/<mix>.json``):

``loop``            "closed" (``clients``) or "open" (``rate_per_s``);
``prompt``/``output`` length distributions: ``{"kind": "uniform", "min",
                    "max"}`` or ``{"kind": "lognormal", "median", "sigma",
                    "min", "max"}``;
``documents``       optional shared documents: ``count``, ``length`` (a
                    distribution) and ``popularity`` (``{"kind": "zipf",
                    "s"}``); a request's prompt is then a document followed by
                    its own ``prompt`` tokens;
``steady_start``    the requests in flight when the window opens (one per
                    client of a closed loop, ``in_flight_at_start`` of an
                    open one) are part way through: each context is the
                    prompt plus a uniform share of its output, the rest is
                    its budget;
``set_size``        M above;
``order``           "seeded" (the default) or "fixed": a closed loop whose
                    window sees only a few fresh requests sends the set in
                    one order for every seed, so that each seed's window
                    holds the same work and the seed draws only the tokens;
``scheduler``       the ``SchedulerConfig`` settings the mix runs under.

Outputs are cut so that prompt + output fits ``scheduler.max_len``.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

BASE_SEED = 20250301          # fixes the size set; never the run's seed
_NORMAL = statistics.NormalDist()


def quantile(dist: Dict[str, Any], u: float) -> int:
    """The ``u`` quantile of a length distribution, as a whole number."""
    kind = dist["kind"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return min(hi, lo + int(u * (hi - lo + 1)))
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(dist["max"], max(dist["min"], round(v))))
    raise ValueError(f"unknown length distribution {kind!r}")


def quantile_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.asarray([quantile(dist, (i + 0.5) / n) for i in range(n)],
                      np.int64)


def zipf_ranks(s: float, n_items: int, n: int) -> np.ndarray:
    """``n`` quantiles of a Zipf(s) law over ranks ``0..n_items-1``."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    u = (np.arange(n) + 0.5) / n
    return np.searchsorted(cdf, u).astype(np.int64)


class Mix:
    """Requests of one traffic mix for one seed.  A request is a
    ``(prompt tokens, output budget)`` pair; ``sizes`` holds the fixed set
    as ``(document or -1, prompt length, output length)``."""

    def __init__(self, spec: Dict[str, Any], vocab: int, seed: int):
        self.spec, self.vocab, self.seed = spec, vocab, int(seed)
        self.max_len = spec["scheduler"]["max_len"]
        M = self.set_size = spec.get("set_size", 512)
        base = np.random.default_rng(BASE_SEED)
        prompt = base.permutation(quantile_set(spec["prompt"], M))
        output = base.permutation(quantile_set(spec["output"], M))
        docs = spec.get("documents")
        if docs:
            n_docs = docs["count"]
            self.doc_lengths = base.permutation(
                quantile_set(docs["length"], n_docs))
            doc_of = base.permutation(zipf_ranks(
                docs["popularity"]["s"], n_docs, M))
        else:
            self.doc_lengths = np.zeros((0,), np.int64)
            doc_of = np.full((M,), -1, np.int64)
        self.sizes = [(int(d), int(p), int(o))
                      for d, p, o in zip(doc_of, prompt, output)]
        self._doc_tokens: Dict[int, np.ndarray] = {}
        if spec["loop"] == "open":
            rate = spec["rate_per_s"]
            self.gaps = base.permutation(np.asarray(
                [-math.log(1.0 - (i + 0.5) / M) / rate for i in range(M)]))

    # -- token ids (the only thing besides order that the seed draws) -------
    def _tokens(self, tag: Tuple[int, ...], n: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed,) + tag)
        return rng.integers(0, self.vocab, n, dtype=np.int64).astype(np.int32)

    def document(self, j: int) -> np.ndarray:
        if j not in self._doc_tokens:
            self._doc_tokens[j] = self._tokens((1, j),
                                               int(self.doc_lengths[j]))
        return self._doc_tokens[j]

    def documents(self) -> List[np.ndarray]:
        return [self.document(j) for j in range(len(self.doc_lengths))]

    def _build(self, size, tag, done_share: float = 0.0):
        doc, n_prompt, n_out = size
        head = self.document(doc) if doc >= 0 else np.zeros((0,), np.int32)
        n_out = max(1, min(n_out, self.max_len - len(head) - n_prompt))
        done = int(done_share * n_out)
        own = self._tokens(tag, n_prompt + done)
        return np.concatenate([head, own]), n_out - done

    def initial(self) -> List[Tuple[np.ndarray, int]]:
        """The requests in flight when the window opens: one per client of a
        closed loop, ``in_flight_at_start`` of an open one.  With
        ``steady_start`` each is part way through (a fixed share per
        request, the same set for every seed)."""
        n = self.spec.get("clients", self.spec.get("in_flight_at_start", 0))
        base = np.random.default_rng(BASE_SEED + 1)
        picks = base.permutation(self.set_size)[:n]
        shares = (base.permutation(n) + 0.5) / n
        if not self.spec.get("steady_start"):
            shares = np.zeros((n,))
        return [self._build(self.sizes[int(i)], (2, c), float(shares[c]))
                for c, i in enumerate(picks)]

    # -- closed loop ----------------------------------------------------------
    def _order(self, epoch: int) -> np.ndarray:
        fixed = self.spec.get("order", "seeded") == "fixed"
        seed = BASE_SEED if fixed else self.seed
        return np.random.default_rng((seed, 3, epoch)).permutation(
            self.set_size)

    def requests(self) -> Iterator[Tuple[np.ndarray, int]]:
        """The closed loop's stream: each pass is the whole size set in a
        fresh order (seeded, unless the mix fixes it)."""
        epoch = 0
        while True:
            for k, i in enumerate(self._order(epoch)):
                yield self._build(self.sizes[int(i)], (4, epoch, k))
            epoch += 1

    # -- open loop ------------------------------------------------------------
    def arrivals(self) -> Iterator[Tuple[float, np.ndarray, int]]:
        """``(seconds after the window opens, prompt, budget)``, in order."""
        t, epoch = 0.0, 0
        while True:
            order = self._order(epoch)
            gaps = self.gaps[np.random.default_rng(
                (self.seed, 5, epoch)).permutation(self.set_size)]
            for k, i in enumerate(order):
                t += float(gaps[k])
                prompt, budget = self._build(self.sizes[int(i)], (6, epoch, k))
                yield t, prompt, budget
            epoch += 1
