#!/usr/bin/env python3
"""Validate observability artifacts (docs/observability.md) — stdlib only.

    python tools/check_trace.py trace.json [--metrics metrics.prom]

Trace checks (Chrome trace-event JSON, the format serve.py --trace writes):

* envelope: ``{"traceEvents": [...]}`` with a list of event records
* every record has a known ``ph`` and the fields that phase requires
  (``X`` → non-negative ``dur``; ``C`` → numeric ``args.value``; ``M`` →
  a recognised metadata name)
* every (pid, tid) that carries events has ``thread_name`` metadata
* ``B``/``E`` events balance per (pid, tid) — every begin is closed by a
  matching end, never cross-nested
* ``X`` spans on one (pid, tid) track nest properly — a span either
  contains or is disjoint from every other span on its track (a step
  encloses its phases, and a ``swap`` phase may sit inside ``grow`` or
  ``admit``; partial overlap means the emitter timed overlapping phases,
  which would double-count wall time)
* timestamps are non-negative and finite
* prefix-cache instants carry well-formed args: ``prefix_hit`` needs
  positive numeric ``tokens``/``blocks``, ``prefix_miss`` numeric
  ``tokens``, and ``cow`` numeric ``block``/``copy`` with
  ``block != copy`` (a block can never be its own COW copy)

Metrics checks (Prometheus text exposition format):

* every sample line parses as ``name{labels} value`` with a valid metric
  name and a finite value
* every sample belongs to a preceding ``# TYPE`` block
* histograms are internally consistent: bucket counts are cumulative
  (non-decreasing as ``le`` ascends), the ``+Inf`` bucket equals
  ``_count``, and ``_sum`` / ``_count`` are both present
* the ``serve_prefix_cache_*`` family is all-or-nothing (a registry that
  exports one of the six instruments must export them all) and
  self-consistent: zero hits cannot coexist with nonzero hit tokens,
  and no member may be negative
* the ``serve_pool_*`` family is likewise all-or-nothing and
  self-consistent: ``serve_pool_quantized`` must be exactly 0 or 1,
  ``serve_pool_bytes_per_token`` must be positive, and no member may
  be negative
* the ``serve_sparse_*`` family (sparse block-top-k decode) is
  all-or-nothing — dense runs export none of it, sparse runs export all
  six instruments (``serve_sparse_selected_blocks`` is a histogram, so
  its ``_bucket``/``_sum``/``_count`` samples count) — non-negative,
  with ``serve_sparse_topk`` positive and selected blocks never
  exceeding candidate blocks; ``sparse_select`` instants need numeric
  ``selected``/``candidate`` args
* the name-encoded ``serve_replica_{i}_*`` family (the router's
  per-replica instruments — the registry has no labels by design) is
  all-or-nothing across BOTH dimensions: replica ids must be contiguous
  from 0, every id must export every suffix, no member may be negative,
  and the per-replica ``submitted_total`` / ``completed_total`` must sum
  to the fleet-wide ``serve_requests_{submitted,completed}_total``

Exit status 0 and a one-line summary on success; every violation is
printed and the exit status is 1.  CI's ``obs`` job runs this against a
freshly traced serve run.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
from collections import defaultdict
from pathlib import Path

ERRORS: list = []

_PH_KNOWN = frozenset("XBEiCM")
_META_NAMES = frozenset({"process_name", "process_labels",
                         "process_sort_index", "thread_name",
                         "thread_sort_index"})
_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
#: required numeric args per prefix-cache instant (serve_loop/core.cache emit)
_CACHE_INSTANT_ARGS = {"prefix_hit": ("tokens", "blocks"),
                       "prefix_miss": ("tokens",),
                       "cow": ("block", "copy"),
                       "sparse_select": ("selected", "candidate")}
#: the complete serve_prefix_cache_* instrument family — all-or-nothing
_PC_FAMILY = ("serve_prefix_cache_hits_total",
              "serve_prefix_cache_misses_total",
              "serve_prefix_cache_hit_tokens_total",
              "serve_prefix_cache_cow_total",
              "serve_prefix_cache_blocks_retained",
              "serve_prefix_cache_blocks_cached")
#: the complete serve_pool_* instrument family — all-or-nothing
_POOL_FAMILY = ("serve_pool_blocks_used",
                "serve_pool_quantized",
                "serve_pool_bytes_per_token",
                "serve_pool_allocated_bytes")
#: the complete serve_sparse_* instrument family — all-or-nothing (absent
#: entirely in dense runs; serve_sparse_selected_blocks is a histogram, so
#: its _bucket/_sum/_count samples belong to the family too)
_SPARSE_FAMILY = ("serve_sparse_topk",
                  "serve_sparse_recent",
                  "serve_sparse_steps_total",
                  "serve_sparse_selected_blocks_total",
                  "serve_sparse_candidate_blocks_total",
                  "serve_sparse_selected_blocks")
#: per-replica suffixes the router exports for EVERY replica id
#: (mirrors runtime/router.py::REPLICA_METRIC_SUFFIXES)
_REPLICA_SUFFIXES = ("submitted_total", "completed_total", "waiting",
                     "resident", "blocks_used")
_REPLICA_RE = re.compile(r"^serve_replica_(\d+)_([a-z_]+)$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)(?:\s+\d+)?$")


def err(msg: str) -> None:
    ERRORS.append(msg)
    print(f"FAIL: {msg}")


def _num(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v) \
        and not isinstance(v, bool)


# ---------------------------------------------------------------------------
# trace-event JSON
# ---------------------------------------------------------------------------

def check_trace(path: Path) -> int:
    try:
        data = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        err(f"{path}: unreadable or invalid JSON ({e})")
        return 0
    if not isinstance(data, dict) or not isinstance(
            data.get("traceEvents"), list):
        err(f"{path}: missing traceEvents list envelope")
        return 0
    events = data["traceEvents"]

    named_tids = set()                       # (pid, tid) with thread_name
    used_tids = set()                        # (pid, tid) carrying events
    be_stacks = defaultdict(list)            # (pid, tid) -> open B names
    x_spans = defaultdict(list)              # (pid, tid) -> (start, end, name)
    for i, e in enumerate(events):
        where = f"{path}: traceEvents[{i}]"
        if not isinstance(e, dict):
            err(f"{where}: not an object")
            continue
        ph = e.get("ph")
        if ph not in _PH_KNOWN:
            err(f"{where}: unknown ph {ph!r}")
            continue
        if not isinstance(e.get("name"), str) or not e["name"]:
            err(f"{where}: missing name")
            continue
        key = (e.get("pid"), e.get("tid"))
        if ph == "M":
            if e["name"] not in _META_NAMES:
                err(f"{where}: unknown metadata name {e['name']!r}")
            if e["name"] == "thread_name":
                named_tids.add(key)
            continue
        ts = e.get("ts")
        if not _num(ts) or ts < 0:
            err(f"{where}: bad ts {ts!r}")
            continue
        used_tids.add(key)
        if ph == "X":
            dur = e.get("dur")
            if not _num(dur) or dur < 0:
                err(f"{where}: X span with bad dur {dur!r}")
                continue
            x_spans[key].append((ts, ts + dur, e["name"]))
        elif ph == "B":
            be_stacks[key].append(e["name"])
        elif ph == "E":
            stack = be_stacks[key]
            if not stack:
                err(f"{where}: E {e['name']!r} with no open B on tid {key}")
            elif stack[-1] != e["name"]:
                err(f"{where}: E {e['name']!r} cross-nests open B "
                    f"{stack[-1]!r} on tid {key}")
                stack.pop()
            else:
                stack.pop()
        elif ph == "C":
            args = e.get("args")
            if not isinstance(args, dict) or not _num(args.get("value")):
                err(f"{where}: counter without numeric args.value")
        elif ph == "i":
            if e.get("s", "t") not in ("g", "p", "t"):
                err(f"{where}: instant with bad scope {e.get('s')!r}")
            spec = _CACHE_INSTANT_ARGS.get(e["name"])
            if spec is not None:
                iargs = e.get("args") if isinstance(e.get("args"), dict) else {}
                for field in spec:
                    if not _num(iargs.get(field)):
                        err(f"{where}: {e['name']} instant missing numeric "
                            f"args.{field}")
                if e["name"] == "prefix_hit" and \
                        _num(iargs.get("tokens")) and iargs["tokens"] <= 0:
                    err(f"{where}: prefix_hit with non-positive tokens "
                        f"{iargs['tokens']!r}")
                if e["name"] == "cow" and _num(iargs.get("block")) and \
                        iargs.get("block") == iargs.get("copy"):
                    err(f"{where}: cow instant copies block "
                        f"{iargs['block']!r} onto itself")

    for key, stack in sorted(be_stacks.items()):
        if stack:
            err(f"{path}: tid {key} ends with unclosed B events {stack} "
                f"(every begin needs a matching end)")
    for key in sorted(used_tids - named_tids):
        err(f"{path}: tid {key} carries events but has no thread_name "
            f"metadata")

    # X proper nesting per track: sweep spans sorted by (start, -end); each
    # span must be contained by or disjoint from every enclosing span.
    for key, spans in sorted(x_spans.items()):
        stack = []                           # (start, end, name) enclosing
        for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
            while stack and stack[-1][1] <= start:
                stack.pop()
            if stack and end > stack[-1][1]:
                err(f"{path}: span {name!r} [{start:.1f},{end:.1f}] on tid "
                    f"{key} partially overlaps {stack[-1][2]!r} "
                    f"[{stack[-1][0]:.1f},{stack[-1][1]:.1f}]")
                continue
            stack.append((start, end, name))
    return len(events)


# ---------------------------------------------------------------------------
# Prometheus text format
# ---------------------------------------------------------------------------

def _parse_value(s: str):
    if s == "+Inf":
        return math.inf
    if s == "-Inf":
        return -math.inf
    try:
        return float(s)
    except ValueError:
        return None


def check_metrics(path: Path) -> int:
    try:
        text = path.read_text(encoding="utf-8")
    except OSError as e:
        err(f"{path}: unreadable ({e})")
        return 0
    types: dict = {}
    samples = []                             # (name, labels-dict, value)
    for ln, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                err(f"{path}:{ln}: malformed TYPE line {line!r}")
                continue
            types[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue                         # HELP / comments
        m = _SAMPLE_RE.match(line)
        if not m:
            err(f"{path}:{ln}: unparseable sample line {line!r}")
            continue
        value = _parse_value(m.group("value"))
        if value is None or (value != value):
            err(f"{path}:{ln}: bad sample value {m.group('value')!r}")
            continue
        labels = {}
        for item in filter(None, (m.group("labels") or "").split(",")):
            if "=" not in item:
                err(f"{path}:{ln}: malformed label {item!r}")
                continue
            k, _, v = item.partition("=")
            labels[k.strip()] = v.strip().strip('"')
        samples.append((m.group("name"), labels, value))

    by_name = defaultdict(list)
    for name, labels, value in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", name) \
            if re.search(r"_(bucket|sum|count)$", name) else name
        owner = base if base in types else name
        if owner not in types:
            err(f"{path}: sample {name!r} has no # TYPE block")
            continue
        by_name[owner].append((name, labels, value))

    for owner, rows in sorted(by_name.items()):
        if types.get(owner) != "histogram":
            continue
        buckets = sorted(
            ((math.inf if r[1]["le"] == "+Inf" else float(r[1]["le"]), r[2])
             for r in rows if r[0] == f"{owner}_bucket" and "le" in r[1]),
            key=lambda t: t[0])
        count = next((r[2] for r in rows if r[0] == f"{owner}_count"), None)
        has_sum = any(r[0] == f"{owner}_sum" for r in rows)
        if not buckets or buckets[-1][0] != math.inf:
            err(f"{path}: histogram {owner} missing +Inf bucket")
            continue
        if count is None or not has_sum:
            err(f"{path}: histogram {owner} missing _count or _sum")
            continue
        cum = [c for _, c in buckets]
        if any(b > a for a, b in zip(cum[1:], cum)):
            err(f"{path}: histogram {owner} buckets not cumulative: {cum}")
        if buckets[-1][1] != count:
            err(f"{path}: histogram {owner} +Inf bucket {buckets[-1][1]} "
                f"!= _count {count}")

    # serve_prefix_cache_* family: all-or-nothing and self-consistent
    pc_vals = {n: v for n, _, v in samples if n in _PC_FAMILY}
    stray = sorted(n for n, _, _ in samples
                   if n.startswith("serve_prefix_cache_")
                   and n not in _PC_FAMILY)
    for n in stray:
        err(f"{path}: unknown serve_prefix_cache_* instrument {n!r}")
    if pc_vals:
        for n in _PC_FAMILY:
            if n not in pc_vals:
                err(f"{path}: serve_prefix_cache_* family incomplete — "
                    f"missing {n}")
        for n, v in sorted(pc_vals.items()):
            if v < 0:
                err(f"{path}: {n} is negative ({v})")
        if pc_vals.get("serve_prefix_cache_hits_total") == 0 and \
                pc_vals.get("serve_prefix_cache_hit_tokens_total", 0) > 0:
            err(f"{path}: hit_tokens_total > 0 with hits_total == 0")

    # serve_pool_* family: all-or-nothing and self-consistent
    pool_vals = {n: v for n, _, v in samples if n in _POOL_FAMILY}
    for n in sorted(n for n, _, _ in samples
                    if n.startswith("serve_pool_") and n not in _POOL_FAMILY):
        err(f"{path}: unknown serve_pool_* instrument {n!r}")
    if pool_vals:
        for n in _POOL_FAMILY:
            if n not in pool_vals:
                err(f"{path}: serve_pool_* family incomplete — missing {n}")
        for n, v in sorted(pool_vals.items()):
            if v < 0:
                err(f"{path}: {n} is negative ({v})")
        q = pool_vals.get("serve_pool_quantized")
        if q is not None and q not in (0.0, 1.0):
            err(f"{path}: serve_pool_quantized must be 0 or 1, got {q}")
        bpt = pool_vals.get("serve_pool_bytes_per_token")
        if bpt is not None and bpt <= 0:
            err(f"{path}: serve_pool_bytes_per_token must be positive, "
                f"got {bpt}")

    # serve_sparse_* family: all-or-nothing and self-consistent
    def _sparse_base(n):
        return re.sub(r"_(bucket|sum|count)$", "", n) \
            if n.startswith("serve_sparse_selected_blocks_") else n
    sparse_vals = {n: v for n, _, v in samples
                   if n in _SPARSE_FAMILY and types.get(n) != "histogram"}
    sparse_seen = {_sparse_base(n) for n, _, _ in samples
                   if n.startswith("serve_sparse_")}
    for n in sorted(sparse_seen - set(_SPARSE_FAMILY)):
        err(f"{path}: unknown serve_sparse_* instrument {n!r}")
    if sparse_seen:
        for n in _SPARSE_FAMILY:
            if n not in sparse_seen:
                err(f"{path}: serve_sparse_* family incomplete — missing {n}")
        for n, v in sorted(sparse_vals.items()):
            if v < 0:
                err(f"{path}: {n} is negative ({v})")
        if sparse_vals.get("serve_sparse_topk", 1) <= 0:
            err(f"{path}: serve_sparse_topk must be positive when the "
                f"sparse family is exported")
        sel = sparse_vals.get("serve_sparse_selected_blocks_total")
        cand = sparse_vals.get("serve_sparse_candidate_blocks_total")
        if sel is not None and cand is not None and sel > cand:
            err(f"{path}: sparse selected blocks ({sel}) exceed candidate "
                f"blocks ({cand})")

    # serve_replica_{i}_* family: all-or-nothing over ids × suffixes
    replica = {}                             # (id, suffix) -> value
    for n, _, v in samples:
        if not n.startswith("serve_replica_"):
            continue
        m = _REPLICA_RE.match(n)
        if not m or m.group(2) not in _REPLICA_SUFFIXES:
            err(f"{path}: unknown serve_replica_* instrument {n!r}")
            continue
        replica[(int(m.group(1)), m.group(2))] = v
    if replica:
        ids = sorted({i for i, _ in replica})
        if ids != list(range(len(ids))):
            err(f"{path}: serve_replica_* ids not contiguous from 0: {ids}")
        for i in ids:
            for suffix in _REPLICA_SUFFIXES:
                if (i, suffix) not in replica:
                    err(f"{path}: serve_replica_* family incomplete — "
                        f"replica {i} missing {suffix}")
        for (i, suffix), v in sorted(replica.items()):
            if v < 0:
                err(f"{path}: serve_replica_{i}_{suffix} is negative ({v})")
        globals_ = {n: v for n, _, v in samples
                    if n in ("serve_requests_submitted_total",
                             "serve_requests_completed_total")}
        for suffix, gname in (("submitted_total",
                               "serve_requests_submitted_total"),
                              ("completed_total",
                               "serve_requests_completed_total")):
            total = sum(v for (i, s), v in replica.items() if s == suffix)
            if gname in globals_ and total != globals_[gname]:
                err(f"{path}: sum of serve_replica_*_{suffix} ({total}) != "
                    f"{gname} ({globals_[gname]})")
    return len(samples)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace-event JSON to validate")
    ap.add_argument("--metrics", default="",
                    help="also validate this Prometheus text-format file")
    args = ap.parse_args(argv)

    ERRORS.clear()                           # fresh verdict per invocation
    n_events = check_trace(Path(args.trace))
    summary = f"{args.trace}: {n_events} events"
    if args.metrics:
        n_samples = check_metrics(Path(args.metrics))
        summary += f"; {args.metrics}: {n_samples} samples"
    if ERRORS:
        print(f"{len(ERRORS)} violation(s) — {summary}")
        return 1
    print(f"OK — {summary}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
