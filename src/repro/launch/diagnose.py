"""Dry-run profiler: top live buffers + per-collective breakdown for a cell.

The "profile" available without hardware is the partitioned HLO — this tool
is the lens the §Perf hypothesis loop looks through.

  python -m repro.launch.diagnose --arch tinyllama_1_1b --shape train_4k

Offline trace analysis (docs/observability.md) — summarise a Chrome trace
written by ``launch/serve.py --trace``:

  python -m repro.launch.diagnose trace-summary trace.json [--top 8]

prints the phase-time table (self time per phase, the step's own
remainder as ``other``), swap traffic, the per-request lifecycle table
(TTFT / residency / retirement reason), the most-preempted requests,
and an ASCII pool-occupancy timeline — the terminal view of what Perfetto
renders graphically.  Traces from a data-parallel run (``--dp N``) add a
per-replica occupancy sparkline block (from the router's ``r{i}_``-prefixed
pool counters) and a replica-imbalance line (max/min requests admitted,
from the ``route`` instants).
"""
import argparse
import json
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path

import numpy as np

_SPARK = " ▁▂▃▄▅▆▇█"


def _sparkline(samples, width):
    """Bin (ts, value) samples into ``width`` columns of block glyphs; each
    column shows the max value seen in its time bin (last value carried
    forward through empty bins — counters hold between updates)."""
    if not samples:
        return "", 0.0
    t0, t1 = samples[0][0], samples[-1][0]
    span = max(t1 - t0, 1e-9)
    peak = max(v for _, v in samples) or 1.0
    cols = [None] * width
    for ts, v in samples:
        c = min(int((ts - t0) / span * width), width - 1)
        cols[c] = v if cols[c] is None else max(cols[c], v)
    out, last = [], 0.0
    for c in cols:
        last = last if c is None else c
        out.append(_SPARK[round(last / peak * (len(_SPARK) - 1))])
    return "".join(out), peak


def _self_ms(spans):
    """Self milliseconds of nested ``X`` spans on one track: each span's
    duration less its direct children's.  → list of (cat, name, self_ms)."""
    out, stack = [], []                      # stack: [cat, name, end, own]
    for ts, dur, cat, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][2] <= ts:
            c, n, _, own = stack.pop()
            out.append((c, n, own / 1e3))
        if stack:
            stack[-1][3] -= dur
        stack.append([cat, name, ts + dur, dur])
    out.extend((c, n, own / 1e3) for c, n, _, own in stack)
    return out


def trace_summary(argv):
    ap = argparse.ArgumentParser(
        prog="diagnose trace-summary",
        description="summarise a Chrome trace written by serve.py --trace")
    ap.add_argument("trace", help="trace-event JSON path")
    ap.add_argument("--top", type=int, default=8,
                    help="rows in the preempted/requests tables")
    ap.add_argument("--width", type=int, default=64,
                    help="columns in the occupancy timeline")
    args = ap.parse_args(argv)

    events = json.loads(Path(args.trace).read_text())["traceEvents"]
    spans = defaultdict(lambda: [0.0, 0])     # (cat, name) -> [ms, calls]
    x_by_tid = defaultdict(list)              # tid -> (ts, dur, cat, name)
    reqs = defaultdict(dict)                  # uid -> lifecycle timestamps
    preempts = Counter()
    occupancy, slots = [], []
    replica_occ = defaultdict(list)           # replica id -> (ts, blocks)
    routed = Counter()                        # replica id -> admissions
    for e in events:
        ph, name, uid = e.get("ph"), e.get("name", ""), \
            (e.get("args") or {}).get("uid")
        if ph == "X":
            x_by_tid[(e.get("pid"), e.get("tid"))].append(
                (e["ts"], e.get("dur", 0.0), e.get("cat", "event"), name))
        elif ph == "i" and uid is not None:
            if name in ("submit", "first_token", "retire"):
                reqs[uid][name] = e["ts"]
                if name == "retire":
                    reqs[uid]["reason"] = e["args"].get("reason", "?")
                    reqs[uid]["tokens"] = e["args"].get("tokens", 0)
            elif name == "preempt":
                preempts[uid] += 1
        elif ph == "C" and name == "pool_blocks_used":
            occupancy.append((e["ts"], float(e["args"]["value"])))
        elif ph == "C" and name == "slots_occupied":
            slots.append((e["ts"], float(e["args"]["value"])))
        elif ph == "C":
            m = re.match(r"r(\d+)_pool_blocks_used$", name)
            if m:
                replica_occ[int(m.group(1))].append(
                    (e["ts"], float(e["args"]["value"])))
        if ph == "i" and name == "route":
            routed[(e.get("args") or {}).get("replica", "?")] += 1

    # phases nest (a swap inside chain growth): each is charged its self
    # time, and a step's own remainder is the ``other`` phase, so the table
    # matches ``ServeReport.phase_ms``
    for track in x_by_tid.values():
        for cat, name, ms in _self_ms(track):
            if cat == "step":
                cat, name = "phase", "other"
            agg = spans[(cat, name)]
            agg[0] += ms
            agg[1] += 1
    for cat, title in (("phase", "phase time"), ("swap", "swap traffic")):
        rows = sorted(((n, ms, c) for (ct, n), (ms, c) in spans.items()
                       if ct == cat), key=lambda r: -r[1])
        if not rows:
            continue
        total = sum(ms for _, ms, _ in rows) or 1.0
        print(f"== {title} ==")
        for n, ms, c in rows:
            print(f"  {n:<14s} {ms:9.1f}ms  {c:5d} calls  "
                  f"{100 * ms / total:3.0f}%")

    done = sorted(reqs.items())
    if done:
        print(f"== requests ({len(done)} submitted, "
              f"{sum('retire' in r for _, r in done)} retired) ==")
        print(f"  {'uid':>4s} {'ttft_ms':>8s} {'total_ms':>9s} "
              f"{'tokens':>6s} {'reason':<7s} preempts")
        for uid, r in done[:args.top]:
            ttft = (f"{(r['first_token'] - r['submit']) / 1e3:8.1f}"
                    if "first_token" in r and "submit" in r else f"{'—':>8s}")
            total = (f"{(r['retire'] - r['submit']) / 1e3:9.1f}"
                     if "retire" in r and "submit" in r else f"{'—':>9s}")
            print(f"  {uid:>4d} {ttft} {total} {r.get('tokens', 0):>6} "
                  f"{r.get('reason', 'live'):<7s} {preempts.get(uid, 0)}")
        if len(done) > args.top:
            print(f"  ... {len(done) - args.top} more")
    if preempts:
        worst = ", ".join(f"req{u}×{c}" for u, c in
                          preempts.most_common(args.top))
        print(f"== top preempted requests ==\n  {worst} "
              f"({sum(preempts.values())} evictions total)")

    for samples, title, unit in ((occupancy, "pool occupancy", "blocks"),
                                 (slots, "slots occupied", "slots")):
        line, peak = _sparkline(samples, args.width)
        if line:
            t_ms = (samples[-1][0] - samples[0][0]) / 1e3
            print(f"== {title} (peak {peak:.0f} {unit} over {t_ms:.0f}ms) ==")
            print(f"  [{line}]")

    if replica_occ:                           # data-parallel run (router)
        print(f"== per-replica pool occupancy ({len(replica_occ)} "
              f"replicas) ==")
        for i in sorted(replica_occ):
            line, peak = _sparkline(replica_occ[i], args.width)
            print(f"  r{i} [{line}] peak {peak:.0f} blocks, "
                  f"{routed.get(i, 0)} routed")
    if routed:
        counts = [routed.get(i, 0) for i in sorted(routed)]
        lo, hi = min(counts), max(counts)
        ratio = "inf" if lo == 0 else f"{hi / lo:.2f}"
        print(f"== replica imbalance ==\n  routed={counts} max/min={ratio} "
              f"(1.00 = perfectly even)")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "trace-summary":
        return trace_summary(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-elitekv", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--scan", action="store_true", help="use scan lowering")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)

    # importing dryrun sets its placeholder-device XLA_FLAGS (only this
    # subcommand needs them; trace-summary leaves the environment alone)
    from repro.launch import dryrun
    res, compiled, cfg = dryrun.lower_cell(
        args.arch, args.shape, args.multi_pod, elitekv=not args.no_elitekv,
        seq_parallel=not args.no_seq_parallel, unroll=True,
        param_dtype=args.param_dtype, return_artifacts=True)
    txt = compiled.as_text()

    print(f"peak/device: {res['memory']['peak_estimate_bytes']/2**30:.2f} GiB  "
          f"(temp {res['memory']['temp_bytes']/2**30:.2f}, "
          f"args {res['memory']['argument_bytes']/2**30:.2f})")
    print(f"flops/device: {res['flops_per_device']:.3e}   "
          f"bytes/device: {res['bytes_accessed_per_device']:.3e}")
    print(f"collectives/device: {res['collective_bytes_per_device']/2**30:.2f} GiB")
    for k, v in sorted(res["collectives"].items()):
        print(f"  {k:20s} n={v['count']:4d}  {v['bytes']/2**30:7.2f} GiB")

    # biggest single collectives
    print("\n== largest collectives ==")
    rows = []
    for line in txt.splitlines():
        m = dryrun._COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        nbytes = 0
        for sm in dryrun._SHAPE_RE.finditer(m.group(1)):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in dryrun._DTYPE_BYTES:
                continue
            n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
            nbytes += n * dryrun._DTYPE_BYTES[dt]
        meta = re.search(r'op_name="([^"]*)"', line)
        rows.append((nbytes, m.group(2), m.group(1)[:60],
                     (meta.group(1)[-80:] if meta else "")))
    rows.sort(reverse=True)
    agg = Counter()
    names = {}
    for nbytes, op, shp, name in rows:
        key = (op, shp)
        agg[key] += nbytes
        names.setdefault(key, name)
    for (op, shp), b in agg.most_common(args.top):
        print(f"  {b/2**30:7.2f} GiB  {op:18s} {shp}")
        print(f"           └─ {names[(op, shp)]}")

    # biggest shapes overall
    print("\n== largest tensor shapes in HLO ==")
    sizes = Counter()
    counts = Counter()
    for m in re.finditer(r"(f32|bf16|s32|u32|f16|s8|u8|pred)\[([\d,]+)\]", txt):
        dims = [int(x) for x in m.group(2).split(",")]
        b = int(np.prod(dims)) * dryrun._DTYPE_BYTES[m.group(1)]
        key = f"{m.group(1)}[{m.group(2)}]"
        sizes[key] = b
        counts[key] += 1
    for k, v in sorted(sizes.items(), key=lambda kv: -kv[1])[:args.top]:
        print(f"  {v/2**30:7.2f} GiB  ×{counts[k]:4d}  {k}")


if __name__ == "__main__":
    main()
